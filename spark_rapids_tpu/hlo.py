"""Per-fusion HLO attribution: which instructions own the bytes.

The cost plane (xla_cost.py) proves byte amplification PER COMPILE SITE
— the agg shape's programs touch 19.4 GB of XLA-reported bytes against a
772 MB layout bound — but a site is a whole program, and "the program
materializes 25x its working set" names no culprit. The TPU analog of
the reference profiling-tool's kernel-level attribution is the HLO
fusion: every ``jax.stages.Compiled`` the probe harvests exposes its
optimized HLO as text (``as_text()``), and the shape annotations on each
instruction (``f32[4096,1024]{1,0}``) are enough to attribute operand
and output bytes per top-level instruction WITHOUT any new dependency.

This module parses that text — tolerantly: backends disagree on dialect
(``%``-prefixed names, layout suffixes like ``{1,0:T(8,128)}``, inline
operand shapes), and an unknown op must degrade the reported parse
coverage, never fail a query — rolls attributions up per fusion /
top-level instruction of the entry computation, and classifies the
idioms known to be the amplifiers:

  * ``scatter`` / ``scatter-add`` — a scatter instruction, or the CPU
    dialect's while-loop lowering (a fused ``dynamic-update-slice``
    accumulator: one element updated per trip, the whole buffer alive);
  * ``one-hot dot`` — a dot fed by a broadcast/iota-compare one-hot
    expansion (the bucket_reduce matmul lowering's signature);
  * ``dot`` / ``conv`` — plain MXU work;
  * ``gather`` / ``sort`` / ``reduce`` / ``transpose/copy`` — data
    movement families;
  * ``collective`` — all-reduce / all-to-all / all-gather /
    reduce-scatter / collective-permute (the mesh exchange surfaces).

Accounting model (deliberately the layout-level one): an instruction
costs its output bytes plus its operands' shape bytes; parameters,
constants, tuples, get-tuple-elements and bitcasts cost zero (XLA's
HloCostAnalysis charges those reads to the consumer, verified against
``cost_analysis()['bytes accessed']`` — a plain dot program matches it
exactly). XLA additionally applies *utilization* weighting inside
fusions and control-flow bodies (a fused dynamic-slice of one element
counts 4 bytes, not the whole operand), so totals can legitimately
diverge; every summary therefore carries ``coverage`` (fraction of
entry instructions fully parsed) and ``accounted_frac`` (our total /
XLA's bytes-accessed, when the backend reported one) so a shortfall is
explained, never silent.

Zero-overhead contract: harvesting rides INSIDE xla_cost.CostProbe's
gated first call — with events + obs off (and FORCE_HARVEST unset) the
probe never runs, ``as_text()`` is never called, and nothing here
executes (tests/test_hlo.py pins this with a spy, the xla_cost
contract). A parse failure records nothing and never fails a query.
"""
from __future__ import annotations

import re
import threading
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from . import events as _events
from .conf import conf

HLO_TOP_K = conf(
    "spark.rapids.tpu.hlo.topK", 5,
    "Fusions/instructions reported per compiled program in the "
    "hlo_summary event's top-fusions list (ranked by attributed bytes). "
    "The full per-instruction table is never logged — only the top-K "
    "plus the scatter count, largest-output producer, and parse "
    "coverage.", conf_type=int,
    check=lambda v: None if v >= 1 else "must be >= 1")

#: bytes per element by HLO primitive type; unknown dtypes (token,
#: opaque, f8 variants not listed) fall back via prefix rules in
#: :func:`_dtype_bytes`
_DTYPE_BYTES: Dict[str, int] = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "tf32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

#: opcodes whose bytes XLA charges to the consumer, not the producer
#: (HloCostAnalysis: parameters/constants are materialized inputs, GTE/
#: tuple/bitcast are pointer shuffling) — attributing them here would
#: double-count every buffer
_ZERO_BYTE_OPS = frozenset((
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "after-all", "iota",
))

_COLLECTIVES = frozenset((
    "all-reduce", "all-to-all", "all-gather", "reduce-scatter",
    "collective-permute", "all-reduce-start", "all-gather-start",
))


def _dtype_bytes(dtype: str) -> Optional[int]:
    b = _DTYPE_BYTES.get(dtype)
    if b is not None:
        return b
    if dtype.startswith("f8"):
        return 1
    if dtype in ("token", "opaque"):
        return 0
    return None


_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,<=\s]*)\]")


def _skip_filler(s: str, i: int) -> int:
    """Advance past spaces and the ``/*index=N*/`` element comments long
    tuples carry in real dumps."""
    while i < len(s):
        if s[i] == " ":
            i += 1
        elif s.startswith("/*", i):
            j = s.find("*/", i)
            if j < 0:
                return len(s)
            i = j + 2
        else:
            break
    return i


def _parse_shape(s: str, i: int) -> Tuple[int, int, int]:
    """Parse one shape starting at ``s[i]`` -> (nbytes, nelems, end).

    Handles tuples ``(f32[2]{0}, s32[])``, layout suffixes with tiling
    ``{1,0:T(8,128)(2,1)S(3)}`` (scanned to the matching brace — TPU
    dialect), and bounded-dynamic dims ``s32[<=10]``. Raises ValueError
    on anything else so the caller can count the line against coverage.
    """
    i = _skip_filler(s, i)
    if i < len(s) and s[i] == "(":
        total_b = total_e = 0
        i += 1
        while True:
            b, e, i = _parse_shape(s, i)
            total_b += b
            total_e += e
            i = _skip_filler(s, i)
            if i < len(s) and s[i] == ",":
                i += 1
                continue
            if i < len(s) and s[i] == ")":
                return total_b, total_e, i + 1
            raise ValueError(f"unterminated tuple shape at {i}")
    m = _SHAPE_RE.match(s, i)
    if m is None:
        # dimensionless types: token[] handled above; bare "token"
        if s.startswith("token", i):
            return 0, 0, i + 5
        raise ValueError(f"no shape at {i}: {s[i:i + 24]!r}")
    per = _dtype_bytes(m.group(1))
    if per is None:
        raise ValueError(f"unknown dtype {m.group(1)!r}")
    elems = 1
    dims = m.group(2).strip()
    if dims:
        for d in dims.split(","):
            d = d.strip().lstrip("<=").strip()
            if not d.isdigit():
                raise ValueError(f"bad dim {d!r}")
            elems *= int(d)
    j = m.end()
    if j < len(s) and s[j] == "{":
        # layout annotation: may nest parens (tiling) but never braces
        k = s.find("}", j)
        if k < 0:
            raise ValueError("unterminated layout")
        j = k + 1
    return per * elems, elems, j


class Instr:
    __slots__ = ("name", "opcode", "out_bytes", "out_elems", "operands",
                 "called", "ok", "target")

    def __init__(self, name: str, opcode: str, out_bytes: int,
                 out_elems: int, operands: List[str], called: List[str],
                 ok: bool, target: Optional[str] = None):
        self.name = name
        self.opcode = opcode
        self.out_bytes = out_bytes
        self.out_elems = out_elems
        self.operands = operands    # operand instruction names
        self.called = called        # computations via calls=/body=/...
        self.ok = ok
        self.target = target        # custom-call target, when present


_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+")
_TARGET_RE = re.compile(r'custom_call_target="([^"]*)"')
_CALLED_RE = re.compile(
    r"(?:calls|to_apply|body|condition|true_computation|"
    r"false_computation)=%?([\w.\-]+)")
_CALLED_LIST_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_NAME_RE = re.compile(r"%?([A-Za-z_][\w.\-]*)$")


def _split_top(s: str) -> List[str]:
    """Split on top-level commas (ignoring (), {}, [] nesting)."""
    parts, depth, start = [], 0, 0
    for i, ch in enumerate(s):
        if ch in "({[":
            depth += 1
        elif ch in ")}]":
            depth -= 1
        elif ch == "," and depth == 0:
            parts.append(s[start:i])
            start = i + 1
    parts.append(s[start:])
    return parts


def _balanced(s: str, i: int) -> int:
    """Index just past the ``)`` matching the ``(`` at ``s[i]``."""
    depth = 0
    for j in range(i, len(s)):
        if s[j] == "(":
            depth += 1
        elif s[j] == ")":
            depth -= 1
            if depth == 0:
                return j + 1
    raise ValueError("unbalanced parens")


def _parse_instruction(line: str) -> Optional[Instr]:
    m = _INSTR_RE.match(line)
    if m is None:
        return None
    name = m.group(1)
    rest = line[m.end():]
    try:
        out_b, out_e, j = _parse_shape(rest, 0)
    except ValueError:
        return Instr(name, "?", 0, 0, [], [], ok=False)
    om = re.match(r"\s*([\w\-]+)", rest[j:])
    if om is None:
        return Instr(name, "?", out_b, out_e, [], [], ok=False)
    opcode = om.group(1)
    tail = rest[j + om.end():]
    operands: List[str] = []
    attrs = tail
    lp = tail.find("(")
    if lp >= 0:
        try:
            rp = _balanced(tail, lp)
        except ValueError:
            return Instr(name, opcode, out_b, out_e, [], [], ok=False)
        attrs = tail[rp:]
        if opcode not in ("constant", "parameter"):
            for piece in _split_top(tail[lp + 1:rp - 1]):
                piece = piece.strip()
                if not piece:
                    continue
                nm = _NAME_RE.search(piece.split()[-1])
                if nm is not None:
                    operands.append(nm.group(1))
    called = [cm.group(1) for cm in _CALLED_RE.finditer(attrs)]
    for cm in _CALLED_LIST_RE.finditer(attrs):
        called.extend(p.strip().lstrip("%") for p in cm.group(1).split(",")
                      if p.strip())
    tm = _TARGET_RE.search(attrs)
    return Instr(name, opcode, out_b, out_e, operands, called, ok=True,
                 target=tm.group(1) if tm else None)


class Module:
    """One parsed HLO module: computations, a module-wide name->Instr
    map, and which computations are absorbed into callers (fused
    bodies / reduce regions are accounted at their call site)."""

    def __init__(self):
        self.computations: Dict[str, List[Instr]] = {}
        self.entry: Optional[str] = None
        self.by_name: Dict[str, Instr] = {}
        self.unparsed: Dict[str, int] = {}

    def instrs(self, comp: str) -> List[Instr]:
        return self.computations.get(comp, [])


def parse_hlo_module(text: str) -> Module:
    mod = Module()
    comp: Optional[str] = None
    for raw in text.splitlines():
        line = raw.strip()
        if not line or line.startswith("//"):
            continue
        if line.startswith("HloModule"):
            continue
        if line == "}":
            comp = None
            continue
        if line.endswith("{") and " = " not in line.split("{", 1)[0]:
            head = line[:-1].strip()
            entry = head.startswith("ENTRY")
            if entry:
                head = head[len("ENTRY"):].strip()
            name = head.split("(", 1)[0].strip().lstrip("%").strip()
            if not name:
                continue
            comp = name
            mod.computations.setdefault(comp, [])
            if entry:
                mod.entry = comp
            continue
        if comp is None:
            continue
        instr = _parse_instruction(line)
        if instr is None:
            mod.unparsed[comp] = mod.unparsed.get(comp, 0) + 1
            continue
        mod.computations[comp].append(instr)
        mod.by_name[instr.name] = instr
    return mod


# ---------------------------------------------------------------------------
# Attribution + classification
# ---------------------------------------------------------------------------
def _opcode_bag(mod: Module, comp: str, seen: Optional[set] = None
                ) -> set:
    """All opcodes reachable from a computation (recursing through
    calls=/body=/to_apply=), for classifying composite instructions."""
    if seen is None:
        seen = set()
    if comp in seen:
        return set()
    seen.add(comp)
    bag: set = set()
    for ins in mod.instrs(comp):
        bag.add(ins.opcode)
        for c in ins.called:
            bag |= _opcode_bag(mod, c, seen)
    return bag


def _dus_update_sizes(mod: Module, ins: Instr) -> List[Optional[int]]:
    """Element counts of the UPDATE operand of every dynamic-update-slice
    reachable from ``ins`` (None when the operand shape is unresolvable)
    — the discriminator between per-element scatter emulation and the
    tile-window writes of the radix-bin loop."""
    sizes: List[Optional[int]] = []
    seen: set = set()

    def walk(comp: str) -> None:
        if comp in seen:
            return
        seen.add(comp)
        for sub in mod.instrs(comp):
            if sub.opcode == "dynamic-update-slice":
                ref = (mod.by_name.get(sub.operands[1])
                       if len(sub.operands) > 1 else None)
                sizes.append(ref.out_elems if ref is not None else None)
            for c in sub.called:
                walk(c)

    for c in ins.called:
        walk(c)
    return sizes


#: custom-call targets that mark a hand-written Pallas/Mosaic kernel
_PALLAS_TARGETS = ("tpu_custom_call", "mosaic", "pallas", "triton")


def _feeds_iota(mod: Module, ins: Instr) -> bool:
    """True when an operand (looking through one tuple/fusion hop — the
    CPU while-lowering feeds its carry as one tuple) is an iota — the
    signature of a ROW-INDEX update stream, which data scatters never
    have."""
    def is_iota(ref: Optional[Instr]) -> bool:
        if ref is None:
            return False
        if ref.opcode == "iota":
            return True
        # jax 0.9's CPU dialect wraps the bare iota in an operand-less
        # kLoop fusion (%wrapped_iota = fusion(), calls=...)
        return (ref.opcode == "fusion" and not ref.operands and any(
            "iota" in _opcode_bag(mod, c) for c in ref.called))

    for op in ins.operands:
        ref = mod.by_name.get(op)
        if is_iota(ref):
            return True
        if ref is not None and ref.opcode in ("tuple", "fusion"):
            if any(is_iota(mod.by_name.get(op2)) for op2 in ref.operands):
                return True
    return False


def classify(mod: Module, ins: Instr) -> str:
    """Idiom name for one top-level instruction (priority order: the
    expensive amplifiers first, so a fusion that both scatters and
    transposes reads as the scatter it is)."""
    bag = {ins.opcode}
    for c in ins.called:
        bag |= _opcode_bag(mod, c)
    if ins.opcode == "custom-call" and ins.target and any(
            t in ins.target.lower() for t in _PALLAS_TARGETS):
        # a hand-written Pallas/Mosaic kernel owns its working set in
        # VMEM; it must never read as the scatter it replaced
        return "pallas"
    if "scatter" in bag:
        if "minimum" in bag and _feeds_iota(mod, ins):
            # a scatter-MIN whose update stream is an IOTA: the
            # direct-address join-table build writing each key's FIRST
            # build row (exec/join DIRECT tier) — its own class, so a
            # deliberately chosen DIRECT join doesn't read as the
            # scatter-add aggregation idiom (summarize_hlo pairs the
            # count table with it by shape)
            return "join-table"
        return "scatter-add" if "add" in bag else "scatter"
    if "dynamic-update-slice" in bag and ins.opcode in (
            "fusion", "while", "conditional"):
        sizes = _dus_update_sizes(mod, ins)
        if sizes and all(s is not None and s > 1 for s in sizes):
            # every update writes a multi-element TILE: the radix-bin
            # loop's sliding output window (ops/radix_bin.py), not the
            # per-element accumulator of the CPU scatter lowering —
            # misreading it as scatter would trip the --diff
            # scatter-appearance gate on the fix itself
            return "radix-bin"
        # the CPU dialect's scatter lowering: a while/fusion updating
        # one slice per step against a full-size accumulator
        if "minimum" in bag and _feeds_iota(mod, ins):
            return "join-table"  # the while-lowered first-table build
        return "scatter-add" if "add" in bag else "scatter"
    if bag & _COLLECTIVES:
        return "collective"
    if "convolution" in bag:
        return "conv"
    if "dot" in bag:
        # one-hot detection must see THROUGH operand producers: the
        # broadcast-compare expansion often compiles as a separate
        # fusion/call feeding the dot (one producer hop is enough).
        # The look-through bag is SEPARATE from the idiom bag above —
        # a dot merely consuming a scatter's/collective's output must
        # not inherit the producer's classification (or inflate
        # scatter_count with a second phantom scatter)
        look = set(bag)
        if not ({"compare", "broadcast", "iota"} <= look):
            for op in ins.operands:
                ref = mod.by_name.get(op)
                if ref is not None:
                    look.add(ref.opcode)
                    for c in ref.called:
                        look |= _opcode_bag(mod, c)
        if "compare" in look and ("broadcast" in look or "iota" in look):
            return "one-hot dot"
        return "dot"
    if "gather" in bag:
        return "gather"
    if "sort" in bag:
        return "sort"
    if "reduce-window" in bag:
        return "reduce-window"
    if "reduce" in bag:
        return "reduce"
    if ins.opcode in ("fusion", "call") and "compare" in bag and (
            "broadcast" in bag or "iota" in bag):
        # a materialized one-hot/mask expansion with no dot consuming it
        # in-fusion — the amplification idiom itself, given its own name
        return "one-hot expand"
    if ins.opcode in ("transpose", "copy") or (
            ins.opcode == "fusion" and bag & {"transpose", "copy"}):
        return "transpose/copy"
    return ins.opcode if ins.opcode != "fusion" else "fusion"


def _instr_bytes(mod: Module, ins: Instr) -> Tuple[int, int]:
    """(total attributed bytes, output bytes) for one instruction:
    output + resolvable operand shapes; zero for the consumer-charged
    opcodes (see _ZERO_BYTE_OPS)."""
    if ins.opcode in _ZERO_BYTE_OPS:
        return 0, 0
    total = ins.out_bytes
    for op in ins.operands:
        ref = mod.by_name.get(op)
        if ref is not None:
            total += ref.out_bytes
    return total, ins.out_bytes


def _instr_flops(mod: Module, ins: Instr,
                 seen: Optional[set] = None) -> float:
    """Shape-derived flop estimate: a dot is 2*M*N*K (K recovered from
    operand/output element counts), composites sum their bodies, plain
    elementwise ops count one per output element."""
    if ins.opcode in _ZERO_BYTE_OPS:
        return 0.0
    if ins.opcode == "dot":
        lhs = mod.by_name.get(ins.operands[0]) if ins.operands else None
        rhs = mod.by_name.get(ins.operands[1]) if len(ins.operands) > 1 \
            else None
        if lhs is not None and rhs is not None and ins.out_elems:
            k2 = (lhs.out_elems * rhs.out_elems) / ins.out_elems
            return 2.0 * ins.out_elems * (k2 ** 0.5)
        return 2.0 * ins.out_elems
    if ins.called:
        if seen is None:
            seen = set()
        total = 0.0
        for c in ins.called:
            if c in seen:
                continue
            seen.add(c)
            for sub in mod.instrs(c):
                total += _instr_flops(mod, sub, seen)
        return total
    return float(ins.out_elems)


def summarize_hlo(text: str, top_k: int = 5) -> Dict[str, Any]:
    """Per-fusion byte/flop attribution of one optimized HLO module.

    Returns the ``hlo_summary`` event payload (all plain JSON): entry
    instruction count, parse ``coverage`` (1.0 = every entry line
    yielded a full attribution), ``total_bytes``/``flops`` summed over
    the entry computation, module-wide ``scatter_count``, the ``top_k``
    instructions by attributed bytes (name, opcode, idiom class, bytes,
    output bytes), and the largest-output producer. Never raises on
    malformed/unknown input — degradation shows up as coverage < 1."""
    mod = parse_hlo_module(text)
    if mod.entry is None:
        return {"instructions": 0, "coverage": 0.0, "total_bytes": 0,
                "flops": 0, "scatter_count": 0, "top_fusions": [],
                "largest_output": None}
    entry = mod.instrs(mod.entry)
    bad = mod.unparsed.get(mod.entry, 0)
    n = len(entry) + bad
    rows: List[Dict[str, Any]] = []
    ok = 0
    total_bytes = 0
    flops = 0.0
    out_elems_by_name: Dict[str, int] = {}
    for ins in entry:
        if ins.ok:
            resolved = all(op in mod.by_name for op in ins.operands)
            ok += 1 if resolved else 0
        b, out_b = _instr_bytes(mod, ins)
        total_bytes += b
        flops += _instr_flops(mod, ins)
        if b > 0 or out_b > 0:
            rows.append({"name": ins.name, "op": ins.opcode,
                         "class": classify(mod, ins), "bytes": int(b),
                         "out_bytes": int(out_b)})
            out_elems_by_name[ins.name] = ins.out_elems
    # the direct-address join-table build is a PAIR of scatters: the
    # first-table scatter-min (classified join-table above, by its iota
    # update) plus the count table's scatter-add over the SAME table
    # shape AND the same scatter-index stream — pair the count scatter
    # with it so a deliberately chosen DIRECT join contributes zero to
    # scatter_count (the appearance gate's business is aggregation
    # amplifiers sneaking back in). The shared-operand requirement keeps
    # an UNRELATED same-sized aggregation scatter in the count: equal
    # element counts alone collide across power-of-two caps.
    jt_rows = [r for r in rows if r["class"] == "join-table"]
    if jt_rows:
        ins_by_name = {i.name: i for i in entry}

        def _feed_names(name: str) -> set:
            """The operand names that identify a scatter's DESTINATION
            stream. For a true ``scatter`` opcode that is exactly the
            indices operand (operand 1) — identical indices mean the
            same table addresses, the pairing signal. For the CPU
            while/fusion lowering (indices ride inside the carry
            tuple), one hop through tuple/fusion minus the
            trivially-shared producers — parameters INCLUDED in the
            exclusions here, so a fused join+agg program whose agg
            scatter merely reads the same key column cannot pair."""
            trivial = ("constant", "broadcast", "iota")
            out: set = set()
            ins = ins_by_name.get(name)
            if ins is None:
                return out
            if ins.opcode == "scatter":
                if len(ins.operands) > 1:
                    out.add(ins.operands[1])
                return out
            for op in ins.operands:
                ref = mod.by_name.get(op)
                if ref is None:
                    continue
                if ref.opcode in ("tuple", "fusion"):
                    out.update(ref.operands)
                if ref.opcode not in trivial:
                    out.add(op)
            return {o for o in out
                    if mod.by_name.get(o) is not None
                    and mod.by_name[o].opcode not in trivial
                    + ("parameter",)}

        for jt in jt_rows:
            jt_feeds = _feed_names(jt["name"])
            jt_n = out_elems_by_name.get(jt["name"])
            for r in rows:
                if (r["class"] == "scatter-add"
                        and out_elems_by_name.get(r["name"]) == jt_n
                        and jt_feeds & _feed_names(r["name"])):
                    r["class"] = "join-table"
    # scatter programs are THE amplifier the roadmap hunts: count every
    # entry-level row the classifier binned as one (a while-lowered
    # scatter is one scatter, not its dozens of body instructions)
    scatter_count = sum(1 for r in rows
                        if r["class"] in ("scatter", "scatter-add"))
    rows.sort(key=lambda r: -r["bytes"])
    largest = max(rows, key=lambda r: r["out_bytes"], default=None)
    return {
        "instructions": n,
        "coverage": round(ok / n, 4) if n else 0.0,
        "total_bytes": int(total_bytes),
        "flops": int(flops),
        "scatter_count": scatter_count,
        "top_fusions": rows[:top_k],
        "largest_output": ({"name": largest["name"],
                            "bytes": largest["out_bytes"]}
                           if largest is not None else None),
    }


# ---------------------------------------------------------------------------
# Harvest plumbing: in-process record table (bench reads it, like
# xla_cost._RECORDS), hlo_summary event, live obs twins
# ---------------------------------------------------------------------------
_LOCK = threading.Lock()
_RECORDS: deque = deque(maxlen=8192)
_SEQ = 0

#: conf-declared top-K, recorded by the session at execute time (the
#: xla_cost.set_conf_peaks pattern: the probe that harvests has no
#: RapidsConf of its own). None until any session declares one.
_TOP_K: Optional[int] = None


def set_conf_top_k(conf_) -> None:
    global _TOP_K
    _TOP_K = int(conf_.get(HLO_TOP_K))

#: summary payload fields every hlo_summary event carries (the event
#: additionally carries site/digest/backend and optional op/
#: accounted_frac)
SUMMARY_FIELDS = ("instructions", "coverage", "total_bytes",
                  "scatter_count", "top_fusions", "largest_output")


def snapshot() -> int:
    with _LOCK:
        return _SEQ


def records_since(seq: int = 0) -> List[dict]:
    with _LOCK:
        return [dict(r) for r in _RECORDS if r["seq"] > seq]


def harvest_hlo(compiled, site: str, digest: str,
                op: Optional[str] = None,
                xla_bytes: Optional[float] = None,
                top_k: Optional[int] = None) -> Optional[dict]:
    """Parse one harvested executable's optimized HLO into a summary
    record + ``hlo_summary`` event + obs twins. Called by
    xla_cost.CostProbe INSIDE its harvesting()-gated first call, so the
    zero-overhead contract is inherited; any failure (no as_text, a
    dialect the parser chokes on) returns None and the query proceeds.
    """
    global _SEQ
    try:
        text = compiled.as_text()
        if not isinstance(text, str) or "HloModule" not in text:
            return None
        import jax

        summary = summarize_hlo(
            text, top_k=top_k or _TOP_K or HLO_TOP_K.default)
        rec: Dict[str, Any] = {
            "site": site, "digest": digest, "op": op,
            "backend": jax.default_backend(),
        }
        rec.update(summary)
        # honesty ratio vs the compiler's own figure: utilization
        # weighting inside fusions/loop bodies makes the two diverge
        # legitimately — report the ratio so a shortfall is explained
        if xla_bytes:
            rec["accounted_frac"] = round(
                summary["total_bytes"] / xla_bytes, 4)
        else:
            rec["accounted_frac"] = None
    except Exception:
        return None
    with _LOCK:
        _SEQ += 1
        rec["seq"] = _SEQ
        _RECORDS.append(rec)
    if _events.enabled():
        ev = {k: rec[k] for k in ("site", "digest", "backend")
              + SUMMARY_FIELDS}
        for k in ("op", "accounted_frac"):
            if rec.get(k) is not None:
                ev[k] = rec[k]
        _events.emit("hlo_summary", **ev)
    from . import obs as _obs

    if _obs.enabled():
        top = rec["top_fusions"][0]["bytes"] if rec["top_fusions"] else 0
        _obs.note_hlo_summary(site, rec["scatter_count"], top)
    return rec


def note_cached_summary(site: str, digest: str, payload: Dict[str, Any],
                        op: Optional[str] = None) -> Optional[dict]:
    """Re-emit a PERSISTED hlo_summary payload on an AOT program-cache
    deserialize hit (serve/program_cache.py): the program's HLO was
    parsed by the process that originally compiled it, and a warm
    process that never compiled anything must still report the same
    per-fusion attribution (flagged ``from_cache``) so the '== hlo =='
    section and the --diff scatter/fusion gates stay truthful. Rides
    the caller's harvesting() gate; a malformed payload records
    nothing and never fails a query."""
    global _SEQ
    try:
        import jax

        rec: Dict[str, Any] = {
            "site": site, "digest": digest, "op": op,
            "backend": jax.default_backend(),
            "accounted_frac": payload.get("accounted_frac"),
            "from_cache": True,
        }
        for k in SUMMARY_FIELDS:
            rec[k] = payload[k]
    except Exception:
        return None
    with _LOCK:
        _SEQ += 1
        rec["seq"] = _SEQ
        _RECORDS.append(rec)
    if _events.enabled():
        ev = {k: rec[k] for k in ("site", "digest", "backend")
              + SUMMARY_FIELDS}
        ev["from_cache"] = True
        for k in ("op", "accounted_frac"):
            if rec.get(k) is not None:
                ev[k] = rec[k]
        _events.emit("hlo_summary", **ev)
    from . import obs as _obs

    if _obs.enabled():
        top = rec["top_fusions"][0]["bytes"] if rec["top_fusions"] else 0
        _obs.note_hlo_summary(site, rec["scatter_count"], top)
    return rec
