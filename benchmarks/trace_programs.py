"""From the same ``.xplane.pb`` that ``trace_reduce`` reads, the numbers
that need the engine's own names: device seconds by program and by phase,
and the engine's host spans with their self time, their counts and their
query.

The reduction works on the plain ``planes`` structure of ``trace_reduce``
with one more item an event, its stats (``read_xplane`` here makes it from
the file, tests build it by hand)::

    [{"name": "/device:TPU:0",
      "lines": [{"name": "XLA Ops",
                 "events": [(name, start_ns, duration_ns, stats), ...]}]}]

What a v5e trace holds of names (one looked at by hand, PR 26: a probe
program ``jit_agg_stage`` with ``jax.named_scope`` pieces, under spans with
counts):

- the device plane's line ``XLA Modules`` has one event a program run, named
  ``jit_<function name>(<fingerprint>)``; the ``XLA Ops`` events of the run
  lie inside it in time. The engine names every program after its
  ``cached_pipeline`` site (``spark_rapids_tpu/exec/base.program``), so the
  function name is the site's word;
- an ``XLA Ops`` event itself carries only ``device_offset_ps``,
  ``device_duration_ps`` and a time scale. The name stack is a stat of the
  event's METADATA, not of the event: ``tf_op`` =
  ``jit(agg_stage)/pq_decode/while/body/closed_call/jit(_take)/gather:``
  (there is no ``op_name`` stat), beside ``program_id`` (the module's
  fingerprint), ``hlo_category``, ``flops``, ``bytes_accessed`` and
  ``source``. ``jax.profiler.ProfileData`` shows an event's own stats only,
  so ``op_metadata`` reads the metadata tables from the file's bytes (the
  protobuf wire format of tsl's ``xplane.proto``; nothing on this machine
  but TensorFlow ships its Python binding, and a benchmark process does not
  import TensorFlow beside JAX);
- a host span made by ``jax.profiler.TraceAnnotation(name, **counts)``
  carries each count as a stat of the event itself: ``query``, ``bytes``,
  ``columns``, ``hits``, ``lookups``, ``cache`` (a string).

``ctx`` carries no path to the trace, so ``for_ctx`` takes the newest
``*.xplane.pb`` under this directory's ``.cache/trace/`` (the ``Tracer``
clears a cell's directory before it traces) and keeps its result in ``ctx``.
Where the program under test lacks the names (the parent of PR 26), the
reduction still runs: every device second is then unnamed and no engine
span but PR 25's ``<Exec>.<section>`` ones is found.
"""
from __future__ import annotations

import bisect
import os
import re
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from trace_reduce import (DEVICE_PLANE, OPS_LINE, QUERY_MARK, SLICE_MARK,
                          op_key)

HERE = os.path.dirname(os.path.abspath(__file__))
MODULES_LINE = "XLA Modules"
#: the words a program of the engine is jitted under: the
#: ``cached_pipeline`` sites and two words for the exchange's site-less
#: programs (``exec/base.PROGRAM_WORDS``), then the two programs jitted
#: outside ``cached_pipeline`` under names of their own
#: (``exec/base.OTHER_PROGRAM_WORDS``). A test holds the copies equal.
PROGRAM_WORDS = (
    "agg_update", "agg_stage", "agg_plan", "pq_decode", "upload_unpack",
    "fused_chain", "project", "sort", "window", "exchange",
    "exchange_slice", "exchange_concat", "join", "mesh_agg", "mesh_sort",
    "mesh_window", "mesh_join",
    "materialize_dict", "eval_exprs",
)
#: the ``jax.named_scope`` words inside a program (``exec/base.SCOPE_WORDS``)
SCOPE_WORDS = ("pq_decode", "upload_unpack", "fused_chain", "agg_update",
               "agg_merge", "project")
#: a host span of the engine: ``<Node>Exec[.<section>[.<part>]]`` or
#: ``TpuSession.<phase>``
ENGINE_SPAN = re.compile(r"^(\w+Exec(\.\w+)*|TpuSession\.\w+)$")
QUERY_SPAN = "TpuSession.query"
UNNAMED = "unnamed"
_MODULE_NAME = re.compile(r"^jit_(\w+?)(\(\d+\))?$")
_MODULE_ID = re.compile(r"\((\d+)\)$")

Event = Tuple[str, float, float, dict]  # name, start ns, duration ns, stats


# ---------------------------------------------------------------------------
# reading the file
# ---------------------------------------------------------------------------
def _varint(buf, pos: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one protobuf message: an int for
    a varint, a memoryview for a length-delimited or fixed-width field."""
    pos, end = 0, len(buf)
    while pos < end:
        tag, pos = _varint(buf, pos)
        field, wire = tag >> 3, tag & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            n, pos = _varint(buf, pos)
            value = buf[pos:pos + n]
            pos += n
        elif wire == 1:
            value = buf[pos:pos + 8]
            pos += 8
        elif wire == 5:
            value = buf[pos:pos + 4]
            pos += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield field, wire, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", errors="replace")


def op_metadata(path: str) -> Dict[str, Dict[str, List[dict]]]:
    """``{plane name: {event name: [{"tf_op": ..., "program_id": ...}]}}``
    from the file's event-metadata tables (XSpace.planes = 1; XPlane.name =
    2, .event_metadata = 4, .stat_metadata = 5; XEventMetadata.name = 2,
    .stats = 5; XStat.metadata_id = 1, .uint64 = 3, .int64 = 4, .str = 5,
    .ref = 7; XStatMetadata.name = 2). The lines, which hold nearly all of
    the file's bytes, are stepped over."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, Dict[str, List[dict]]] = {}
    for field, _, plane in _fields(space):
        if field != 1:
            continue
        name = ""
        stat_names: Dict[int, str] = {}
        metas = []
        for pf, _, value in _fields(plane):
            if pf == 2:
                name = _text(value)
            elif pf == 5:  # map entry: key = 1, value = 2 (XStatMetadata)
                key, sname = 0, ""
                for ef, _, ev in _fields(value):
                    if ef == 1:
                        key = ev
                    elif ef == 2:
                        for sf, _, sv in _fields(ev):
                            if sf == 2:
                                sname = _text(sv)
                stat_names[key] = sname
            elif pf == 4:
                for ef, _, ev in _fields(value):
                    if ef == 2:
                        metas.append(ev)
        if not DEVICE_PLANE.match(name):
            continue
        wanted = {k for k, v in stat_names.items()
                  if v in ("tf_op", "program_id")}
        by_name: Dict[str, List[dict]] = {}
        for meta in metas:
            ev_name, stats = "", {}
            for mf, _, mv in _fields(meta):
                if mf == 2:
                    ev_name = _text(mv)
                elif mf == 5:
                    sid, val = 0, None
                    for sf, _, sv in _fields(mv):
                        if sf == 1:
                            sid = sv
                        elif sf in (3, 4):
                            val = sv
                        elif sf == 5:
                            val = _text(sv)
                        elif sf == 7:
                            val = stat_names.get(sv, "")
                    if sid in wanted:
                        stats[stat_names[sid]] = val
            if stats:
                by_name.setdefault(ev_name, []).append(stats)
        out[name] = by_name
    return out


def read_xplane(path: str) -> List[dict]:
    """``trace_reduce.read_xplane`` keeping each event's stats, and with a
    device operation's ``tf_op`` and ``program_id`` (its metadata's) among
    them."""
    from jax.profiler import ProfileData

    metadata = op_metadata(path)
    planes = []
    for plane in ProfileData.from_file(path).planes:
        by_name = metadata.get(plane.name, {})
        lines = []
        for line in plane.lines:
            events = []
            named = line.name == OPS_LINE and by_name
            for e in line.events:
                stats = dict(e.stats)
                if named:
                    found = by_name.get(e.name)
                    if found:
                        stats["_metadata"] = found
                events.append((e.name, float(e.start_ns),
                               float(e.duration_ns), stats))
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


# ---------------------------------------------------------------------------
# names
# ---------------------------------------------------------------------------
def module_word(name: str) -> Optional[str]:
    """``jit_agg_update(123)`` -> ``agg_update``."""
    m = _MODULE_NAME.match(name)
    return m.group(1) if m else None


def scope_word(tf_op: Optional[str]) -> Optional[str]:
    """The first engine word in an operation's name stack:
    ``jit(agg_stage)/pq_decode/while/body/.../gather:`` -> ``pq_decode``."""
    if not tf_op:
        return None
    for part in tf_op.split("/")[1:]:
        part = part.rstrip(":")
        if part in SCOPE_WORDS:
            return part
    return None


def _stack_program(tf_op: Optional[str]) -> Optional[str]:
    """``jit(agg_stage)/...`` -> ``agg_stage``: an operation's program by
    its own stat, for one that lies in no module event."""
    m = re.match(r"^jit\((\w+)\)", tf_op or "")
    return m.group(1) if m else None


def _op_names(stats: dict, module_id: Optional[int]) -> Optional[str]:
    """An operation's ``tf_op``: its own stat (hand-built planes), else its
    metadata's; of several metadata of one name, the enclosing module's."""
    if "tf_op" in stats:
        return stats["tf_op"]
    found = stats.get("_metadata") or ()
    for meta in found:
        if module_id is not None and meta.get("program_id") == module_id:
            return meta.get("tf_op")
    return found[0].get("tf_op") if found else None


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------
def self_times(events: Sequence[Event], lo: float, hi: float
               ) -> List[Tuple[Event, float, float]]:
    """(event, clipped ns, self ns) for the events of ONE line that overlap
    [lo, hi]: self is the event's clipped time less that of the events
    nested directly in it (a loop's body under the loop, a span's parts
    under the span)."""
    out: List[list] = []
    stack: List[list] = []  # [index in out, end]
    for ev in sorted(events, key=lambda e: (e[1], -e[2])):
        s, e = max(ev[1], lo), min(ev[1] + ev[2], hi)
        if e <= s:
            continue
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            out[stack[-1][0]][2] -= min(e, stack[-1][1]) - s
        stack.append([len(out), e])
        out.append([ev, e - s, e - s])
    return [(ev, total, max(own, 0.0)) for ev, total, own in out]


def _slice_bounds(planes: List[dict]) -> Tuple[float, float]:
    marks, extent = [], []
    for plane in planes:
        for line in plane["lines"]:
            for ev in line["events"]:
                if plane["name"].startswith("/host:") and ev[0] == SLICE_MARK:
                    marks.append((ev[1], ev[1] + ev[2]))
                elif (DEVICE_PLANE.match(plane["name"])
                      and line["name"] == OPS_LINE):
                    extent.append((ev[1], ev[1] + ev[2]))
    use = marks or extent
    if not use:
        return 0.0, 0.0
    return min(s for s, _ in use), max(e for _, e in use)


def _add(into: dict, key, seconds: float) -> None:
    into[key] = into.get(key, 0.0) + seconds


def _device(planes: List[dict], lo: float, hi: float,
            query_spans: Sequence[Tuple[int, float, float]]) -> dict:
    by_program: Dict[str, float] = {}
    by_scope: Dict[str, float] = {}
    by_label: Dict[str, float] = {}
    unnamed_ops: Dict[str, float] = {}
    by_query: Dict[int, Dict[str, float]] = {}
    total = unnamed = 0.0
    chips = 0
    for plane in planes:
        if not DEVICE_PLANE.match(plane["name"]):
            continue
        ops = [ev for line in plane["lines"] if line["name"] == OPS_LINE
               for ev in line["events"]]
        if not ops:
            continue
        chips += 1
        modules = sorted(
            (ev for line in plane["lines"] if line["name"] == MODULES_LINE
             for ev in line["events"]), key=lambda ev: ev[1])
        starts = [m[1] for m in modules]
        for ev, _, own in self_times(ops, lo, hi):
            if own <= 0.0:
                continue
            sec = own / 1e9
            i = bisect.bisect_right(starts, ev[1]) - 1
            module = (modules[i] if i >= 0
                      and ev[1] < modules[i][1] + modules[i][2] else None)
            module_id = None
            if module is not None:
                m = _MODULE_ID.search(module[0])
                module_id = int(m.group(1)) if m else None
            tf_op = _op_names(ev[3], module_id)
            program = (module_word(module[0]) if module is not None
                       else _stack_program(tf_op))
            scope = scope_word(tf_op)
            engine_program = program if program in PROGRAM_WORDS else None
            label = scope or engine_program or UNNAMED
            total += sec
            _add(by_program, program or UNNAMED, sec)
            if scope:
                _add(by_scope, scope, sec)
            _add(by_label, label, sec)
            if label == UNNAMED:
                unnamed += sec
                _add(unnamed_ops, f"{program or '?'}: {op_key(ev[0])}", sec)
            mid = max(ev[1], lo)
            for qid, q0, q1 in query_spans:
                if q0 <= mid < q1:
                    _add(by_query.setdefault(qid, {}), label, sec)
                    break
    chips = max(1, chips)

    def scaled(d: Dict[str, float]) -> Dict[str, float]:
        return {k: v / chips for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])}

    return {"device_s": total / chips, "unnamed_s": unnamed / chips,
            "named_s": (total - unnamed) / chips,
            "by_program": scaled(by_program), "by_scope": scaled(by_scope),
            "by_label": scaled(by_label),
            "unnamed_ops": list(scaled(unnamed_ops).items())[:10],
            "device_by_query": {q: scaled(d) for q, d in by_query.items()}}


def _summary() -> dict:
    return {"count": 0, "total_s": 0.0, "self_s": 0.0, "counts": {},
            "values": {}}


def _note_span(into: Dict[str, dict], ev: Event, total: float,
               own: float) -> None:
    rec = into.setdefault(ev[0], _summary())
    rec["count"] += 1
    rec["total_s"] += total / 1e9
    rec["self_s"] += own / 1e9
    for key, value in ev[3].items():
        if key == "query" or key.startswith("_"):
            continue
        if isinstance(value, bool) or not isinstance(value, (int, float)):
            tally = rec["values"].setdefault(key, {})
            tally[str(value)] = tally.get(str(value), 0) + 1
        else:
            rec["counts"][key] = rec["counts"].get(key, 0) + value


def _host(planes: List[dict], lo: float, hi: float) -> dict:
    spans: Dict[str, dict] = {}
    by_query: Dict[int, Dict[str, dict]] = {}
    query_spans: List[Tuple[int, float, float]] = []
    marks = 0
    for plane in planes:
        if not plane["name"].startswith("/host:"):
            continue
        for line in plane["lines"]:
            engine = []
            for ev in line["events"]:
                if ev[0] == QUERY_MARK and lo <= ev[1] < hi:
                    marks += 1
                elif ENGINE_SPAN.match(ev[0]):
                    engine.append(ev)
            for ev, total, own in self_times(engine, lo, hi):
                _note_span(spans, ev, total, own)
                qid = ev[3].get("query")
                if qid is not None:
                    _note_span(by_query.setdefault(int(qid), {}), ev,
                               total, own)
                    if ev[0] == QUERY_SPAN:
                        query_spans.append((int(qid), ev[1], ev[1] + ev[2]))
    return {"spans": spans, "spans_by_query": by_query,
            "query_spans": sorted(query_spans, key=lambda q: q[1]),
            "query_marks": marks}


def reduce_programs(planes: List[dict]) -> dict:
    """Inside ``bench.slice`` (else the device events' extent):

    - ``by_program`` / ``by_scope`` / ``by_label``: device seconds (each
      operation's self time, averaged over the chips that ran any) by the
      program that contains the operation, by the first engine word of its
      name stack, and by the one the metrics use (the scope, else the
      engine program, else ``unnamed``); ``device_s``, ``named_s``,
      ``unnamed_s`` and the largest ``unnamed_ops``;
    - ``spans``: for each engine span name ``count``, ``total_s``,
      ``self_s`` (less its parts on the same thread), ``counts`` (the sums
      of its numeric arguments) and ``values`` (a tally of each string
      argument);
    - ``spans_by_query`` and ``device_by_query``: the same by the spans'
      ``query`` argument, the device seconds by the ``TpuSession.query``
      span they fall in."""
    lo, hi = _slice_bounds(planes)
    out = _host(planes, lo, hi)
    out.update(_device(planes, lo, hi, out["query_spans"]))
    out["window_s"] = (hi - lo) / 1e9
    return out


# ---------------------------------------------------------------------------
# what the metric files read
# ---------------------------------------------------------------------------
TRACE_ROOT = os.path.join(HERE, ".cache", "trace")


def newest_trace(root: str = TRACE_ROOT) -> Optional[str]:
    """The newest ``*.xplane.pb`` anywhere under ``root``."""
    paths = [os.path.join(d, f) for d, _, files in os.walk(root)
             for f in files if f.endswith(".xplane.pb")]
    return max(paths, key=os.path.getmtime) if paths else None


def for_ctx(ctx: dict) -> Optional[dict]:
    """The reduction of the run's trace, made once a run and kept in
    ``ctx`` (a test hands it in under the same key)."""
    if "trace_programs" not in ctx:
        path = newest_trace() if ctx.get("trace") else None
        ctx["trace_programs"] = (reduce_programs(read_xplane(path))
                                 if path else None)
    return ctx["trace_programs"]


def section_spans(reduced: dict, *sections: str) -> List[dict]:
    """The summaries of every span whose section is one of ``sections``
    (``upload`` matches ``TpuFileSourceScanExec.upload``)."""
    return [rec for name, rec in reduced["spans"].items()
            if name.partition(".")[2] in sections]


def scanned_a_file(reduced: dict) -> bool:
    """Did the slice look a file up at all (in the cache or on disk)?"""
    return bool(section_spans(reduced, "cache_lookup", "read_file",
                              "page_plan", "host_decode"))


def has_engine_names(reduced: Optional[dict]) -> bool:
    """Does the program under test carry this vocabulary at all? The
    parent of the PR that brought it does not (its one stable program
    name, ``jit_materialize_dict``, is an accident of a ``def``): a
    reader then returns nothing rather than a share that was never
    measured. The ``TpuSession.query`` span came with the names."""
    return bool(reduced) and QUERY_SPAN in reduced["spans"]


def per_query(ctx: dict, seconds: float) -> Optional[float]:
    queries = (ctx.get("trace") or {}).get("queries")
    return 1000.0 * seconds / queries if queries else None


def device_share(ctx: dict, *labels: str) -> Optional[float]:
    """Percent of the slice's device busy time under the given labels."""
    reduced = for_ctx(ctx)
    busy = (ctx.get("trace") or {}).get("busy_s")
    if not has_engine_names(reduced) or not busy:
        return None
    return 100.0 * sum(reduced["by_label"].get(w, 0.0) for w in labels) / busy

