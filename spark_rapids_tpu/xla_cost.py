"""Compiled-program cost plane: what XLA says a program costs.

Every byte/flop figure the engine reported before this module was a
LAYOUT-DERIVED estimate (``bytesTouched`` = rows x row-bytes); nothing
measured what XLA actually compiled. The compiler itself closes that
loop: ``jax.stages.Compiled.cost_analysis()`` and ``memory_analysis()``
report per-program FLOPs, bytes accessed, and temp/argument/output
allocation straight from the optimized HLO — exactly the evidence
needed to decide whether a slow program is MXU-bound or wasting HBM
bandwidth before anyone rewrites it (reference contrast: the JVM plugin
leans on cudf's kernel-level buildTime/GPU metrics for the same call).

Mechanism: :func:`exec.base.cached_pipeline` — the single chokepoint
every jit pipeline cache in the engine goes through (incl. the mesh
path's ``_cached_program``) — wraps each freshly-built jit callable in a
:class:`CostProbe` at compile-miss time. The probe's FIRST call runs the
trace (``lower``) and compile phases explicitly, timed separately,
harvests the cost/memory analyses from the compiled executable, emits
ONE typed ``program_cost`` event (+ live obs twins), and keeps the
compiled executable for every later call — so a warm rerun emits
nothing and pays nothing (the recompile-guard contract).

Zero-overhead contract (the events.py/obs pattern): with event logging
AND the live obs plane off — and :data:`FORCE_HARVEST` unset — wrapping
is skipped entirely at miss time and ``cost_analysis`` is never called
(tests/test_program_cost.py pins this with a spy). ``FORCE_HARVEST`` is
the test harness's opt-in: harvesting without any event sink still
records into the in-process table below (``tests/harness.py`` reads it
for the analysis cross-check).

Graceful degradation: the CPU fallback backend reports different (or
missing) cost keys than a real TPU — every harvested field is therefore
Optional and every consumer (profiler roofline, explain_metrics
columns, bench) guards on key presence instead of erroring.
"""
from __future__ import annotations

import contextlib
import hashlib
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

from . import events as _events
from .conf import conf

ROOFLINE_PEAK_HBM_GBPS = conf(
    "spark.rapids.tpu.roofline.peakHbmGBps", 0.0,
    "Peak HBM bandwidth (GB/s) the roofline report measures achieved "
    "bandwidth against (tpu_profile '== roofline ==', explain_metrics, "
    "bench hbm_frac_xla). 0.0 (the default) picks a per-backend peak: "
    "819 GB/s on TPU (v5e public spec), a nominal 100 GB/s on the CPU "
    "fallback backend. Calibrate per deployment: run a saturating "
    "memcpy-shaped query and set this to the best achieved figure so "
    "the limiter classification reflects YOUR part, not the spec sheet.",
    conf_type=float,
    check=lambda v: None if v >= 0 else "must be >= 0")
ROOFLINE_PEAK_TFLOPS = conf(
    "spark.rapids.tpu.roofline.peakTflops", 0.0,
    "Peak compute throughput (TFLOP/s) for the roofline report's "
    "compute-bound classification. 0.0 (the default) picks a per-backend "
    "peak: 197 TFLOP/s on TPU (v5e bf16 spec; the one-hot bucket_reduce "
    "matmuls run on the MXU), a nominal 1 TFLOP/s on the CPU fallback "
    "backend.", conf_type=float,
    check=lambda v: None if v >= 0 else "must be >= 0")

#: per-backend (peak HBM GB/s, peak TFLOP/s) defaults when the roofline
#: confs are 0.0 — the TPU row is the v5e public spec (the 819 of
#: ``benchmarks/peaks.json``), the CPU row a nominal DDR-class
#: figure so the fallback backend still classifies limiters
BACKEND_PEAKS: Dict[str, Tuple[float, float]] = {
    "tpu": (819.0, 197.0),
    "cpu": (100.0, 1.0),
}


#: explicitly conf-set roofline peaks, recorded by the session at
#: execute time (set_conf_peaks) so harvested program_cost events carry
#: them to the OFFLINE profiler, which has no RapidsConf to read —
#: that is the only channel through which the conf can reach it. None
#: while both confs are 0.0 (per-backend defaults apply everywhere).
#: Process-global last-writer-wins, like the conf-derived engine
#: singletons: concurrent sessions disagreeing on declared hardware
#: peaks is a misconfiguration, not a supported state.
_CONF_PEAKS: Optional[Tuple[float, float]] = None


def set_conf_peaks(conf_) -> None:
    global _CONF_PEAKS
    g = conf_.get(ROOFLINE_PEAK_HBM_GBPS)
    t = conf_.get(ROOFLINE_PEAK_TFLOPS)
    _CONF_PEAKS = (g, t) if (g or t) else None


# ---------------------------------------------------------------------------
# Harvest gating + the in-process record table
# ---------------------------------------------------------------------------
#: bench/harness opt-in: harvest even with events+obs off (records land
#: only in the in-process table below). NOT a user conf — the user-facing
#: switches are the event log / obs plane themselves.
FORCE_HARVEST = False

_LOCK = threading.Lock()
#: bounded: a long-lived serving process must not grow without bound;
#: consumers needing durability use the event log
_RECORDS: deque = deque(maxlen=8192)
_SEQ = 0

#: the program_cost event's REQUIRED fields (None when the backend
#: didn't report them — consumers .get() and guard)
COST_FIELDS = ("flops", "bytes_accessed", "temp_bytes", "argument_bytes",
               "output_bytes", "alias_bytes")


#: lazily-bound obs module (circular import: obs imports events); bound
#: once so the disabled hot path below never hits sys.modules
_OBS_MOD = None


def harvesting() -> bool:
    """True when compile misses should harvest XLA cost analyses.
    Consulted at every compile miss (cached_pipeline) AND by op_timed on
    every hot-section entry (attribution scope rides the same gate so a
    harvest can never lose its op silently) — the disabled path is two
    module-bool reads, no allocation."""
    global _OBS_MOD
    if FORCE_HARVEST:
        return True
    if _events.enabled():
        return True
    mod = _OBS_MOD
    if mod is None:
        # double-checked under the record lock: the bind is idempotent
        # but the sanctioned shape costs nothing off the first call
        with _LOCK:
            if _OBS_MOD is None:
                from . import obs

                _OBS_MOD = obs
            mod = _OBS_MOD
    return mod.enabled()


def snapshot() -> int:
    """Monotonic record sequence — snapshot before a run, pass to
    :func:`records_since` after, and you have THAT run's programs (the
    compile_snapshot() pattern)."""
    with _LOCK:
        return _SEQ


def records_since(seq: int = 0) -> List[dict]:
    with _LOCK:
        return [dict(r) for r in _RECORDS if r["seq"] > seq]


def digest_of(key: Any) -> str:
    """Stable short digest of a pipeline-cache key — the program's
    signature identity across the event log, obs, and reports."""
    return hashlib.sha1(repr(key).encode()).hexdigest()[:12]


# ---------------------------------------------------------------------------
# Current-op attribution: exec/base.op_timed pushes the executing exec's
# name here (only while a cost consumer is on), so a program compiled
# inside TpuHashAggregateExec.op_timed() records op=TpuHashAggregateExec
# and the roofline report can join XLA bytes against that op's measured
# device lane. Compiles outside any op scope (scan staging helpers)
# record op=None; consumers guard.
# ---------------------------------------------------------------------------
_OP = threading.local()


@contextlib.contextmanager
def op_scope(name: str):
    stack = getattr(_OP, "stack", None)
    if stack is None:
        stack = _OP.stack = []
    stack.append(name)
    try:
        yield
    finally:
        stack.pop()


def current_op() -> Optional[str]:
    stack = getattr(_OP, "stack", None)
    return stack[-1] if stack else None


# ---------------------------------------------------------------------------
# Harvesting a compiled executable
# ---------------------------------------------------------------------------
def harvest_compiled(compiled) -> Dict[str, Any]:
    """Cost/memory fields from a ``jax.stages.Compiled``, every key
    guarded: backends disagree on the cost_analysis payload (a list of
    dicts on CPU, a dict on newer jax; key spellings vary) and
    memory_analysis may be absent entirely — missing values surface as
    None, never as an exception."""
    out: Dict[str, Any] = {k: None for k in COST_FIELDS}
    try:
        ca = compiled.cost_analysis()
    except Exception:
        ca = None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if isinstance(ca, dict):
        if ca.get("flops") is not None:
            out["flops"] = float(ca["flops"])
        if ca.get("bytes accessed") is not None:
            out["bytes_accessed"] = float(ca["bytes accessed"])
        # optional per-output breakdown (spelling varies by backend)
        for k in ("bytes accessedout{}", "bytes accessed output"):
            if ca.get(k) is not None:
                out["out_bytes"] = float(ca[k])
                break
    try:
        ma = compiled.memory_analysis()
    except Exception:
        ma = None
    if ma is not None:
        for field, attr in (
            ("temp_bytes", "temp_size_in_bytes"),
            ("argument_bytes", "argument_size_in_bytes"),
            ("output_bytes", "output_size_in_bytes"),
            ("generated_code_bytes", "generated_code_size_in_bytes"),
            ("alias_bytes", "alias_size_in_bytes"),
        ):
            v = getattr(ma, attr, None)
            if v is not None:
                out[field] = int(v)
        # donation correction: XLA folds input buffers aliased to
        # outputs (donate_argnums) INTO temp_size_in_bytes and reports
        # them separately as alias_size_in_bytes. Raw temp therefore
        # RISES under donation even though no new HBM is allocated —
        # the aliased bytes are the donated inputs being reused.
        # Subtracting restores temp_bytes' meaning ("scratch allocated
        # beyond the arguments"); a non-donating program has alias 0,
        # so every existing consumer sees unchanged numbers.
        if out.get("temp_bytes") is not None and out.get("alias_bytes"):
            out["temp_bytes"] = max(
                0, out["temp_bytes"] - out["alias_bytes"])
    return out


def note_program_cost(site: str, digest: str, trace_ns: int,
                      compile_ns: int, cost: Dict[str, Any],
                      op: Optional[str] = None) -> dict:
    """Record one compiled program's cost: in-process table always,
    typed ``program_cost`` event + live obs twins when those planes are
    on. Exactly one call per compile miss (CostProbe guarantees it)."""
    global _SEQ
    rec: Dict[str, Any] = {
        "site": site, "digest": digest,
        "backend": jax.default_backend(),
        "trace_ms": round(trace_ns / 1e6, 3),
        "compile_ms": round(compile_ns / 1e6, 3),
        "op": op,
    }
    if _CONF_PEAKS is not None:
        g, t = _CONF_PEAKS
        if g:
            rec["peak_hbm_gbps"] = g
        if t:
            rec["peak_tflops"] = t
    rec.update(cost)
    with _LOCK:
        _SEQ += 1
        rec["seq"] = _SEQ
        _RECORDS.append(rec)
    if _events.enabled():
        ev = {k: rec.get(k) for k in
              ("site", "digest", "backend", "trace_ms", "compile_ms")
              + COST_FIELDS}
        for k in ("op", "out_bytes", "generated_code_bytes",
                  "peak_hbm_gbps", "peak_tflops", "from_cache",
                  "saved_ms"):
            if rec.get(k) is not None:
                ev[k] = rec[k]
        _events.emit("program_cost", **ev)
    from . import obs as _obs

    if _obs.enabled():
        _obs.note_program_cost(site, trace_ns / 1e9, compile_ns / 1e9,
                               rec.get("temp_bytes"))
    return rec


# ---------------------------------------------------------------------------
# The probe
# ---------------------------------------------------------------------------
class CostProbe:
    """First-call shim around a cached jit callable: run trace+compile
    explicitly (timed separately), harvest the executable's analyses,
    then serve every call from the kept ``Compiled``. Total first-call
    work is the same trace+compile+run jit would have done lazily.

    Defensive by design — a probe must never fail a query: if the
    callable can't ``lower`` with these args, or the AOT executable
    rejects them (signature drift the cache key didn't capture), the
    probe falls back to the plain jit path permanently."""

    __slots__ = ("_fn", "_site", "_digest", "_compiled", "_done", "_lock")

    def __init__(self, fn: Callable, site: str, digest: str):
        self._fn = fn
        self._site = site
        self._digest = digest
        self._compiled = None
        self._done = False
        self._lock = threading.Lock()

    def __call__(self, *args, **kwargs):
        if not self._done:
            with self._lock:
                if not self._done:
                    self._harvest(args, kwargs)
                    self._done = True
        c = self._compiled
        if c is not None:
            try:
                return c(*args, **kwargs)
            except (TypeError, ValueError):
                # args the AOT executable won't take (the cache key
                # under-captured the signature): jit handles them.
                # ONLY signature errors fall back — a genuine runtime
                # failure (device OOM, XlaRuntimeError) must propagate,
                # not silently retrace+recompile and fail twice
                self._compiled = None
        return self._fn(*args, **kwargs)

    def _harvest(self, args, kwargs) -> None:
        if not harvesting():
            return
        try:
            t0 = time.perf_counter_ns()
            lowered = self._fn.lower(*args, **kwargs)
            t1 = time.perf_counter_ns()
            compiled = lowered.compile()
            t2 = time.perf_counter_ns()
        except Exception:
            return
        rec = note_program_cost(self._site, self._digest, t1 - t0, t2 - t1,
                                harvest_compiled(compiled), op=current_op())
        # per-fusion HLO attribution (hlo.py): same gate — this runs
        # only inside the harvesting() window, so with events+obs off
        # as_text() is never fetched (the zero-overhead contract); a
        # parse failure records nothing and never fails the query
        from . import hlo as _hlo

        _hlo.harvest_hlo(compiled, self._site, self._digest,
                         op=rec.get("op"),
                         xla_bytes=rec.get("bytes_accessed"))
        self._compiled = compiled


def wrap(built, site: Optional[str], key) -> Any:
    """Pipeline-cache hook (exec/base.cached_pipeline): wrap a freshly
    built value in a CostProbe when harvesting is on. Handles the mesh
    path's ``(jit_fn, aux)`` tuples; values without a ``lower`` hook
    (plain callables) pass through untouched, as does everything when no
    cost consumer is active (the zero-overhead contract)."""
    if site is None or not harvesting():
        return built
    if (isinstance(built, tuple) and built
            and callable(built[0]) and hasattr(built[0], "lower")):
        return (CostProbe(built[0], site, digest_of(key)),) + built[1:]
    if callable(built) and hasattr(built, "lower"):
        return CostProbe(built, site, digest_of(key))
    return built
