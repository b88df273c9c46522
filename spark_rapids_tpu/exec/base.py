"""Exec base class, metrics, and batch<->traced-value plumbing.

Reference analog: GpuExec.scala:27-150 — the metric names/builders
(GpuMetricNames) and the ``doExecuteColumnar(): RDD[ColumnarBatch]``
contract. Here the unit of data parallelism is the partition index; an exec
exposes ``num_partitions`` and ``execute_partition(i)`` and the driver (or
the exchange layer) decides where partitions run.
"""
from __future__ import annotations

import contextlib
import functools
import threading
import time
from typing import Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import jax

from .. import events as _events
from .. import faults as _faults
from .. import obs as _obs
from .. import xla_cost as _xla_cost
from ..serve import program_cache as _progcache
from ..columnar import ColumnarBatch, DeviceColumn
from ..conf import RapidsConf
from ..expr.eval import ColV, DictV, StrV, Val
from ..types import StructType
from ..utils.locks import ordered_lock

# Standard metric names (reference: GpuMetricNames in GpuExec.scala:27-60)
NUM_OUTPUT_ROWS = "numOutputRows"
NUM_OUTPUT_BATCHES = "numOutputBatches"
TOTAL_TIME = "totalTime"
PEAK_DEVICE_MEMORY = "peakDevMemory"
NUM_INPUT_ROWS = "numInputRows"
NUM_INPUT_BATCHES = "numInputBatches"
#: device-accurate per-op time under metrics.deviceSync.enabled (the
#: block-until-ready wait for the op's own output; see RapidsConf doc)
OP_TIME_DEVICE = "opTimeDevice"
#: output bytes per op: rows x row-bytes from the batch layout
BYTES_TOUCHED = "bytesTouched"


# ---------------------------------------------------------------------------
# Compile cache-miss accounting (profiler): every pipeline cache in the
# engine notes its misses here, so a recompile storm (ragged shapes, a
# fusion key that churns) is visible in explain_metrics() instead of only
# as mysterious wall-clock (reference contrast: the JVM plugin surfaces
# cudf JIT compiles in its buildTime metric).
# ---------------------------------------------------------------------------
class CompileCounter:
    __slots__ = ("total", "by_site", "_lock")

    def __init__(self):
        self.total = 0
        self.by_site: Dict[str, int] = {}
        # concurrent sessions compile concurrently: unguarded += would
        # lose counts and break the recompile-guard tests' exact deltas
        self._lock = ordered_lock("exec.compile_counter")

    def note(self, site: str) -> None:
        with self._lock:
            self.total += 1
            self.by_site[site] = self.by_site.get(site, 0) + 1

    def snapshot(self) -> tuple:
        with self._lock:
            return self.total, dict(self.by_site)


COMPILE_COUNTER = CompileCounter()


# ---------------------------------------------------------------------------
# Shared guard for the process-global jit pipeline caches. Every cache in
# the engine (fused_chain/project/agg/mesh/exchange/pq_decode/
# upload_unpack) had the same get-then-build shape, which under
# concurrent sessions is a check-then-act race: two threads both see a
# miss, both count it, and both build — the recompile guarantees ("this
# plan compiles exactly once") silently break. One helper, one lock:
# the fast path stays a lock-free dict read (GIL-atomic), the slow path
# double-checks under the lock before counting + building. Builders only
# CONSTRUCT the jitted callable (tracing/compilation is deferred to the
# first call, which jax serializes internally), so holding the lock
# across build() is cheap.
# ---------------------------------------------------------------------------
_PIPELINE_CACHE_LOCK = ordered_lock("exec.pipeline_cache", reentrant=True)

#: cache dicts that have passed through cached_pipeline (dedup by
#: identity, O(1) via the id set) — the clear_pipeline_caches() sweep
#: set. Most caches are module globals (8 across the engine) and always
#: register: a process keeps them for good, so the sweep has to reach
#: them. window and join also route per-INSTANCE ``self._jits`` dicts
#: through here (``per_instance=True``; the exchange's and the sort's
#: are process-wide since PRs 35 and 37), and a registered dict pins its
#: exec's compiled executables and, through the program's closure, the
#: exec's whole plan for the process lifetime (dicts aren't
#: weakref-able): they are BOUNDED. Past the cap a per-instance dict simply isn't
#: registered — it stays collectable with its owner, and the sweep (a
#: test/maintenance helper) loses nothing it needs: a fresh session
#: builds fresh exec instances anyway.
_PIPELINE_CACHE_REGISTRY_CAP = 64
_ALL_PIPELINE_CACHES: List[dict] = []
_ALL_PIPELINE_CACHE_IDS: set = set()


def clear_pipeline_caches() -> int:
    """Drop every in-memory compiled-pipeline entry (returns how many).
    Test/maintenance helper: a cleared process re-enters the compile
    path on its next batch — with the persistent AOT program cache
    (serve/program_cache.py) enabled that path is a disk lookup, which
    is exactly how the warm-hit tests exercise it in-process."""
    with _PIPELINE_CACHE_LOCK:
        n = sum(len(c) for c in _ALL_PIPELINE_CACHES)
        for c in _ALL_PIPELINE_CACHES:
            c.clear()
        return n


def cached_pipeline(cache: dict, key, site: Optional[str],
                    build: Callable[[], Callable],
                    max_entries: int = 512,
                    donate: Tuple[int, ...] = (),
                    per_instance: bool = False) -> Callable:
    if donate:
        # the donation mask is part of the program's identity: a
        # donating and a non-donating dispatch of the same logical
        # pipeline are DIFFERENT executables (input/output aliasing
        # differs), and the fold below also reaches the AOT
        # program-cache entry name (entry_name hashes the key repr) so
        # a warm process can never load a non-donating export into a
        # donating call site. tools/tpu_donate.py TPU203 flags any
        # donate_argnums declared outside this chokepoint.
        key = (key, ("donate", tuple(donate)))
    fn = cache.get(key)
    if fn is not None:
        return fn
    with _PIPELINE_CACHE_LOCK:
        fn = cache.get(key)
        if fn is None:
            if (id(cache) not in _ALL_PIPELINE_CACHE_IDS
                    and not (per_instance and len(_ALL_PIPELINE_CACHES)
                             >= _PIPELINE_CACHE_REGISTRY_CAP)):
                _ALL_PIPELINE_CACHES.append(cache)
                _ALL_PIPELINE_CACHE_IDS.add(id(cache))
            if len(cache) > max_entries:
                cache.clear()
            pc = (_progcache.active()
                  if site is not None and _progcache.enabled() else None)
            if pc is not None:
                # persistent AOT program cache (serve/program_cache.py):
                # a disk hit deserializes the executable — no trace, no
                # backend compile, no compile_miss — and re-emits the
                # persisted cost payload flagged from_cache at first
                # call. Anything else (entry absent, corrupt, identity
                # mismatch) returns None and the plain path below runs.
                fn = pc.lookup(site, key, build, donate=donate)
            if fn is None:
                if _faults.enabled():
                    # injected compile failure (chaos testing): raised
                    # BEFORE the miss is counted or the entry installed,
                    # so a failed build never pollutes the cache or the
                    # miss accounting
                    _faults.check("compile", site or "<anon>")
                if site is not None:
                    note_compile_miss(site)
                if pc is not None:
                    # miss with the cache on: the store probe exports +
                    # persists at first call AND subsumes the cost-plane
                    # harvest (it falls back to xla_cost.wrap itself for
                    # programs that cannot participate)
                    fn = pc.wrap_store(build(), site, key, donate=donate)
                else:
                    # compiled-program cost plane (xla_cost.py): while a
                    # cost consumer is active (events / obs / the
                    # bench-harness FORCE_HARVEST hook), the fresh jit
                    # callable is wrapped so its first call times
                    # trace+compile separately and harvests
                    # cost_analysis()/memory_analysis() into ONE
                    # program_cost record; with everything off (the
                    # default) wrap() returns the value untouched and
                    # cost_analysis is never called
                    fn = _xla_cost.wrap(build(), site, key)
            cache[key] = fn
    return fn


def note_compile_miss(site: str) -> None:
    COMPILE_COUNTER.note(site)
    # misses are rare (that's the point); the event names the site so the
    # offline profiler can attribute recompile storms without a rerun
    _events.emit("compile_miss", site=site, total=COMPILE_COUNTER.total)
    if _obs.enabled():
        # live twin: the registry's miss ring feeds the watchdog's
        # recompile-storm window
        _obs.note_compile_miss(site)


def compile_miss_count() -> int:
    """Total pipeline-cache misses so far (tests snapshot/diff this to
    guard against recompile regressions)."""
    return COMPILE_COUNTER.total


# ---------------------------------------------------------------------------
# Sanctioned device→host sync points. EVERY host pull in exec/ops/expr
# goes through these two helpers (tools/tpu_lint.py enforces it): a sync
# costs a full host round trip, so funneling them here keeps the hot path
# auditable — grep for host_pull and you have the complete sync story.
# ---------------------------------------------------------------------------
def host_pull(tree):
    """ONE batched device→host transfer of a pytree of arrays.

    Callers batch every scalar they need into a single call (a list) —
    each separate pull pays a host round trip. This is the only
    sanctioned way to read device values on the host outside this
    module; tools/tpu_lint.py flags raw jax.device_get/.item() sites."""
    out = jax.device_get(tree)
    if _events.enabled() or _obs.enabled():
        nb = sum(int(getattr(a, "nbytes", 0))
                 for a in jax.tree_util.tree_leaves(out))
        _events.emit("transfer", direction="d2h", bytes=nb,
                     site="host_pull")
        if _obs.enabled():
            _obs.inc("tpu_transfers", 1, direction="d2h")
            _obs.inc("tpu_transfer_bytes", nb, direction="d2h")
    return out


def host_fence(arrays):
    """Block until the given device buffers are computed (the profiling /
    ordering fence; the device-sync metric path uses it). Returns the
    arrays so call sites can chain."""
    out = jax.block_until_ready(arrays)
    if _events.enabled():
        _events.emit("transfer", direction="fence", bytes=0,
                     site="host_fence")
    if _obs.enabled():
        _obs.inc("tpu_transfers", 1, direction="fence")
    return out


_PLANNING = threading.local()


@contextlib.contextmanager
def planning_mode():
    """Marks plan CONSTRUCTION: adaptive reads report their static
    partition count instead of materializing their stage (reference: AQE
    only re-plans at stage boundaries during execution, never in
    explain)."""
    prev = getattr(_PLANNING, "on", False)
    _PLANNING.on = True
    try:
        yield
    finally:
        _PLANNING.on = prev


def in_planning() -> bool:
    return getattr(_PLANNING, "on", False)


# ---------------------------------------------------------------------------
# Names on the profiler's clock. One vocabulary serves three places: a
# program is named after its cached_pipeline site (``program``), a phase
# inside a program is a ``jax.named_scope`` of the same word, and a host
# span is ``<Exec>.<section>`` (op_timed). docs/tuning.md lists them.
# ---------------------------------------------------------------------------
#: every word a program may be jitted under: the cached_pipeline sites,
#: plus a word for the two exchange programs whose site is None
PROGRAM_WORDS = (
    "agg_update", "agg_stage", "agg_plan", "pq_decode", "upload_unpack",
    "fused_chain", "project", "sort", "window", "exchange",
    "exchange_slice", "exchange_concat", "join", "mesh_agg", "mesh_sort",
    "mesh_window", "mesh_join",
)
#: programs jitted outside cached_pipeline, under stable names of their own
#: (columnar/column.py's dictionary expansion, expr/eval.py's evaluator)
OTHER_PROGRAM_WORDS = ("materialize_dict", "eval_exprs")
#: the named_scope words: the pieces a fused program is built from. A
#: piece has one word whether it runs alone (its program's name) or fused
SCOPE_WORDS = ("pq_decode", "upload_unpack", "fused_chain", "agg_update",
               "agg_merge", "project")
#: scopes only a program across chips has: the collective exchange
#: between a mesh stage's partial and final halves (parallel/distributed)
MESH_SCOPE_WORDS = ("mesh_exchange",)
#: the scope of the three ``jit_exchange*`` programs of the one-host
#: shuffle (exec/exchange.py): partition ids and sort, the slice of a
#: piece, the concat of a reduce partition's pieces
EXCHANGE_SCOPE_WORDS = ("shuffle_exchange",)


def program(site: str):
    """Decorator for the callable a build site hands to ``jax.jit``: the
    program takes its site's name, so the profiler's ``XLA Modules`` line
    reads ``jit_<site>(<fingerprint>)`` and not ``jit_run`` for all of
    them. A name, not a conf: it costs nothing at run time."""
    assert site in PROGRAM_WORDS, site

    def name_it(fn):
        fn.__name__ = fn.__qualname__ = site
        return fn

    return name_it


#: per thread: ``op`` — the exec whose op_timed section is open here (what
#: ``phase`` times into), ``query`` — the id every span of the drain carries
_AMBIENT = threading.local()


def current_query() -> Optional[int]:
    return getattr(_AMBIENT, "query", None)


@contextlib.contextmanager
def query_scope(qid: Optional[int]):
    """Set once per plan+drain by the session: every span opened on this
    thread (and on pool threads through ``carry``) carries ``query=qid``."""
    prev = getattr(_AMBIENT, "query", None)
    _AMBIENT.query = qid
    try:
        yield
    finally:
        _AMBIENT.query = prev


def carry(fn: Callable) -> Callable:
    """Bind the submitting thread's open section and query to a pool
    task, so work on the decode/prefetch pools times into the same exec
    and carries the same span names and ``query`` there."""
    op = getattr(_AMBIENT, "op", None)
    qid = getattr(_AMBIENT, "query", None)
    if op is None and qid is None:
        return fn

    def task(*args, **kwargs):
        prev = (getattr(_AMBIENT, "op", None),
                getattr(_AMBIENT, "query", None))
        _AMBIENT.op, _AMBIENT.query = op, qid
        try:
            return fn(*args, **kwargs)
        finally:
            _AMBIENT.op, _AMBIENT.query = prev

    return task


@functools.lru_cache(maxsize=None)
def section_metric(section: str) -> str:
    """The metric a named section times into, by one rule:
    ``read_file`` -> ``readFileTime``, ``merge.pull`` -> ``mergePullTime``."""
    head, *rest = section.replace(".", "_").split("_")
    return head + "".join(w.capitalize() for w in rest) + "Time"


def phase(section: str, **counts):
    """A named host phase in code BELOW the exec layer (io/, columnar/):
    times into ``section_metric(section)`` of the exec whose op_timed
    section is open on this thread and, when that exec traces, is a span
    ``<Exec>.<section>`` nested in it, with the keyword ``counts``. With
    no exec above (a scanner driven directly), nothing."""
    op = getattr(_AMBIENT, "op", None)
    if op is None:
        return contextlib.nullcontext(NO_SPAN)
    return op.section(section, **counts)


class _Span:
    """What an open section yields while it traces: ``set(bytes=n)``
    attaches counts to the span (``TraceMe.set_metadata``), so a count is
    recorded at the boundary whose work it sizes, on the trace's clock."""

    __slots__ = ("_annotation",)
    on = True

    def __init__(self, annotation):
        self._annotation = annotation

    def set(self, **counts) -> None:
        self._annotation.set_metadata(**counts)


class _NoSpan:
    """The same with tracing off: nothing is built. Callers guard a count
    that is not already at hand with ``if span.on``."""

    __slots__ = ()
    on = False

    def set(self, **counts) -> None:
        pass


NO_SPAN = _NoSpan()


class Metric:
    """One named counter. ``kind`` drives explain_metrics() formatting:
    'ns' (rendered as ms), 'bytes', or 'count'; inferred from the name so
    lazily-created metrics format like registered ones."""

    __slots__ = ("name", "value", "kind")

    def __init__(self, name: str, kind: Optional[str] = None):
        self.name = name
        self.value = 0
        if kind is None:
            if "Time" in name or name == TOTAL_TIME:
                kind = "ns"
            elif name.startswith("bytes") or name.endswith("Bytes"):
                kind = "bytes"
            else:
                kind = "count"
        self.kind = kind

    def add(self, v: int) -> None:
        self.value += v

    def set(self, v: int) -> None:
        self.value = v

    def pretty(self) -> str:
        if self.kind == "ns":
            return f"{self.value / 1e6:.1f}ms"
        if self.kind == "bytes":
            return f"{self.value / 1e6:.1f}MB"
        return str(self.value)

    def __repr__(self):
        return f"{self.name}={self.value}"


@contextlib.contextmanager
def timed(metric: Optional[Metric], trace_name: str = "", trace: bool = False,
          event_op: Optional[str] = None, event_section: str = "",
          owner=None, **counts):
    """Time a hot section into a metric; optionally emit a profiler range
    (reference: NvtxWithMetrics.scala -> jax.profiler.TraceAnnotation).
    The range carries ``query=<id>`` of the drain it belongs to and the
    keyword ``counts``; the yielded span takes counts known only later
    (``span.set(bytes=n)``). With ``trace`` off no annotation is built.
    ``owner`` is the exec that ``phase`` sections nested on this thread
    time into. ``event_op`` (set only while event logging is on)
    additionally emits a host-lane ``op_span`` event, so the offline
    timeline shares the same start/dur the metric accumulated."""
    if trace:
        qid = getattr(_AMBIENT, "query", None)
        if qid is not None:
            counts["query"] = qid
        ctx = jax.profiler.TraceAnnotation(
            trace_name or (metric.name if metric else "op"), **counts)
        span = _Span(ctx)
    else:
        ctx = contextlib.nullcontext()
        span = NO_SPAN
    prev = getattr(_AMBIENT, "op", None)
    if owner is not None:
        _AMBIENT.op = owner
    start = time.perf_counter_ns()
    try:
        with ctx:
            yield span
    finally:
        _AMBIENT.op = prev
    dur = time.perf_counter_ns() - start
    if metric is not None:
        metric.add(dur)
    if event_op is not None:
        _events.emit("op_span", op=event_op, section=event_section,
                     start=start, dur=dur, lane="host")


@contextlib.contextmanager
def _op_scoped(inner, op: str):
    """Cost-plane attribution wrapper (built only while a cost consumer
    is on): programs compiled inside this exec's hot section record
    op=<node_name> so the roofline report can join XLA bytes/flops
    against the op's measured device lane."""
    with _xla_cost.op_scope(op):
        with inner as span:
            yield span


@contextlib.contextmanager
def _obs_timed(inner, op: str, section: str):
    """op_timed's live-metrics wrapper (built ONLY while the obs plane is
    on — the disabled fast path returns the plain timed() context): the
    open-span table is what the watchdog samples for stall detection, so
    registration must precede the body, not follow it."""
    token = _obs.span_open(op, section)
    start = time.perf_counter_ns()
    try:
        with inner as span:
            yield span
    finally:
        _obs.span_close(token)
        _obs.add_op_time(op, "host", time.perf_counter_ns() - start)


class TpuExec:
    """Base physical operator producing columnar batches on TPU.

    Whole-stage fusion (TPU-first design, no reference analog): execs that
    set ``fusable`` and implement ``lower_batch``/``fusion_key`` are traced
    together into ONE XLA program per maximal single-child chain — project,
    filter, and the aggregate's update step all fuse, so a scan->filter->
    project->aggregate pipeline is a single device dispatch with zero
    intermediate host syncs (row counts ride along as device scalars).
    The reference launches one cudf kernel per expression node instead.
    """

    #: True when this exec can lower into a shared fused trace
    fusable = False

    def __init__(self, conf: RapidsConf, children: Sequence["TpuExec"] = ()):
        from ..conf import ENABLE_TRACE, METRICS_DEVICE_SYNC

        self.conf = conf
        self.children: List[TpuExec] = list(children)
        self.metrics: Dict[str, Metric] = {}
        self._trace = conf.get(ENABLE_TRACE)
        self._device_sync = conf.get(METRICS_DEVICE_SYNC)
        for name in (NUM_OUTPUT_ROWS, NUM_OUTPUT_BATCHES, TOTAL_TIME):
            self._register_metric(name)

    # -- contracts ---------------------------------------------------------
    @property
    def output_schema(self) -> StructType:
        raise NotImplementedError(type(self).__name__)

    @property
    def num_partitions(self) -> int:
        if self.children:
            return self.children[0].num_partitions
        return 1

    def execute_partition(self, index: int) -> Iterator[ColumnarBatch]:
        raise NotImplementedError(type(self).__name__)

    def execute_columnar(self) -> Iterator[ColumnarBatch]:
        """All partitions, serially (driver-side collect path).

        Each partition holds the TPU concurrency semaphore while its device
        work runs (reference: GpuSemaphore.acquireIfNecessary before the
        first device allocation of a task, released at task end)."""
        from ..memory import TpuSemaphore

        sem = TpuSemaphore.initialize(self.conf)
        for p in range(self.num_partitions):
            sem.acquire_if_necessary()
            try:
                yield from self.execute_partition(p)
            finally:
                sem.release_if_necessary()

    def host_prefetch(self) -> None:
        """Serving-path pipelining hook: start this plan's host-side work
        (file reads, parquet decode on the shared pools) BEFORE the
        caller takes the device semaphore, so an admitted query's host
        phase overlaps the running query's device compute. Default:
        recurse — scans override (exec/scan.py). Must not block on the
        work it starts and must be safe to call at most once per plan."""
        for c in self.children:
            c.host_prefetch()

    #: True when lower_batch may clear liveness bits (filters); tells the
    #: chain driver a final compaction is needed for standalone output
    sparsifies = False

    # -- fusion ------------------------------------------------------------
    def fusion_key(self) -> tuple:
        """Structural identity of this exec's lowering (cache key part)."""
        raise NotImplementedError(type(self).__name__)

    def lower_batch(self, cols, live, cap, side=()):
        """Pure traced transform: (cols, live_mask) -> (cols, live_mask).

        ``live`` is a (cap,) bool mask — filters just clear bits instead of
        gathering rows (TPU gathers are slow; reductions consume the mask
        for free). Compaction happens only at chain boundaries that need
        dense batches.

        ``side``: this exec's :meth:`side_vals` arrays as traced jit
        ARGUMENTS (e.g. a join's build-side table) — passing them as args
        instead of closure constants keeps one compiled chain serving
        every build."""
        raise NotImplementedError(type(self).__name__)

    def side_vals(self) -> tuple:
        """Device arrays this exec's ``lower_batch`` needs beyond the
        child batch (passed through the fused jit as arguments)."""
        return ()

    def fusion_stream_child(self) -> Optional["TpuExec"]:
        """The child whose batches stream through this exec's lowering.
        Single-child execs stream their only child; a fast-path join
        streams its probe side (the build side enters via side_vals)."""
        return self.children[0] if len(self.children) == 1 else None

    def fused_source_chain(self):
        """(source exec, [fusable execs bottom-up ending at self])."""
        node = self
        chain: List[TpuExec] = []
        while node.fusable:
            nxt = node.fusion_stream_child()
            if nxt is None:
                break
            chain.append(node)
            node = nxt
        return node, list(reversed(chain))

    # -- conveniences ------------------------------------------------------
    def _register_metric(self, name: str, kind: Optional[str] = None) -> Metric:
        """THE metric construction path — constructor-declared and
        lazily-created metrics both land here, so every metric carries a
        kind and shows up in explain_metrics()."""
        m = Metric(name, kind)
        self.metrics[name] = m
        return m

    def metric(self, name: str, kind: Optional[str] = None) -> Metric:
        if name not in self.metrics:
            return self._register_metric(name, kind)
        return self.metrics[name]

    def op_timed(self, section: str = "", metric_name: str = TOTAL_TIME,
                 **counts):
        """Shared hot-section timer: host wall-clock into ``metric_name``
        plus a profiler TraceAnnotation ``<Exec>.<section>`` (with the
        keyword ``counts`` and the drain's ``query``) when
        sql.trace.enabled is on — EVERY exec wraps its per-batch device
        work in this (reference: NvtxWithMetrics.scala pairing each hot
        section with a GpuMetric + NVTX range). Yields the span, for
        counts known only at the section's end."""
        name = self.node_name + ("." + section if section else "")
        # event args attach only while logging is on, so the disabled fast
        # path is byte-for-byte the pre-event-log behavior
        ctx = timed(self.metric(metric_name), name, self._trace,
                    event_op=self.node_name if _events.enabled() else None,
                    event_section=section, owner=self, **counts)
        if _obs.enabled():
            # live plane: per-op time counters + the open-span table the
            # stall watchdog samples (wrapper only exists while obs is on)
            ctx = _obs_timed(ctx, self.node_name, section)
        if (_xla_cost.harvesting() or _events.enabled()
                or _obs.enabled()):
            # ambient op attribution has two consumers: the cost-plane
            # harvester (programs compiled in this hot section record
            # op=<node_name>) and the HBM ledger (buffers registered in
            # it carry an owning op — the ledger arms exactly when
            # events or obs are on, so ride the same gates); the
            # disabled fast path stays the plain timed() context
            ctx = _op_scoped(ctx, self.node_name)
        return ctx

    def section(self, name: str, **counts):
        """A named part of a hot section with a metric of its own
        (``section_metric``): ``op_timed`` without the second name."""
        return self.op_timed(name, section_metric(name), **counts)

    def record_batch(self, batch: ColumnarBatch) -> ColumnarBatch:
        nr = batch.num_rows_lazy
        if self._device_sync:
            # device-accurate op timing: the wait-for-output fence. With
            # the conf on plan-wide, inputs were already fenced by the
            # child's record_batch, so this wait is THIS op's device time
            # (+ one dispatch) — the CUDA-event-timing analog.
            t0 = time.perf_counter_ns()
            jax.block_until_ready(batch_arrays(batch))
            dt = time.perf_counter_ns() - t0
            self.metric(OP_TIME_DEVICE, "ns").add(dt)
            if _obs.enabled():
                _obs.add_op_time(self.node_name, "device", dt)
            if _events.enabled():
                # the device lane: THIS op's isolated device wait (inputs
                # were fenced by the child's record_batch under the
                # plan-wide conf — see the deviceSync doc)
                _events.emit("op_span", op=self.node_name,
                             section="device_wait", start=t0, dur=dt,
                             lane="device")
            if not isinstance(nr, int):
                nr = int(jax.device_get(nr))  # free: buffers are ready
        if isinstance(nr, int):
            self.metrics[NUM_OUTPUT_ROWS].add(nr)
        self.metrics[NUM_OUTPUT_BATCHES].add(1)
        by = batch_bytes(batch, nr if isinstance(nr, int) else None)
        self.metric(BYTES_TOUCHED, "bytes").add(by)
        if _events.enabled():
            _events.emit("op_batch", op=self.node_name,
                         rows=nr if isinstance(nr, int) else None, bytes=by)
        if _obs.enabled():
            # live counters + the per-query progress numerators /status
            # divides into the analyzer's row/batch forecasts
            _obs.note_op_batch(self.node_name,
                               nr if isinstance(nr, int) else None, by)
        return batch

    def collect(self) -> List[tuple]:
        """Columnar-to-row boundary for the whole plan
        (reference: GpuColumnarToRowExec / GpuBringBackToHost)."""
        rows: List[tuple] = []
        for batch in self.execute_columnar():
            rows.extend(batch.to_rows())
        return rows

    @property
    def node_name(self) -> str:
        return type(self).__name__

    def tree_string(self, indent: int = 0) -> str:
        lines = ["  " * indent + self.describe()]
        for c in self.children:
            lines.append(c.tree_string(indent + 1))
        return "\n".join(lines)

    def describe(self) -> str:
        return self.node_name

    def __repr__(self):
        return self.tree_string()


# ---------------------------------------------------------------------------
# Profiler plumbing: batch introspection + the explain_metrics report
# ---------------------------------------------------------------------------
def batch_arrays(batch: ColumnarBatch) -> List:
    """Every device buffer a batch owns (the block_until_ready fence set)."""
    out: List = []
    for c in batch.columns:
        if c.is_dict:
            d = c.dictv
            out.extend((d.codes, d.dictionary.offsets, d.dictionary.chars,
                        d.validity))
        elif c.is_string:
            out.extend((c.offsets, c.chars, c.validity))
        else:
            out.extend((c.data, c.validity))
    nr = batch.num_rows_lazy
    if not isinstance(nr, int):
        out.append(nr)
    return out


def batch_bytes(batch: ColumnarBatch, rows: Optional[int] = None) -> int:
    """rows x row-bytes from the batch layout: fixed-width columns count
    their storage width + 1 validity byte per row; strings add 4 offset
    bytes plus their chars pool; dict columns count 4 code bytes plus the
    dictionary. ``rows`` falls back to the padded capacity when the row
    count is still a device scalar (no sync just for accounting)."""
    import numpy as np

    total = 0
    for c in batch.columns:
        r = rows if rows is not None else c.capacity
        if c.is_dict:
            d = c.dictv
            total += r * 5 + int(d.dictionary.chars.shape[0])
            total += 4 * int(d.dictionary.offsets.shape[0])
        elif c.is_string:
            total += r * 5 + int(c.chars.shape[0])
        else:
            total += r * (np.dtype(c.data.dtype).itemsize + 1)
    return total


def compile_snapshot() -> tuple:
    """(total, by_site) snapshot for delta reporting (sessions snapshot
    before executing a plan so explain_metrics attributes misses to THAT
    plan, not to everything compiled since process start)."""
    return COMPILE_COUNTER.snapshot()


def format_metrics(plan: TpuExec, since: Optional[tuple] = None,
                   cost_since: Optional[int] = None,
                   boundary=None) -> str:
    """Per-operator metrics report — the profiler's user-facing output
    (reference: the SQL-UI metric table GpuExec publishes per node). One
    line per exec with its metrics prettied by kind, plus a derived HBM
    GB/s LABELED BY THE LANE THAT FED IT: ``hbm_gbps[device]`` (layout
    bytes / opTimeDevice, deviceSync runs) is preferred whenever the
    device lane exists; without it the column degrades to
    ``hbm_gbps[host]`` (layout bytes / host wall-clock) — an async
    dispatch makes the host lane far smaller than the device work it
    queued, so an UNLABELED figure fed by it silently overstates
    bandwidth. ``cost_since`` (an xla_cost.snapshot()) additionally adds
    per-op XLA-compiler columns (xla_bytes/xla_flops/xla_gbps) for
    programs harvested during this run, and a footer reports
    pipeline-cache compile misses by site plus the harvested
    trace/compile split (relative to the ``since`` compile_snapshot).
    ``boundary``: the ColumnarToRowExec above the plan, whose d2h/to_rows
    sections print on a line of their own after the tree."""
    lines: List[str] = []
    cost_recs = (_xla_cost.records_since(cost_since)
                 if cost_since is not None else [])
    cost_by_op: Dict[str, List[dict]] = {}
    for r in cost_recs:
        if r.get("op"):
            cost_by_op.setdefault(r["op"], []).append(r)
    # cost attribution is by CLASS name (op_scope pushes node_name): a
    # class appearing at several plan nodes prints its harvested costs
    # ONCE (first visit pops the entry), and gets no xla_gbps — any
    # single node's device lane is the wrong denominator for the
    # class-wide byte sum
    name_counts: Dict[str, int] = {}

    def count_names(n: TpuExec) -> None:
        name_counts[n.node_name] = name_counts.get(n.node_name, 0) + 1
        for c in n.children:
            count_names(c)

    count_names(plan)

    def walk(node: TpuExec, depth: int) -> None:
        parts = []
        for m in node.metrics.values():
            if m.value:
                parts.append(f"{m.name}={m.pretty()}")
        dev = node.metrics.get(OP_TIME_DEVICE)
        host = node.metrics.get(TOTAL_TIME)
        byt = node.metrics.get(BYTES_TOUCHED)
        if byt is not None and byt.value:
            # bandwidth the op actually demanded: its INPUT stream (the
            # children's output bytes) plus its own output — output alone
            # would misdiagnose a reducing op (an aggregate streaming GBs
            # into 100 group rows) as latency-bound
            in_bytes = sum(
                c.metrics[BYTES_TOUCHED].value
                for c in node.children if BYTES_TOUCHED in c.metrics
            )
            io_bytes = byt.value + in_bytes
            if io_bytes and dev is not None and dev.value:
                parts.append(f"hbm_gbps[device]={io_bytes / dev.value:.2f}")
            elif io_bytes and host is not None and host.value:
                parts.append(f"hbm_gbps[host]={io_bytes / host.value:.2f}")
        recs = cost_by_op.pop(node.node_name, None)
        if recs:
            xb = sum(r["bytes_accessed"] for r in recs
                     if r.get("bytes_accessed") is not None)
            xf = sum(r["flops"] for r in recs if r.get("flops") is not None)
            if xb:
                parts.append(f"xla_bytes={xb / 1e6:.1f}MB")
            if xf:
                parts.append(f"xla_flops={xf / 1e6:.1f}M")
            if (xb and dev is not None and dev.value
                    and name_counts.get(node.node_name) == 1):
                parts.append(f"xla_gbps[device]={xb / dev.value:.2f}")
        lines.append("  " * depth + node.describe()
                     + (": " + ", ".join(parts) if parts else ""))
        for c in node.children:
            walk(c, depth + 1)

    walk(plan, 0)
    if boundary is not None:
        parts = [f"{m.name}={m.pretty()}"
                 for m in boundary.metrics.values() if m.value]
        if parts:
            lines.append(f"collect boundary ({boundary.node_name}): "
                         + ", ".join(parts))
    base_total, base_sites = (0, {}) if since is None else since
    now_total, now_sites = COMPILE_COUNTER.snapshot()
    total = now_total - base_total
    deltas = {
        k: v - base_sites.get(k, 0)
        for k, v in now_sites.items()
        if v - base_sites.get(k, 0)
    }
    sites = ", ".join(f"{k}={v}" for k, v in sorted(deltas.items()))
    lines.append(f"compile cache misses: {total}"
                 + (f" ({sites})" if sites else ""))
    if cost_recs:
        trace_ms = sum(r.get("trace_ms") or 0 for r in cost_recs)
        comp_ms = sum(r.get("compile_ms") or 0 for r in cost_recs)
        temps = [r["temp_bytes"] for r in cost_recs
                 if r.get("temp_bytes") is not None]
        lines.append(
            f"programs harvested: {len(cost_recs)} "
            f"(trace {trace_ms:.1f}ms + compile {comp_ms:.1f}ms"
            + (f", largest temp {max(temps) / 1e6:.1f}MB" if temps else "")
            + ")")
    lines.append(memory_footer())
    return "\n".join(lines)


def memory_footer() -> str:
    """The explain_metrics memory line: the buffer catalog's live device
    bytes, the peak watermark, and the spill/unspill story (process-wide
    counters — the catalog is a process singleton, like the reference's
    RapidsBufferCatalog). ``spilled_bytes`` was tracked since the catalog
    landed but never reported anywhere; this is its user-facing surface."""
    from ..memory.catalog import BufferCatalog

    cat = BufferCatalog.get()
    m = cat.metrics

    def mb(v: int) -> str:
        return f"{v / 1e6:.1f}MB"

    line = (f"memory: device {mb(cat.device_bytes)} "
            f"(peak {mb(m.peak_device_bytes)}), "
            f"spilled {mb(m.spilled_bytes)} in {m.device_to_host} "
            f"spill(s) ({m.host_to_disk} to disk), "
            f"{m.unspills} unspill(s)")
    # the HBM ledger (when armed) decomposes that peak by owning op —
    # the "who held the bytes" column the bare watermark can't answer
    peaks = {op: b for op, b in cat.ledger.op_peaks().items() if b > 0}
    if peaks:
        rows = sorted(peaks.items(), key=lambda kv: kv[1], reverse=True)
        line += "\nmemory by op (peak): " + ", ".join(
            f"{op} {mb(b)}" for op, b in rows)
        leaked = cat.ledger.stats()["leaked_live"]
        if leaked:
            line += f"; LEAKED {leaked} buffer(s)"
    return line


# ---------------------------------------------------------------------------
# ColumnarBatch <-> traced value plumbing
# ---------------------------------------------------------------------------
def vals_of_batch(batch: ColumnarBatch) -> List[Val]:
    from ..columnar import column as _colmod

    out: List[Val] = []
    for c in batch.columns:
        if c.is_dict:
            if _colmod.DICT_MATERIALIZE_EAGERLY:
                c = c.materialize()
                out.append(StrV(c.offsets, c.chars, c.validity))
            else:
                out.append(c.dictv)
        elif c.is_string:
            out.append(StrV(c.offsets, c.chars, c.validity))
        else:
            out.append(ColV(c.data, c.validity))
    return out


def materialized_batch(batch: ColumnarBatch) -> ColumnarBatch:
    """Batch with every dict-encoded column expanded to the plain string
    layout — the boundary call for execs without a dict path (sort keys,
    joins, window partitioning, exchange serialization)."""
    if not any(c.is_dict for c in batch.columns):
        return batch
    return ColumnarBatch(
        [c.materialize() for c in batch.columns], batch.schema,
        batch.num_rows_lazy)


def batch_from_vals(
    vals: Sequence[Val], schema: StructType, num_rows: int,
    capacity: Optional[int] = None,
) -> ColumnarBatch:
    cols = []
    for f, v in zip(schema.fields, vals):
        if isinstance(v, DictV):
            cols.append(DeviceColumn.dict_encoded(f.dataType, num_rows, v))
        elif isinstance(v, StrV):
            cols.append(
                DeviceColumn(f.dataType, num_rows, None, v.validity, v.offsets, v.chars)
            )
        else:
            cols.append(DeviceColumn(f.dataType, num_rows, v.data, v.validity))
    # ``capacity`` matters only for zero-column outputs (fully-pruned
    # projections): the batch then has no column to carry the bucket
    return ColumnarBatch(cols, schema, num_rows, capacity=capacity)


_FUSED_CACHE: Dict[tuple, Callable] = {}


def count_scalar(num_rows):
    """Row count as a traced int32 scalar (host int or device scalar in)."""
    import jax.numpy as jnp

    return jnp.int32(num_rows) if isinstance(num_rows, int) else num_rows


def side_signature(sides: Sequence[tuple]) -> tuple:
    """Structural cache key for chain side inputs (shape+dtype per array)."""
    return tuple(
        tuple((tuple(a.shape), str(a.dtype)) for a in s) for s in sides
    )


def _donation():
    """Lazy handle on plugin/donation.py — plugin/__init__ imports the
    overrides layer which imports this module, so a module-level import
    here would cycle; by first dispatch everything is in sys.modules."""
    from ..plugin import donation

    return donation


def fused_pipeline(chain: Sequence[TpuExec], sig: tuple, cap: int,
                   sides: Sequence[tuple] = (), nonnull: tuple = (),
                   donate: Tuple[int, ...] = ()):
    """One jitted program applying every exec in ``chain`` bottom-up.

    The chain threads a liveness MASK between stages; if any stage
    sparsified it (a filter), rows compact once at the end so the emitted
    batch is dense — otherwise the input row count passes straight through.

    ``nonnull``: per-input-column elision flags from the static plan
    analyzer's nullability lattice (plugin/plananalysis.py) — flagged
    columns enter the chain with the iota-derived liveness mask as their
    validity instead of reading the stored plane (see
    ops/filter_gather.elide_validity for why that is bit-identical).
    """
    key = (tuple(e.fusion_key() for e in chain), sig, cap,
           side_signature(sides), nonnull)

    def build():
        chain_t = tuple(chain)
        needs_compact = any(e.sparsifies for e in chain_t)

        @program("fused_chain")
        def run(cols, num_rows, side_args):
            from ..ops import filter_gather

            live = filter_gather.live_of(num_rows, cap)
            cols = filter_gather.elide_validity(cols, live, nonnull)
            for e, s in zip(chain_t, side_args):
                cols, live = e.lower_batch(cols, live, cap, s)
            if needs_compact:
                cols, count = filter_gather.filter_cols(cols, live, num_rows)
                return cols, count
            return cols, num_rows

        return jax.jit(run, donate_argnums=donate)

    return cached_pipeline(_FUSED_CACHE, key, "fused_chain", build,
                           max_entries=1024, donate=donate)


def run_fused_chain(exec_self: TpuExec, index: int) -> Iterator[ColumnarBatch]:
    """Shared execute_partition for fusable execs: the whole chain below
    (and including) ``exec_self`` runs as one XLA dispatch per batch, with
    the row count threaded through as a device scalar (no host syncs).

    Each dispatch runs under the OOM retry harness (memory/retry.py): a
    device allocation failure spills + re-attempts, and exhausted retries
    split the batch in half — the halves recompile the chain at their
    smaller capacity buckets and the piece outputs re-join row-wise
    (exact: the chain is row-local by construction)."""
    from ..memory.retry import with_oom_retry
    from ..plugin.plananalysis import entry_nonnull_flags

    source, chain = exec_self.fused_source_chain()
    out_schema = exec_self.output_schema
    sides = [e.side_vals() for e in chain]
    nonnull = entry_nonnull_flags(source.output_schema, exec_self.conf)
    # pressure hook: a scan source's staged prefetch holds device
    # residency an OOM recovery wants back (exec/scan.py)
    on_pressure = getattr(source, "invalidate_prefetch", None)

    def attempt(b: ColumnarBatch) -> ColumnarBatch:
        don = _donation()
        cap = b.capacity
        mask = don.dispatch_mask("fused_chain", b, exec_self.conf)
        fn = fused_pipeline(chain, batch_signature(b), cap, sides,
                            nonnull, donate=mask)
        if mask:
            # donating dispatch: the guard snapshots b's planes so
            # split-and-retry can re-read them on failure, accounts
            # donated_bytes, and (under the witness) asserts the
            # donated buffers really died
            with don.guard("fused_chain", b, op=exec_self.node_name,
                           conf=exec_self.conf,
                           metric=exec_self.metric("donatedBytes")):
                vals, nr = fn(vals_of_batch(b),
                              count_scalar(b.num_rows_lazy), sides)
        else:
            vals, nr = fn(
                vals_of_batch(b), count_scalar(b.num_rows_lazy), sides)
        # the output planes come straight out of the program — no other
        # reference exists, so the next certified site may donate them
        return don.mark_exclusive(
            batch_from_vals(vals, out_schema, nr, capacity=cap))

    for batch in source.execute_partition(index):
        with exec_self.op_timed():
            out = with_oom_retry(exec_self.node_name, attempt, batch,
                                 exec_self.conf, on_pressure=on_pressure)
        yield exec_self.record_batch(out)


def batch_signature(batch: ColumnarBatch) -> tuple:
    """Structural cache key for compiled per-exec pipelines: dtype + shapes."""
    from ..columnar import column as _colmod

    sig = []
    for f, c in zip(batch.schema.fields, batch.columns):
        if c.is_dict and not _colmod.DICT_MATERIALIZE_EAGERLY:
            d = c.dictv
            sig.append((f.dataType, "dict", int(d.codes.shape[0]),
                        d.dict_size, int(d.dictionary.chars.shape[0]),
                        d.mat_cap, d.max_len, d.unique))
        elif c.is_dict:  # eager-materialize hook: sign as the plain layout
            d = c.dictv
            sig.append((f.dataType, int(d.codes.shape[0]) + 1, d.mat_cap))
        elif c.is_string:
            sig.append((f.dataType, int(c.offsets.shape[0]), int(c.chars.shape[0])))
        else:
            sig.append((f.dataType, int(c.data.shape[0])))
    return tuple(sig)
