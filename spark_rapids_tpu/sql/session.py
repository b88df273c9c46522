"""TpuSession + DataFrame: the Catalyst stand-in.

A DataFrame is an immutable logical node tree; ``collect()`` lowers it to a
CPU physical plan (the 'what Spark would hand us' plan), runs the override
pass, and executes the result. ``last_executed_plan`` and
``last_explain`` expose what happened for the differential-test harness
(reference: ExecutionPlanCaptureCallback, Plugin.scala:216-305).
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

from .. import events as _events  # registers the eventLog.* conf entries
from .. import faults as _faults  # registers the test.faults.* entries
from .. import obs as _obs
from ..conf import (DONATION_WITNESS_ENABLED, ENABLE_TRACE,
                    RACECHECK_WITNESS_ENABLED, RapidsConf)
from ..cpu import plan as C
from ..memory import catalog as _catalog  # noqa: F401 — registers the
# memory.* conf entries (hbm.budgetBytes) BEFORE RapidsConf validates a
# user's settings dict; the plan analyzer's OOM check reads them
from ..serve import scheduler as _serve  # noqa: F401 — registers the
# serve.* conf entries (serve.enabled picks the submit path below)
from ..exec.transitions import ColumnarToRowExec
from ..expr import aggregates as A
from ..expr import expressions as E
from ..plugin.overrides import TpuOverrides
from ..types import StructType
from ..utils import locks as _locks
from .cache import CacheManager


@dataclasses.dataclass(frozen=True)
class LNode:
    """Logical node; lowered 1:1 to a CPU physical exec."""

    kind: str
    args: tuple  # hashable payload
    children: Tuple["LNode", ...] = ()


_SCANNER_CACHE: Dict[tuple, Any] = {}
_SCANNER_CACHE_LOCK = threading.Lock()


def _make_scanner(fmt: str, path: str, opts: tuple, conf: RapidsConf,
                  pushed: tuple = ()):
    """Build (and cache) a file scanner; the cache avoids re-parsing
    footers on every schema access (conf identity is part of the key).
    Guarded: concurrent serving sessions plan in parallel, and the
    check-then-act would otherwise build (and race-install) duplicate
    scanners for one file."""
    # the key holds the conf VALUES planning depends on, not id(conf): an
    # id can be reused after GC and silently serve a scanner planned under
    # different settings (advisor finding r2)
    from ..conf import (
        CLOUD_SCHEMES,
        MAX_READER_BATCH_SIZE_BYTES,
        PARQUET_READER_TYPE,
    )

    key = (fmt, path, opts, pushed, conf.get(PARQUET_READER_TYPE),
           conf.get(MAX_READER_BATCH_SIZE_BYTES), conf.get(CLOUD_SCHEMES))
    sc = _SCANNER_CACHE.get(key)
    if sc is not None:
        return sc
    with _SCANNER_CACHE_LOCK:
        sc = _SCANNER_CACHE.get(key)
        if sc is not None:
            return sc
        od = dict(opts)
        if fmt == "parquet":
            from ..io.parquet import ParquetScanner

            sc = ParquetScanner(
                path, conf, columns=od.get("columns"),
                filters=list(pushed), required=od.get("required"))
        elif fmt == "csv":
            from ..io.csv import CsvScanner

            sc = CsvScanner(
                path, conf, schema=od.get("schema"),
                header=od.get("header", True), sep=od.get("sep", ","))
        elif fmt == "orc":
            from ..io.orc import OrcScanner

            sc = OrcScanner(path, conf, columns=od.get("columns"),
                            filters=list(pushed),
                            required=od.get("required"))
        else:
            raise ValueError(f"unknown file format {fmt}")
        if len(_SCANNER_CACHE) > 256:
            _SCANNER_CACHE.clear()
        _SCANNER_CACHE[key] = sc
    return sc


def _extract_pushed_filters(cond: E.Expression) -> tuple:
    """col-vs-literal conjuncts for row-group pruning (reference: the
    parquet pushdown assembled in GpuParquetScan's filterBlocks). Unknown
    shapes are simply not pushed — pruning is advisory, the filter exec
    still runs."""
    from ..io.parquet import PushedFilter

    out: List[PushedFilter] = []

    def visit(e: E.Expression):
        if isinstance(e, E.And):
            visit(e.left)
            visit(e.right)
            return
        ops = {
            E.EqualTo: "=", E.LessThan: "<", E.LessThanOrEqual: "<=",
            E.GreaterThan: ">", E.GreaterThanOrEqual: ">=",
        }
        t = type(e)
        if t in ops:
            l, r = e.left, e.right
            if isinstance(l, E.UnresolvedAttribute) and isinstance(r, E.Literal):
                out.append(PushedFilter(l.name, ops[t], r.value))
            elif isinstance(r, E.UnresolvedAttribute) and isinstance(l, E.Literal):
                flip = {"<": ">", "<=": ">=", ">": "<", ">=": "<=", "=": "="}
                out.append(PushedFilter(r.name, flip[ops[t]], l.value))
        elif isinstance(e, E.IsNull) and isinstance(
                e.child, E.UnresolvedAttribute):
            out.append(PushedFilter(e.child.name, "isnull"))
        elif isinstance(e, E.IsNotNull) and isinstance(
                e.child, E.UnresolvedAttribute):
            out.append(PushedFilter(e.child.name, "notnull"))

    visit(cond)
    return tuple(out)


def _resolve_udfs(e: E.Expression, conf: RapidsConf) -> E.Expression:
    """Resolution pass: PythonUDF -> bytecode-compiled expression tree when
    spark.rapids.tpu.sql.udfCompiler.enabled (reference: the udf-compiler's
    injectResolutionRule rewriting ScalaUDF bodies, Plugin.scala:31-64).
    Uncompilable UDFs stay as PythonUDF nodes and run row-by-row on CPU."""
    from ..conf import UDF_COMPILER_ENABLED

    if not conf.get(UDF_COMPILER_ENABLED):
        return e

    def rw(node):
        if isinstance(node, E.PythonUDF):
            from ..udf import try_compile

            compiled = try_compile(node)
            if compiled is not None:
                return compiled
        return node

    return e.transform(rw)


def _column_refs(exprs) -> Optional[frozenset]:
    """Names of the columns the expressions read; None where a node cannot
    be walked (then nothing is pruned below it)."""
    import dataclasses

    names = set()
    todo = list(exprs)
    while todo:
        e = todo.pop()
        if not dataclasses.is_dataclass(e):
            return None
        if isinstance(e, E.UnresolvedAttribute):
            names.add(e.name)
        todo.extend(e.children)
    return frozenset(names)


def _pruned_scanner(fmt: str, path: str, opts: tuple, conf: RapidsConf,
                    pushed: tuple, required: Optional[frozenset]):
    """The file's scanner, reading only the columns the plan above it
    reads (``required``; None = all): a chunk of a column nobody reads is
    never planned, paged in or uploaded (reference: Spark's
    ColumnPruning feeding the scan's readDataSchema). A ``columns`` option
    of the user's own stands."""
    if (required is not None and fmt in ("parquet", "orc")
            and not dict(opts).get("columns")):
        opts = opts + (("required", required),)
    return _make_scanner(fmt, path, opts, conf, pushed)


def _lower(node: LNode, conf: RapidsConf, cache=None,
           required: Optional[frozenset] = None) -> C.CpuExec:
    """Logical plan -> CPU physical plan. ``cache`` is the session's
    ``CacheManager``: a subtree that ``DataFrame.cache()`` marked lowers
    to a ``CpuInMemoryTableScanExec`` over itself (Spark's useCachedData),
    whichever DataFrame it is reached from. ``required``: the columns the
    plan above reads of this node's output (None = all of them)."""
    rel = cache.lookup(node) if cache else None
    if rel is None:
        return _lower_node(node, conf, cache, required)
    from .cache import file_scan_paths, files_identity

    now = files_identity(node)
    if rel.filled and rel.files_key != now:
        # a file under the plan was rewritten: what is resident answers
        # for another file. Free it, forget the scanners that parsed the
        # old footers, and let this query's first action refill
        rel.release()
        paths = set(file_scan_paths(node))
        with _SCANNER_CACHE_LOCK:
            for key in [k for k in _SCANNER_CACHE if k[1] in paths]:
                del _SCANNER_CACHE[key]
    return C.CpuInMemoryTableScanExec(
        conf, _lower_node(node, conf, cache), rel, now)


def _child_columns(node: LNode, required: Optional[frozenset]
                   ) -> Optional[frozenset]:
    """What ``node`` reads of its child, given what is read of ``node``:
    an aggregate or a projection reads what its expressions name, a
    filter and a sort that and what passes through them, a limit what
    passes through; any other node (a join, a window, a union) reads
    everything."""
    k = node.kind
    if k == "aggregate":
        keys, aggs = node.args
        return _column_refs(keys + aggs)
    if k == "project":
        return _column_refs(node.args[0])
    if k in ("filter", "sort"):
        own = _column_refs(node.args[:1] if k == "filter" else node.args[0])
        return None if required is None or own is None else required | own
    if k in ("limit", "collect_limit"):
        return required
    return None


def _lower_node(node: LNode, conf: RapidsConf, cache=None,
                required: Optional[frozenset] = None) -> C.CpuExec:
    k = node.kind
    rx = lambda ex: _resolve_udfs(ex, conf)  # noqa: E731
    below = _child_columns(node, required)
    if k == "filter" and node.children[0].kind == "file_scan" and not (
            cache and cache.lookup(node.children[0])):
        # (a cached scan holds every row group: nothing is pushed into it)
        # push col-vs-literal conjuncts into the scan for row-group pruning
        (cond,) = node.args
        cond = rx(cond)
        fmt, path, opts = node.children[0].args
        pushed = (
            _extract_pushed_filters(cond) if fmt in ("parquet", "orc") else ())
        sc = _pruned_scanner(fmt, path, opts, conf, pushed, below)
        return C.CpuFilterExec(conf, cond, C.CpuFileScanExec(conf, sc, fmt))
    kids = [_lower(c, conf, cache, below) for c in node.children]
    if k == "file_scan":
        fmt, path, opts = node.args
        return C.CpuFileScanExec(
            conf, _pruned_scanner(fmt, path, opts, conf, (), required), fmt)
    if k == "scan":
        rows, schema, nparts = node.args
        per = (len(rows) + nparts - 1) // nparts if rows else 0
        parts = [
            list(rows[i * per: (i + 1) * per]) if per else []
            for i in range(nparts)
        ] if nparts > 1 else [list(rows)]
        return C.CpuScanExec(conf, parts, schema)
    if k == "range":
        start, end, step, slices, name = node.args
        return C.CpuRangeExec(conf, start, end, step, slices, name)
    if k == "project":
        (exprs,) = node.args
        return C.CpuProjectExec(conf, [rx(e) for e in exprs], kids[0])
    if k == "filter":
        (cond,) = node.args
        return C.CpuFilterExec(conf, rx(cond), kids[0])
    if k == "aggregate":
        keys, aggs = node.args
        return C.CpuHashAggregateExec(
            conf, [rx(e) for e in keys], [rx(a) for a in aggs], kids[0])
    if k == "sort":
        exprs, orders = node.args
        return C.CpuSortExec(
            conf, [rx(e) for e in exprs], list(orders), kids[0])
    if k == "limit":
        (n,) = node.args
        return C.CpuLocalLimitExec(conf, n, kids[0])
    if k == "collect_limit":
        (n,) = node.args
        return C.CpuCollectLimitExec(conf, n, kids[0])
    if k == "generate":
        gens, name, with_pos = node.args
        return C.CpuGenerateExec(
            conf, [rx(g) for g in gens], name, with_pos, kids[0])
    if k == "union":
        return C.CpuUnionExec(conf, kids)
    if k == "expand":
        projections, names = node.args
        return C.CpuExpandExec(conf, [list(p) for p in projections], list(names), kids[0])
    if k == "join":
        lkeys, rkeys, how, cond = node.args
        return C.CpuJoinExec(conf, kids[0], kids[1], list(lkeys), list(rkeys), how, cond)
    if k == "window":
        (wexprs,) = node.args
        return C.CpuWindowExec(conf, list(wexprs), kids[0])
    raise ValueError(f"unknown logical node {k}")


def _as_expr(e: Union[str, E.Expression]) -> E.Expression:
    return E.col(e) if isinstance(e, str) else e


_SESSION_SEQ = [0]
_SESSION_SEQ_LOCK = threading.Lock()


def _next_session_id() -> str:
    with _SESSION_SEQ_LOCK:
        _SESSION_SEQ[0] += 1
        return f"session-{_SESSION_SEQ[0]}"


# Query ids are PROCESS-global, not per-session: concurrent serving
# sessions share the live progress tracker (keyed by query id) and merge
# their event logs for offline profiling — per-session numbering would
# collide entries across sessions (two live "query 3"s overwrite each
# other's progress attribution).
_QUERY_SEQ = [0]
_QUERY_SEQ_LOCK = threading.Lock()


def _next_query_id() -> int:
    with _QUERY_SEQ_LOCK:
        _QUERY_SEQ[0] += 1
        return _QUERY_SEQ[0]


class TpuSession:
    """reference analog: SparkSession with the plugin installed."""

    def __init__(self, settings: Optional[Dict[str, Any]] = None):
        self.conf = RapidsConf(settings)
        self._trace = self.conf.get(ENABLE_TRACE)
        self.overrides = TpuOverrides(self.conf)
        self.last_executed_plan = None
        self.last_cpu_plan = None
        self.last_analysis = None
        #: plans marked by DataFrame.cache() and what they keep resident
        self.cache_manager = CacheManager()
        #: stable name in serving queues / event lanes ("session-N")
        self.serve_id = _next_session_id()
        # planning is session-state-mutating (last_* fields, the pending
        # obs slot): the serving path lets N threads share one session,
        # so plan+claim runs under this lock (the drain itself is
        # arbitrated by the scheduler + semaphore, not this lock)
        self._plan_lock = _locks.ordered_lock("sql.plan", reentrant=True)
        self._serve_analysis = None
        self._serve_plan_key = None
        self._last_digest: Optional[str] = None
        # the structured event log (events.py): a ring buffer always backs
        # export_trace(); a JSONL sink appears when eventLog.dir is set.
        # Disabled (the default) costs one boolean per emit site.
        self.events = _events.EventLogger(self.conf)
        self._active_query: Optional[int] = None
        self._pending_obs: Optional[tuple] = None
        # the live observability plane (obs/): registry + conf-gated
        # /metrics + /status exporter thread + watchdog. ensure_started
        # is a no-op returning None with the confs off (the default) —
        # no registry, no threads, one boolean per emit site.
        self._obs_plane = _obs.ensure_started(self.conf)
        # deterministic fault injector (faults.py, chaos testing): a
        # no-op returning None with the test.faults.* confs off (the
        # default) — nothing installed, injection sites stay one
        # module-global boolean read. Never uninstalled implicitly;
        # tests pair install with faults.uninstall().
        _faults.install(self.conf)
        # persistent AOT program cache (serve/program_cache.py): a no-op
        # returning None with the aotCache.* confs off (the default) —
        # no directory touched, no jax config change, the pipeline-cache
        # fast path unchanged. Same lifecycle as the fault injector:
        # process-global, tests pair install with uninstall().
        from ..serve import program_cache as _progcache

        _progcache.install(self.conf)
        # runtime lock-order witness (utils/locks.py): validates every
        # ordered_lock acquire against the declared LOCK_ORDER and
        # records observed acquisition pairs. Off (the default) keeps an
        # acquire at one module-global read; process-global once on,
        # tests pair install_witness with uninstall_witness().
        if self.conf.get(RACECHECK_WITNESS_ENABLED):
            _locks.install_witness()
        # runtime donation witness (plugin/donation.py): asserts donated
        # planes really were deleted post-dispatch and types use-after-
        # donation errors. Same lifecycle as the lock witness (process-
        # global once on; SRTPU_DONATION_WITNESS=1 is the env hook).
        if self.conf.get(DONATION_WITNESS_ENABLED):
            from ..plugin import donation as _donation

            _donation.install_witness()

    def close(self) -> None:
        """Free every cached relation, flush/close the session's event
        sink (atexit also covers a forgotten close) and detach it from
        the process-global emit path. The obs plane is process-wide and
        stays up for other sessions; stop it explicitly with
        obs.shutdown()."""
        self.cache_manager.clear()
        if _events._ACTIVE is self.events:
            _events.uninstall()
        self.events.close()

    @property
    def obs_address(self) -> Optional[str]:
        """Base URL of the live metrics exporter (None unless
        spark.rapids.tpu.metrics.http.enabled): <url>/metrics is the
        Prometheus scrape target, <url>/status feeds tools/tpu_top.py."""
        return (self._obs_plane.address
                if self._obs_plane is not None else None)

    @property
    def last_explain(self) -> str:
        return self.overrides.last_explain

    def create_dataframe(
        self, data: Dict[str, Sequence[Any]], schema: StructType,
        num_partitions: int = 1,
    ) -> "DataFrame":
        names = schema.names
        n = len(data[names[0]]) if names else 0
        rows = tuple(
            tuple(data[name][i] for name in names) for i in range(n)
        )
        return DataFrame(self, LNode("scan", (rows, schema, num_partitions)))

    def from_rows(self, rows: Sequence[tuple], schema: StructType,
                  num_partitions: int = 1) -> "DataFrame":
        return DataFrame(
            self, LNode("scan", (tuple(tuple(r) for r in rows), schema, num_partitions))
        )

    def range(self, start: int, end: Optional[int] = None, step: int = 1,
              num_slices: int = 1) -> "DataFrame":
        if end is None:
            start, end = 0, start
        return DataFrame(self, LNode("range", (start, end, step, num_slices, "id")))

    @property
    def read(self) -> "DataFrameReader":
        return DataFrameReader(self)

    # -- execution ---------------------------------------------------------
    def _execute(self, node: LNode) -> C.CpuExec:
        """Plan one query: ``_lower`` + analysis + ``overrides.apply``,
        under the span ``TpuSession.plan``. The query takes its id here
        (or from ``_collect``'s ``TpuSession.query`` span around plan and
        drain) whether or not events/obs are on: every span of the
        drain carries it."""
        from ..exec.base import current_query, query_scope, timed

        qid = current_query()
        if qid is None:
            qid = _next_query_id()
        with query_scope(qid), timed(None, "TpuSession.plan", self._trace):
            return self._plan(node, qid)

    def _plan(self, node: LNode, qid: int) -> C.CpuExec:
        from ..exec.base import compile_snapshot

        cpu = _lower(node, self.conf, self.cache_manager)
        self.last_cpu_plan = cpu
        from ..conf import ANALYSIS_CROSS_CHECK, ANALYSIS_ENABLED, SQL_ENABLED

        obs_on = _obs.enabled()
        serve_on = self._serve_enabled()
        run_analysis = self.conf.get(SQL_ENABLED) and (
            self.conf.get(ANALYSIS_CROSS_CHECK)
            # with event logging on, the analyzer's forecasts ride in the
            # log so tpu_profile's forecast-vs-actual report has its
            # bounds without a separate explain() run; the live plane
            # needs them too — /status progress denominators; the serving
            # scheduler needs the peak-HBM forecast for admission
            or ((self.events.enabled or obs_on or serve_on)
                and self.conf.get(ANALYSIS_ENABLED)))
        digest: Optional[str] = None
        if self.events.enabled or obs_on or serve_on:
            import hashlib

            digest = hashlib.sha1(
                cpu.tree_string().encode()).hexdigest()[:12]
        self._last_digest = digest
        analysis = None
        self._serve_analysis = None
        self._serve_plan_key = None
        if run_analysis:
            # the static analyzer runs BEFORE conversion/execution — it
            # must never touch the device (plugin/plananalysis.py)
            from ..plugin.plananalysis import analyze_plan

            if serve_on and digest is not None:
                # one analysis per plan digest across ALL sessions: the
                # admission forecast of a repeated plan shape is served
                # from the shared cache instead of recomputed
                from ..serve import SharedPlanCache, conf_fingerprint

                key = (digest, conf_fingerprint(self.conf))
                self._serve_plan_key = key
                analysis, _hit = SharedPlanCache.get().analysis_for(
                    key, lambda: analyze_plan(cpu, self.conf))
                self.last_analysis = analysis
            else:
                analysis = self.last_analysis = analyze_plan(
                    cpu, self.conf)
            self._serve_analysis = analysis
        final, is_tpu = self.overrides.apply(cpu)
        if is_tpu:
            final = ColumnarToRowExec(self.conf, final)
        self.last_executed_plan = final
        # snapshot BEFORE execution so explain_metrics reports only the
        # misses THIS plan's run compiled (the counter is process-global)
        self._compile_baseline = compile_snapshot()
        from .. import xla_cost as _xla_cost

        # same pattern for harvested program costs: the report shows the
        # XLA cost columns for programs THIS run compiled (a warm rerun
        # compiles nothing, so its report carries none — steady state);
        # conf-declared roofline peaks ride in the harvested events so
        # the offline profiler (which has no conf) honors calibration
        self._cost_baseline = _xla_cost.snapshot()
        _xla_cost.set_conf_peaks(self.conf)
        from .. import hlo as _hlo

        _hlo.set_conf_top_k(self.conf)
        if self.events.enabled or obs_on:
            self._active_query = qid
            if self.events.enabled:
                self._emit_query_events(node, qid, digest, is_tpu)
            if obs_on:
                # progress registration is DEFERRED to the drain paths
                # (_run_collect / the writer generator) whose finally
                # guarantees a matching note_query_end — a direct
                # _execute consumer (ml/columnar_rdd, bench device
                # timing) must not strand a forever-"running" query in
                # /status. THIS query's analysis only — last_analysis
                # may hold a previous query's when the analyzer was
                # skipped here.
                self._pending_obs = (
                    qid, digest,
                    analysis.rows_by_op if analysis is not None else None,
                    analysis.batches_by_op
                    if analysis is not None else None)
        return final

    def _obs_take_pending(self) -> Optional[tuple]:
        """Claim the deferred progress registration for one drain path.
        Callers take it EAGERLY (right after _execute) — the slot is
        shared per session, so a later query must not be able to
        overwrite a writer's registration before its sink drains."""
        pending = self._pending_obs
        self._pending_obs = None
        return pending

    @staticmethod
    def _obs_begin(pending: Optional[tuple]) -> Optional[int]:
        """Activate a claimed registration on the DRAINING thread
        (attribution is by thread); returns the qid to close."""
        if pending is None or not _obs.enabled():
            return None
        qid, digest, rows_by_op, batches_by_op = pending
        _obs.note_query_start(qid, digest, rows_by_op, batches_by_op)
        return qid

    # -- event log ---------------------------------------------------------
    def _emit_query_events(self, node: LNode, qid: int, plan_digest: str,
                           is_tpu: bool) -> None:
        """query_start + plan_tagged + plan_analysis for one execution.
        The session's logger becomes the process-wide active sink, so
        engine-level emitters (catalog, caches, transports) attribute to
        this session's log."""
        import hashlib

        from .. import envinfo as _envinfo

        _events.install(self.events)
        # env provenance rides on every query_start so a merged/archived
        # log records WHAT hardware produced it (tpu_profile --diff
        # warns when two logs' environments differ)
        _events.emit("query_start", query_id=qid, plan_digest=plan_digest,
                     sql_hash=hashlib.sha1(
                         repr(node).encode()).hexdigest()[:12],
                     env=_envinfo.environment_info())
        if self.overrides.last_meta is not None:
            _events.emit("plan_tagged", query_id=qid, on_tpu=is_tpu,
                         fallbacks=self.plan_fallbacks())
        if self.last_analysis is not None:
            _events.emit("plan_analysis", query_id=qid,
                         **self.last_analysis.event_fields())

    def plan_fallbacks(self) -> List[dict]:
        """Every operator of the last tagged plan that stays on the CPU,
        with its reasons: ``[{"op": name, "reasons": [...]}]`` (empty =
        the whole plan runs on the device)."""
        fallbacks: List[dict] = []

        def walk(m):
            if m.reasons:
                name = m.rule.name if m.rule else m.wrapped.node_name
                fallbacks.append({"op": name, "reasons": list(m.reasons)})
            for c in m.child_metas:
                walk(c)

        if self.overrides.last_meta is not None:
            walk(self.overrides.last_meta)
        return fallbacks

    _PENDING_UNSET = object()

    def _run_collect(self, final: C.CpuExec, qid: Optional[int] = None,
                     pending: Any = _PENDING_UNSET,
                     digest: Optional[str] = None) -> List[tuple]:
        """Driver-side collect with the query_end event (duration + row
        count) paired to _execute's query_start. Emitted in a finally so a
        failing query still CLOSES its window — an unterminated
        query_start would make the offline profiler attribute every later
        event to the dead query. The serving path passes ``qid`` and the
        ``pending`` obs registration it claimed under the plan lock
        (concurrent submits on one session would otherwise race the
        shared slots)."""
        import time as _time

        t0 = _time.perf_counter_ns()
        if pending is TpuSession._PENDING_UNSET:
            pending = self._obs_take_pending()
        if qid is None:
            qid = self._active_query
        obs_qid = self._obs_begin(pending)
        # the HBM ledger's ownership window: buffers registered by this
        # drain belong to this query; the sweep at close folds the
        # observed peak into the per-digest admission feed and runs the
        # leak sentinel. qid is None exactly when events+obs are off, so
        # the off path never touches the ledger (zero-overhead contract).
        from ..memory import ledger as _ledger

        scope = _ledger.query_scope(qid) if qid is not None else None
        rows: Optional[List[tuple]] = None
        try:
            if scope is not None:
                with scope:
                    rows = final.collect()
            else:
                rows = final.collect()
            return rows
        finally:
            if self.events.enabled:
                _events.emit(
                    "query_end", query_id=qid,
                    dur=_time.perf_counter_ns() - t0,
                    rows=len(rows) if rows is not None else None,
                    error=rows is None)
            if obs_qid is not None:
                _obs.note_query_end(
                    obs_qid,
                    rows=len(rows) if rows is not None else None,
                    error=rows is None)
            if qid is not None:
                _catalog.BufferCatalog.get().ledger.sweep_query(
                    qid, digest=digest or self._last_digest)

    # -- serving path (serve/scheduler.py) ---------------------------------
    def _serve_enabled(self) -> bool:
        return self.conf.get(_serve.SERVE_ENABLED)

    def _collect(self, node: LNode) -> List[tuple]:
        """Plan + drain one query, through the serving scheduler when
        spark.rapids.tpu.serve.enabled is set. Traced, the query's span
        carries the process's CPU over it (``process_cpu_us``)."""
        from ..exec.base import query_scope, timed

        with query_scope(_next_query_id()), \
                timed(None, "TpuSession.query", self._trace,
                      process_cpu=True):
            if not self._serve_enabled():
                return self._run_collect(self._execute(node))
            return self._collect_serve(node)

    def _collect_serve(self, node: LNode) -> List[tuple]:
        """Serve-path drain with the OOM requeue contract (ROADMAP item
        4's failure mode): an admitted query whose runtime peak busts
        its static forecast — and whose spill/retry/split recovery
        (memory/retry.py) still couldn't complete it at the CURRENT
        occupancy — releases its reservation (the finally below) and is
        resubmitted exactly ONCE with its forecast inflated to the
        observed peak watermark, so the scheduler queues it until that
        much headroom is real. A second typed OOM propagates: forecast
        misses degrade to queueing, genuine can't-fit degrades to a
        named error, never a crash loop."""
        from ..memory.retry import TpuOOMError
        from ..serve import QueryScheduler

        try:
            return self._collect_serve_once(node)
        except TpuOOMError as e:
            from ..memory.catalog import BufferCatalog

            # THIS query's observed need: the ledger's per-query peak
            # when it tracked the failed attempt (the attributed figure
            # — catalog-registered buffers this query actually owned),
            # else the catalog watermark the typed error captured at its
            # failure. NEVER the process-lifetime peak_device_bytes,
            # which an earlier heavy query pins forever and would
            # inflate every later small query's requeue. Capped at the
            # total budget so a transient OOM can never convert into a
            # permanent ServeAdmissionRejected (acquire rejects
            # forecasts above the budget outright).
            cat = BufferCatalog.get()
            led_peak = cat.observed_query_peak(self._active_query)
            observed = led_peak or getattr(e, "watermark", None) or 0
            budget, _, _ = cat.admission_state()
            if budget is not None:
                observed = min(observed, budget)
            QueryScheduler.get(self.conf).note_oom_requeue(
                self.serve_id, self._last_digest or "", observed or None,
                forecast_source="ledger" if led_peak else "watermark")
            # the resubmission is an execution of its own: a fresh id
            # (its query_start must not repeat the failed attempt's)
            from ..exec.base import query_scope

            with query_scope(_next_query_id()):
                return self._collect_serve_once(
                    node, forecast_floor=observed or None)

    def _collect_serve_once(self, node: LNode,
                            forecast_floor: Optional[int] = None
                            ) -> List[tuple]:
        """Submit-through-scheduler: plan on the calling thread (host
        work of a queued query overlaps the running query's device
        compute), admit against the peak-HBM forecast, host-prefetch
        scans after admission but BEFORE the device semaphore, then
        drain. The reservation releases in a finally so a failed query
        frees its headroom. ``forecast_floor``: the OOM-requeue path's
        inflated forecast (the observed peak watermark of the failed
        attempt) — admission then waits for headroom reality showed the
        query needs, not what the analyzer guessed."""
        from ..serve import QueryScheduler, SharedPlanCache
        from ..serve.scheduler import SERVE_PRIORITY

        sched = QueryScheduler.get(self.conf)
        with self._plan_lock:
            final = self._execute(node)
            digest = self._last_digest or ""
            plan_key = self._serve_plan_key
            analysis = self._serve_analysis
            pending = self._obs_take_pending()
            qid = self._active_query
        # the analyzer's peak-HBM forecast whenever it produced one —
        # "bounded" (forecasts ASSERTED) is a stronger property than the
        # admission check needs: parquet plans forecast a peak (footer-
        # derived residency) without being fully bounded
        forecast = analysis.peak_hbm if analysis is not None else None
        forecast_source = "analyzer"
        # the measured-stats loop (ROADMAP 5a): once the HBM ledger has
        # observed a completed run of this plan digest, its per-query
        # peak replaces the static bound — admission charges what the
        # plan was MEASURED to hold, not what the analyzer guessed
        from ..memory.catalog import BufferCatalog as _BC

        observed = _BC.get().ledger.observed_peak(digest)
        if observed:
            forecast = observed
            forecast_source = "ledger"
        if forecast_floor is not None:
            forecast = max(forecast or 0, forecast_floor)
        try:
            # priority/timeout/depth are THIS session's settings — the
            # scheduler singleton may have been created by another one
            ticket = sched.acquire(
                self.serve_id, self.conf.get(SERVE_PRIORITY), forecast,
                digest, conf_=self.conf,
                forecast_source=forecast_source)
        except Exception:
            # a reject/timeout must still CLOSE the query_start window
            # _execute opened, or the offline profiler attributes every
            # later event to the dead query
            if self.events.enabled and qid is not None:
                _events.emit("query_end", query_id=qid, dur=0, rows=None,
                             error=True)
            raise
        try:
            if isinstance(final, ColumnarToRowExec):
                # pipelined phase split: host-side decode starts now, on
                # the shared pools, while whoever holds the semaphore
                # keeps the device busy
                final.tpu_child.host_prefetch()
            rows = self._run_collect(final, qid=qid, pending=pending,
                                     digest=digest)
            if plan_key is not None:
                SharedPlanCache.get().mark_warm(plan_key)
            return rows
        finally:
            sched.release(ticket)

    def export_trace(self, path: str) -> str:
        """Write the session's event ring buffer as Chrome/Perfetto
        trace-event JSON — open it directly in ui.perfetto.dev. Works with
        or without eventLog.dir (the ring buffer always backs it); raises
        when event logging is off entirely."""
        if not self.events.enabled:
            raise RuntimeError(
                "event logging is off: set spark.rapids.tpu.eventLog."
                "enabled (ring buffer only) or eventLog.dir (JSONL file) "
                "to record a trace")
        return _events.export_chrome_trace(self.events.records(), path)

    def explain_metrics(self) -> str:
        """Per-operator metrics report for the LAST executed plan — the
        profiler's user-facing output (reference analog: the SQL-UI metric
        table each GpuExec publishes). Every exec line shows wall-clock
        totalTime, output rows/batches, and bytesTouched; runs under
        spark.rapids.tpu.metrics.deviceSync.enabled add device-accurate
        opTimeDevice and a derived per-op HBM GB/s labeled by the lane
        that fed it (hbm_gbps[device] preferred; hbm_gbps[host]
        otherwise — the host lane understates async device work, so its
        figure overstates bandwidth and says so in its label). When the
        cost plane harvested programs during the run (event log / obs
        on), per-op xla_bytes/xla_flops/xla_gbps columns report what XLA
        actually compiled. The footer counts XLA pipeline compile-cache
        misses by site for THIS plan's run (a recompile-storm detector)
        plus the harvested trace/compile time split. How to read it:
        docs/tuning.md."""
        from ..exec.base import TpuExec, format_metrics

        plan = self.last_executed_plan
        if plan is None:
            return "<no plan executed yet>"
        node = plan.tpu_child if isinstance(plan, ColumnarToRowExec) else plan
        if not isinstance(node, TpuExec):
            return "<last plan ran on CPU; no device metrics>"
        return format_metrics(
            node, getattr(self, "_compile_baseline", None),
            cost_since=getattr(self, "_cost_baseline", None),
            boundary=plan if isinstance(plan, ColumnarToRowExec) else None)


class GroupedData:
    def __init__(self, df: "DataFrame", keys: Sequence[E.Expression]):
        self._df = df
        self._keys = list(keys)

    def agg(self, *aggs: A.AggregateExpression) -> "DataFrame":
        return DataFrame(
            self._df.session,
            LNode("aggregate", (tuple(self._keys), tuple(aggs)), (self._df.node,)),
        )

    def count(self) -> "DataFrame":
        return self.agg(A.agg(A.Count(), "count"))


class DataFrameReader:
    """reference analog: spark.read with the plugin's scan rules."""

    def __init__(self, session: "TpuSession"):
        self._session = session

    def parquet(self, path: str,
                columns: Optional[Sequence[str]] = None) -> "DataFrame":
        opts = (("columns", tuple(columns) if columns else None),)
        return DataFrame(
            self._session, LNode("file_scan", ("parquet", path, opts)))

    def csv(self, path: str, schema: Optional[StructType] = None,
            header: bool = True, sep: str = ",") -> "DataFrame":
        opts = (("schema", schema), ("header", header), ("sep", sep))
        return DataFrame(
            self._session, LNode("file_scan", ("csv", path, opts)))

    def orc(self, path: str,
            columns: Optional[Sequence[str]] = None) -> "DataFrame":
        opts = (("columns", tuple(columns) if columns else None),)
        return DataFrame(
            self._session, LNode("file_scan", ("orc", path, opts)))


class DataFrameWriter:
    """reference analog: df.write through GpuParquetFileFormat +
    GpuFileFormatWriter's commit protocol."""

    def __init__(self, df: "DataFrame"):
        self._df = df

    def _batches(self):
        df = self._df
        sess = df.session
        final = sess._execute(df.node)
        schema = final.output_schema
        # capture NOW: by the time the generator drains, another query on
        # this session may have replaced _active_query (and, same race,
        # overwritten the shared _pending_obs slot)
        qid = sess._active_query
        obs_pending = sess._obs_take_pending()

        def gen():
            import time as _time

            t0 = _time.perf_counter_ns()
            # activated here, on the draining thread, so note_batch
            # attribution lands on this query (and the finally below
            # guarantees the matching end)
            obs_qid = sess._obs_begin(obs_pending)
            from ..memory import ledger as _ledger

            scope = _ledger.query_scope(qid) if qid is not None else None
            ok = False
            try:
                if scope is not None:
                    scope.__enter__()
                if isinstance(final, ColumnarToRowExec):
                    # columnar fast path: hand device batches to the writer
                    yield from final.tpu_child.execute_columnar()
                else:
                    from ..columnar.batch import batch_from_rows

                    buf: List[tuple] = []
                    for row in (
                        r for p in range(final.num_partitions)
                        for r in final.execute_rows_partition(p)
                    ):
                        buf.append(row)
                        if len(buf) >= 65536:
                            yield batch_from_rows(buf, schema)
                            buf = []
                    if buf:
                        yield batch_from_rows(buf, schema)
                ok = True
            finally:
                if scope is not None:
                    scope.__exit__(None, None, None)
                if sess.events.enabled:
                    # writer path: duration only (a row count would force
                    # a device sync per batch just for logging); the
                    # finally closes the window even on error/abandonment
                    _events.emit("query_end", query_id=qid,
                                 dur=_time.perf_counter_ns() - t0,
                                 rows=None, error=not ok)
                if obs_qid is not None:
                    _obs.note_query_end(obs_qid, rows=None, error=not ok)
                if qid is not None:
                    _catalog.BufferCatalog.get().ledger.sweep_query(
                        qid, digest=sess._last_digest)

        return gen(), schema

    def parquet(self, path: str, compression: str = "snappy") -> Dict[str, int]:
        from ..io.parquet import write_parquet

        batches, schema = self._batches()
        return write_parquet(batches, path, schema, compression)

    def orc(self, path: str, compression: str = "zstd") -> Dict[str, int]:
        from ..io.orc import write_orc

        batches, schema = self._batches()
        return write_orc(batches, path, schema, compression)

    def csv(self, path: str) -> Dict[str, int]:
        from ..io.csv import write_csv

        batches, schema = self._batches()
        return write_csv(batches, path, schema)


class DataFrame:
    def __init__(self, session: TpuSession, node: LNode):
        self.session = session
        self.node = node

    @property
    def write(self) -> DataFrameWriter:
        return DataFrameWriter(self)

    # -- transformations ---------------------------------------------------
    def select(self, *exprs: Union[str, E.Expression]) -> "DataFrame":
        return DataFrame(
            self.session,
            LNode("project", (tuple(_as_expr(e) for e in exprs),), (self.node,)),
        )

    def where(self, cond: E.Expression) -> "DataFrame":
        return DataFrame(self.session, LNode("filter", (cond,), (self.node,)))

    filter = where

    def with_column(self, name: str, expr: E.Expression) -> "DataFrame":
        schema = self.schema
        exprs: List[E.Expression] = []
        replaced = False
        for f in schema.fields:
            if f.name == name:
                exprs.append(E.Alias(expr, name))
                replaced = True
            else:
                exprs.append(E.col(f.name))
        if not replaced:
            exprs.append(E.Alias(expr, name))
        return self.select(*exprs)

    def group_by(self, *keys: Union[str, E.Expression]) -> GroupedData:
        return GroupedData(self, [_as_expr(k) for k in keys])

    def agg(self, *aggs: A.AggregateExpression) -> "DataFrame":
        return GroupedData(self, []).agg(*aggs)

    def order_by(self, *exprs: Union[str, E.Expression],
                 ascending: Union[bool, Sequence[bool]] = True,
                 nulls_first: Union[None, bool, Sequence[Optional[bool]]] = None,
                 ) -> "DataFrame":
        es = [_as_expr(e) for e in exprs]
        if isinstance(ascending, bool):
            ascending = [ascending] * len(es)
        if nulls_first is None or isinstance(nulls_first, bool):
            nulls_first = [nulls_first] * len(es)
        orders = tuple(zip(ascending, nulls_first))
        return DataFrame(self.session, LNode("sort", (tuple(es), orders), (self.node,)))

    sort = order_by

    def limit(self, n: int) -> "DataFrame":
        """Global limit (Spark CollectLimit semantics: at most n rows total,
        taken from partitions in order)."""
        return DataFrame(
            self.session, LNode("collect_limit", (n,), (self.node,)))

    def local_limit(self, n: int) -> "DataFrame":
        """Per-partition limit (Spark LocalLimit)."""
        return DataFrame(self.session, LNode("limit", (n,), (self.node,)))

    def explode(self, values: Sequence[E.Expression], name: str = "col",
                pos: bool = False) -> "DataFrame":
        """explode(array(e1..eN)): one output row per element, keeping the
        input columns (posexplode with ``pos=True``)."""
        return DataFrame(
            self.session,
            LNode("generate", (tuple(values), name, pos), (self.node,)))

    def cross_join(self, other: "DataFrame",
                   condition: Optional[E.Expression] = None) -> "DataFrame":
        """Cartesian product, optionally with a residual condition."""
        return DataFrame(
            self.session,
            LNode("join", ((), (), "inner", condition),
                  (self.node, other.node)),
        )

    def union(self, other: "DataFrame") -> "DataFrame":
        return DataFrame(
            self.session, LNode("union", (), (self.node, other.node))
        )

    def join(self, other: "DataFrame", on: Union[str, Sequence[str], Sequence[Tuple[str, str]]],
             how: str = "inner", condition: Optional[E.Expression] = None) -> "DataFrame":
        if isinstance(on, str):
            on = [on]
        pairs = [
            (k, k) if isinstance(k, str) else k for k in on
        ]
        lkeys = tuple(E.col(a) for a, _ in pairs)
        rkeys = tuple(E.col(b) for _, b in pairs)
        return DataFrame(
            self.session,
            LNode("join", (lkeys, rkeys, how, condition), (self.node, other.node)),
        )

    def with_windows(self, *wexprs) -> "DataFrame":
        """Append window columns (function OVER partition/order spec)."""
        return DataFrame(
            self.session, LNode("window", (tuple(wexprs),), (self.node,))
        )

    def distinct(self) -> "DataFrame":
        keys = tuple(E.col(f.name) for f in self.schema.fields)
        return DataFrame(
            self.session, LNode("aggregate", (keys, ()), (self.node,))
        )

    # -- caching -------------------------------------------------------------
    def cache(self) -> "DataFrame":
        """Mark this plan as cached (Spark's ``Dataset.cache()``) and
        return the frame. Nothing runs now: the first action over the
        plan — from this frame or any other of the session built over
        the same plan and the same files — fills the relation on the
        device (one shard on every device of a mesh) and every later one
        is served from it until ``unpersist()``. A file rewritten in
        between refills. ``explain()`` names the cached scan."""
        self.session.cache_manager.mark(self.node)
        return self

    persist = cache

    def unpersist(self) -> "DataFrame":
        """Forget the mark and free what the relation held on the
        device(s)."""
        self.session.cache_manager.drop(self.node)
        return self

    @property
    def is_cached(self) -> bool:
        return self.session.cache_manager.lookup(self.node) is not None

    # -- actions -----------------------------------------------------------
    @property
    def schema(self) -> StructType:
        return _lower(self.node, self.session.conf).output_schema

    @property
    def columns(self) -> List[str]:
        return self.schema.names

    def collect(self) -> List[tuple]:
        return self.session._collect(self.node)

    def count(self) -> int:
        return len(self.collect())

    def to_pydict(self) -> Dict[str, List[Any]]:
        rows = self.collect()
        names = self.columns
        return {n: [r[i] for r in rows] for i, n in enumerate(names)}

    def explain(self) -> str:
        """Tagging report (which operators run on TPU and why not) plus —
        when sql.analysis.enabled — the static plan analysis: per-operator
        batch layouts, nullability, the compile-signature forecast
        (recompile-storm detection), and the predicted peak HBM footprint
        checked against the memory budget. Nothing is lowered or executed
        and no device allocation happens (see docs/tuning.md)."""
        conf = self.session.conf
        cpu = _lower(self.node, conf, self.session.cache_manager)
        from ..plugin.overrides import PlanMeta

        meta = PlanMeta(cpu, conf)
        meta.tag_for_tpu()
        lines = meta.explain_lines()
        from ..conf import ANALYSIS_ENABLED, SQL_ENABLED

        if conf.get(SQL_ENABLED) and conf.get(ANALYSIS_ENABLED):
            from ..plugin.plananalysis import analyze_plan

            analysis = analyze_plan(cpu, conf, meta=meta)
            self.session.last_analysis = analysis
            lines.extend(analysis.render_lines())
        return "\n".join(lines)
