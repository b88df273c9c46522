"""Hash-aggregate exec (sort-compatible implementation on TPU).

Reference analog: GpuHashAggregateExec (aggregate.scala:341-806): per-batch
partial aggregation, a concat+merge loop across batches, then the final
projection. The cudf hash groupby is replaced by ops/groupby's
sort+segment-reduce (one fused XLA program per batch); the merge loop reuses
the same kernel with each function's merge ops, exactly mirroring Spark's
update/merge aggregate split so partial results can cross an exchange.

Modes (expr/aggregates.py): COMPLETE (no exchange), PARTIAL (emit buffer
columns), FINAL (merge buffer columns, evaluate results).
"""
from __future__ import annotations

import contextlib
import functools
from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..columnar import ColumnarBatch
from ..conf import RapidsConf
from ..expr import aggregates as A
from ..expr import expressions as E
from ..expr.eval import ColV, DictV, StrV, Val, lower, materialize_dict
from ..expr.values import val_capacity
from ..ops import concat as concat_ops
from ..ops import groupby as groupby_ops
from ..ops.sort import max_string_len
from ..types import StructField, StructType
from ..columnar.column import choose_capacity
from .base import (
    NO_SPAN,
    TpuExec,
    batch_from_vals,
    batch_signature,
    count_scalar,
    program,
    vals_of_batch,
)


_AGG_CACHE: dict = {}


# ---------------------------------------------------------------------------
# Aggregation strategy chooser (conf sql.agg.strategy). AUTO picks from
# what the code can observe: the backend and the capacity bucket. On an
# accelerator it resolves MATMUL and nothing else: the one lowering the
# v5e compiler takes at every capacity the benchmark's cells run (it
# refuses RADIX at capacity 256: "Scoped allocation with size 19.14M and
# limit 16.00M" in the 64-bit ``cumsum`` of ops/radix_bin._tile_diffs),
# which sums exact floats too, as fixed-point limbs (ops/bucket_reduce).
# The roofline peaks below price the join chooser.
# ---------------------------------------------------------------------------
#: sustained streaming fraction of peak HBM bandwidth (exec/join.py)
_HBM_DERATE = 0.6
#: CPU-backend AUTO: below this capacity the native scatter's serial
#: walk is cheap and the radix sort dominates, so SCATTER keeps its
#: round-1-measured win; at or above it the SCATTER dialect's byte
#: amplification (the while-loop accumulator XLA charges per
#: instruction — 19.4 GB vs a 772 MB bound at cap=2^24, BENCH_r09)
#: is the dominant cost and the tiled RADIX lowering takes over.
#: Lowered 2^22 -> 2^21 in round 14: the join and parquet bench shapes
#: both feed a cap=2^21 aggregate whose scatter plan alone charged
#: 2.36 GB / 1.77 GB (the bulk of those shapes' 29.8x / 15x
#: amplification) — the byte model says the flip point sits below the
#: old threshold, and the merge gate is bytes, not shared-box wall clock
_RADIX_CPU_MIN_CAP = 1 << 21


def _roofline_peaks(conf: RapidsConf, backend: str) -> Tuple[float, float]:
    """(peak HBM bytes/s, peak MAC/s) for a chooser: the conf-declared
    roofline peaks when set, else the per-backend defaults — the same
    resolution order the roofline report uses."""
    from ..xla_cost import (BACKEND_PEAKS, ROOFLINE_PEAK_HBM_GBPS,
                            ROOFLINE_PEAK_TFLOPS)

    if backend not in BACKEND_PEAKS:
        # a backend nobody measured: refuse instead of borrowing a row
        raise ValueError(
            f"no roofline peaks for backend {backend!r} (known: "
            f"{sorted(BACKEND_PEAKS)}); the aggregation chooser cannot "
            "price strategies for it")
    dg, dt = BACKEND_PEAKS[backend]
    g = conf.get(ROOFLINE_PEAK_HBM_GBPS) or dg
    t = conf.get(ROOFLINE_PEAK_TFLOPS) or dt
    return g * 1e9, t * 1e12 / 2.0


def choose_agg_strategy(
    conf: RapidsConf,
    cap: int,
    update_ops: Sequence[str],
    update_exprs: Sequence[Optional[E.Expression]],
    backend: Optional[str] = None,
) -> Tuple[str, str]:
    """Pick the grouped-aggregation lowering for ONE plan shape from its
    STATIC layout — backend, capacity bucket, aggregated columns — never
    from data (the choice must be a trace-time constant or it would churn
    the compile cache). Returns ``(strategy, reason)``; the reason rides
    into explain_metrics and the 'agg_strategy' event so a wrong
    prediction is debuggable offline. AUTO resolves:

      * CPU backend -> SCATTER below _RADIX_CPU_MIN_CAP (native segment
        scatters; both the materialized one-hot and the bitonic sort
        lose there in wall clock, measured in round 1), RADIX at or
        above it — the scatter dialect's XLA-charged byte amplification
        dominates at scale and the merge gate is bytes, not the wall
        clock of a shared box. Exact float sums without variableFloatAgg
        keep RADIX out (its stream split is order-insensitive);
      * an accelerator -> MATMUL, at every capacity and for every
        aggregate: the lowering the chip is known to compile and to sum
        right (see above).
    """
    from ..conf import AGG_STRATEGY, IMPROVED_FLOAT_OPS

    mode = conf.get(AGG_STRATEGY)
    if mode != "AUTO":
        return mode, "forced by spark.rapids.tpu.sql.agg.strategy"
    if backend is None:
        backend = jax.default_backend()
    if backend != "cpu":
        from ..xla_cost import BACKEND_PEAKS

        if backend not in BACKEND_PEAKS:
            # a backend nobody ran: refuse instead of guessing a lowering
            raise ValueError(
                f"the aggregation chooser knows no backend {backend!r} "
                f"(known: {sorted(BACKEND_PEAKS)}); AUTO resolves only a "
                "lowering the backend is known to compile")
        return ("MATMUL",
                f"AUTO: {backend} backend — the one-hot limb matmul is "
                "the lowering its compiler takes at every capacity (it "
                "refuses RADIX's 64-bit cumsum at small ones)")
    # exact float sums demand the order-preserving scatter adds; RADIX's
    # NORMAL/BIG stream split is order-insensitive, so AUTO may only
    # pick it when the query opted into variableFloatAgg semantics
    exact_float_sum = not conf.get(IMPROVED_FLOAT_OPS) and any(
        op == "sum" and e is not None
        and getattr(e.dtype, "is_floating", False)
        for op, e in zip(update_ops, update_exprs))
    if cap >= _RADIX_CPU_MIN_CAP and not exact_float_sum:
        return ("RADIX",
                "AUTO: CPU backend at cap>=2^21 — the scatter "
                "dialect's while-loop accumulator amplifies "
                "XLA-charged bytes ~25x past the layout bound "
                "(BENCH_r09); the tiled radix lowering is sized to "
                "the bound")
    return ("SCATTER",
            "AUTO: CPU backend — native segment scatters beat both "
            "the materialized one-hot and the bitonic sort")


def _agg_pipeline(
    chain,  # fusable execs below this aggregate (fused into the update step)
    key_exprs: Tuple[E.Expression, ...],
    key_dtypes: Tuple[T.DataType, ...],
    value_exprs: Tuple[Optional[E.Expression], ...],
    ops: Tuple[str, ...],
    sig: tuple,
    cap: int,
    str_max_lens: Tuple[int, ...],
    approx_float_sum: bool = False,
    sides: Sequence[tuple] = (),
    str_val_max_lens: Tuple[int, ...] = (),
    nonnull: Tuple[bool, ...] = (),
    strategy: Optional[str] = None,
    donate: Tuple[int, ...] = (),
):
    """ONE fused program: child chain (filter/project/join probe...),
    key+input projection, groupby reduce — a whole query stage per
    dispatch. ``str_val_max_lens``: static byte bound per string-typed
    min/max input, in order (drives the rank sort's chunk count).
    ``nonnull``: the plan analyzer's validity-elision flags for the input
    columns (ops/filter_gather.elide_validity). ``strategy``: the
    resolved aggregation lowering (part of the cache key — a strategy
    flip is a different program)."""
    from .base import side_signature

    key = (
        tuple(e.fusion_key() for e in chain), key_exprs, key_dtypes,
        value_exprs, ops, sig, cap, str_max_lens, approx_float_sum,
        side_signature(sides), str_val_max_lens, nonnull, strategy,
    )
    chain_t = tuple(chain)

    def build():
        @program("agg_update")
        def run(cols, num_rows, side_args):
            from ..ops.filter_gather import elide_validity, live_of

            live = live_of(num_rows, cap)
            cols = elide_validity(cols, live, nonnull)
            with jax.named_scope("fused_chain"):
                for e, s in zip(chain_t, side_args):
                    cols, live = e.lower_batch(cols, live, cap, s)
            with jax.named_scope("agg_update"):
                keys = [lower(e, cols, cap) for e in key_exprs]
                vals: List[Optional[ColV]] = []
                for e in value_exprs:
                    vals.append(
                        None if e is None else lower(e, cols, cap))
                if key_exprs:
                    return groupby_ops.groupby_agg(
                        keys, list(key_dtypes), vals, list(ops), live,
                        str_max_lens, approx_float_sum=approx_float_sum,
                        str_val_max_lens=str_val_max_lens,
                        strategy=strategy,
                    )
                outs = groupby_ops.reduce_no_keys(
                    vals, list(ops), live,
                    str_val_max_lens=str_val_max_lens)
                return [], outs, jnp.int32(1)

        return jax.jit(run, donate_argnums=donate)

    from .base import cached_pipeline

    return cached_pipeline(_AGG_CACHE, key, "agg_update", build,
                           donate=donate)


def _fused_agg_trace(key_exprs, key_dts, value_exprs, update_ops, merge_ops,
                     eval_exprs, approx, bucket_min, chain_t,
                     strategy=None):
    """The shared in-trace core of BOTH fused aggregate programs (the
    scan→agg stage fusion and the whole-plan fusion): returns
    ``(update_batch, finish)`` closures. ``update_batch`` lowers one
    batch's fused child chain + key/value projection + update groupby;
    ``finish`` concat-pads the partials, runs the merge groupby, and
    applies the result projection (non-PARTIAL). One definition so the
    two paths can never drift semantically — only their ingest differs
    (decoded row groups vs direct batch columns)."""
    nkeys = len(key_exprs)

    def agg_once(keys, vals, ops_, live):
        if key_exprs:
            k_, a_, nseg = groupby_ops.groupby_agg(
                keys, list(key_dts), vals, list(ops_), live,
                (), approx_float_sum=approx, strategy=strategy)
            return list(k_) + list(a_), nseg
        a_ = groupby_ops.reduce_no_keys(vals, list(ops_), live)
        return list(a_), jnp.int32(1)

    # the phases carry the words their programs carry when they run alone
    # (base.SCOPE_WORDS), so a trace of the fused program still tells
    # decode, chain, update and merge apart
    def update_batch(cols, live, cap, side_args):
        with jax.named_scope("fused_chain"):
            for e, s in zip(chain_t, side_args):
                cols, live = e.lower_batch(cols, live, cap, s)
        with jax.named_scope("agg_update"):
            keys = [lower(e, cols, cap) for e in key_exprs]
            vals = [None if e is None else lower(e, cols, cap)
                    for e in value_exprs]
            return agg_once(keys, vals, update_ops, live)

    def finish(partial_sets):
        if len(partial_sets) == 1:
            merged_vals, nseg = partial_sets[0]
        else:
            # batches/row groups may carry DIFFERENT dictionaries: dict
            # group keys expand before the cross-partial concat
            col_parts = [
                [materialize_dict(c) if isinstance(c, DictV) else c
                 for c in p[0]]
                for p in partial_sets
            ]
            counts = [p[1] for p in partial_sets]
            pcaps = [p[0][0].validity.shape[0] for p in partial_sets]
            out_cap = choose_capacity(sum(pcaps), bucket_min)
            with jax.named_scope("agg_merge"):
                cols2, mask, _ = concat_ops.concat_padded_cols(
                    col_parts, counts, out_cap)
                merged_vals, nseg = agg_once(
                    cols2[:nkeys], cols2[nkeys:], merge_ops, mask)
        if eval_exprs is not None:
            ocap = (merged_vals[0].validity.shape[0]
                    if merged_vals else 1)
            with jax.named_scope("project"):
                return [lower(e, merged_vals, ocap)
                        for e in eval_exprs], nseg
        return merged_vals, nseg

    return update_batch, finish


#: the merge's concat programs, process-wide and keyed by structure alone:
#: an aggregate is built anew for every query's plan, and a cache of its
#: own would re-trace the program (and count a compile miss) every query
_MERGE_CACHE: dict = {}


def _merge_concat(sigs: Tuple[tuple, ...], takes: Tuple[int, ...],
                  out_cap: int):
    """ONE program for a synced merge's input: each partial (``sigs``) cut
    to its first ``takes`` slots where that is under its capacity
    (``ops/concat.live_prefix``), its dictionary keys expanded there, and
    the parts spliced into ``out_cap`` rows with their row counts as an
    operand and their byte counts read from the expanded offsets, so that
    the host needs no string length. Each string column's byte pool is
    the sum of its parts' pools, or, where every part held it as a
    dictionary, what ``out_cap`` rows of its longest entry can hold if
    that is less."""
    key = ("merge_concat", sigs, takes, out_cap)

    def build():
        @program("agg_update")
        def run(parts, counts):
            with jax.named_scope("agg_merge"):
                col_parts = [
                    concat_ops.live_prefix(vals, take)
                    if take < val_capacity(vals[0]) else vals
                    for vals, take in zip(parts, takes)]
                char_caps = []
                for j, v in enumerate(col_parts[0]):
                    if not isinstance(v, (StrV, DictV)):
                        continue
                    col = [p[j] for p in col_parts]
                    cap = sum(c.mat_cap if isinstance(c, DictV)
                              else int(c.chars.shape[0]) for c in col)
                    if all(isinstance(c, DictV) for c in col):
                        cap = min(cap, choose_capacity(
                            max(1, out_cap * max(c.max_len for c in col)),
                            128))
                    char_caps.append(cap)
                col_parts = [[materialize_dict(v) if isinstance(v, DictV)
                              else v for v in vals] for vals in col_parts]
                byte_counts = [[v.offsets[counts[i]] for v in vals
                                if isinstance(v, StrV)]
                               for i, vals in enumerate(col_parts)]
                return concat_ops.concat_pieces_traced(
                    col_parts, [counts[i] for i in range(len(parts))],
                    byte_counts, out_cap, char_caps)

        return jax.jit(run)

    from .base import cached_pipeline

    return cached_pipeline(_MERGE_CACHE, key, "agg_update", build)


class TpuHashAggregateExec(TpuExec):
    def __init__(
        self,
        conf: RapidsConf,
        group_exprs: Sequence[E.Expression],
        agg_exprs: Sequence[A.AggregateExpression],
        child: TpuExec,
        mode: str = A.COMPLETE,
    ):
        super().__init__(conf, [child])
        self.group_exprs = list(group_exprs)
        self.agg_exprs = list(agg_exprs)
        self.mode = mode
        child_schema = child.output_schema

        # group key output fields. FINAL consumes a partial's
        # [keys..., buffers...] output, where computed key EXPRESSIONS are
        # already evaluated — keys bind positionally there, never by
        # re-binding the original expression (whose input columns no
        # longer exist; reference: the FINAL GpuHashAggregateExec binds
        # against the partial attributes, aggregate.scala:341)
        self._key_fields: List[StructField] = []
        self._bound_keys: List[E.Expression] = []
        for i, g in enumerate(self.group_exprs):
            name = g.name if isinstance(g, (E.UnresolvedAttribute,)) else (
                g.name if isinstance(g, E.Alias) else f"key{i}"
            )
            if self.mode == A.FINAL:
                cf = child_schema.fields[i]
                b: E.Expression = E.BoundReference(
                    i, cf.dataType, cf.nullable)
            else:
                b = E.bind_references(g, child_schema)
            self._key_fields.append(StructField(name, b.dtype, b.nullable))
            self._bound_keys.append(b)

        # bind each aggregate function's input against the child schema so
        # dtype/buffer layout resolve (reference: boundInputReferences in
        # aggregate.scala)
        import dataclasses as _dc

        nk = len(self.group_exprs)
        self._bound_funcs: List[A.AggregateFunction] = []
        bufpos = nk
        for ae in self.agg_exprs:
            f = ae.func
            if f.input is not None:
                if self.mode == A.FINAL:
                    # child emits [keys..., buffers...]: bind the function's
                    # input to its first buffer column so dtype/layout
                    # resolve from the partial's output types
                    bf = child_schema.fields[bufpos]
                    f = _dc.replace(
                        f, child=E.BoundReference(bufpos, bf.dataType, True)
                    )
                else:
                    f = _dc.replace(
                        f, child=E.bind_references(f.child, child_schema)
                    )
            self._bound_funcs.append(f)
            bufpos += f.num_buffers

        # per-function buffer layout
        self._buf_fields: List[StructField] = []
        self._update_exprs: List[Optional[E.Expression]] = []
        self._update_ops: List[str] = []
        self._merge_ops: List[str] = []
        self._buf_slices: List[Tuple[int, int]] = []  # [start, end) per func
        pos = 0
        for ai, f in enumerate(self._bound_funcs):
            ops = f.update_ops
            bs = f.buffer_schema
            self._buf_slices.append((pos, pos + len(ops)))
            for j, ((op, in_expr), bdt) in enumerate(zip(ops, bs)):
                self._buf_fields.append(
                    StructField(f"agg{ai}_buf{j}", bdt, True)
                )
                if in_expr is None:
                    self._update_exprs.append(None)
                else:
                    if self.mode == A.FINAL:
                        # inputs are the buffer columns of the child
                        self._update_exprs.append(None)  # filled below
                    else:
                        self._update_exprs.append(
                            E.bind_references(in_expr, child_schema)
                        )
                self._update_ops.append(op)
                pos += 1
            self._merge_ops.extend(f.merge_ops)

        if self.mode == A.FINAL:
            # child emits [keys..., buffers...]; merge those buffers
            nk = len(self._key_fields)
            self._update_exprs = []
            self._update_ops = list(self._merge_ops)
            for j, bf in enumerate(self._buf_fields):
                cf = child_schema.fields[nk + j]
                self._update_exprs.append(
                    E.BoundReference(nk + j, cf.dataType, True)
                )
            # keys come straight from the child's key columns
            self._bound_keys = [
                E.BoundReference(i, f.dataType, f.nullable)
                for i, f in enumerate(child_schema.fields[:nk])
            ]
            self._key_fields = [
                StructField(kf.name, cf.dataType, cf.nullable)
                for kf, cf in zip(self._key_fields, child_schema.fields[:nk])
            ] if self._key_fields else []

        # output schema
        if self.mode == A.PARTIAL:
            self._schema = StructType(tuple(self._key_fields + self._buf_fields))
        else:
            fields = list(self._key_fields)
            for ae, f in zip(self.agg_exprs, self._bound_funcs):
                fields.append(StructField(ae.resolved_name(), f.dtype, True))
            self._schema = StructType(tuple(fields))

        # the evaluate projection runs over [keys..., buffers...]
        self._buffer_schema = StructType(tuple(self._key_fields + self._buf_fields))
        # aggregation strategy (conf sql.agg.strategy): resolved lazily
        # per capacity bucket — the choice must see the real batch shape —
        # and memoized so AUTO never flips mid-plan (the recompile guard
        # in tests/test_metrics.py pins this)
        self._strategy_by_cap: dict = {}
        self._strategy_choice: Optional[Tuple[str, str]] = None

    @property
    def output_schema(self):
        return self._schema

    def describe(self):
        keys = ", ".join(str(k) for k in self.group_exprs)
        aggs = ", ".join(a.resolved_name() for a in self.agg_exprs)
        strat = (f", strategy={self._strategy_choice[0]}"
                 if self._strategy_choice is not None else "")
        return (f"TpuHashAggregateExec(mode={self.mode}, keys=[{keys}], "
                f"aggs=[{aggs}]{strat})")

    def resolved_strategy(self, cap: int) -> Optional[str]:
        """Resolve (and memoize per capacity bucket) the aggregation
        lowering for this plan. The choice lands in describe() — and thus
        explain_metrics() — and emits ONE 'agg_strategy' event per
        (exec, capacity), so tools/tpu_profile.py can hold the chooser
        accountable against the measured op spans of the same log."""
        if not self.group_exprs:
            return None  # grand aggregates use the plain masked reduces
        hit = self._strategy_by_cap.get(cap)
        if hit is not None:
            return hit
        strategy, reason = choose_agg_strategy(
            self.conf, cap, self._update_ops, self._update_exprs)
        self._strategy_by_cap[cap] = strategy
        self._strategy_choice = (strategy, reason)
        from .. import events as _events
        from .. import obs as _obs

        if _events.enabled():
            _events.emit("agg_strategy", op=self.node_name,
                         strategy=strategy, reason=reason, cap=cap)
        if _obs.enabled():
            _obs.inc("tpu_agg_strategy", 1, strategy=strategy)
        return strategy

    # -- helpers -----------------------------------------------------------
    @contextlib.contextmanager
    def _agg_timed(self, section: str):
        """``op_timed`` for the aggregate's hot sections: the span says
        which half of a partial -> exchange -> final plan it belongs to
        (``mode``) and, once the section has resolved one, the lowering it
        ran (``strategy``)."""
        with self.op_timed(section, mode=self.mode) as span:
            yield span
            if span.on and self._strategy_choice is not None:
                span.set(strategy=self._strategy_choice[0])

    def _key_dtypes(self) -> Tuple[T.DataType, ...]:
        return tuple(f.dataType for f in self._key_fields)

    def _exprs_str_max_lens(self, exprs, batch: ColumnarBatch,
                            direct: bool) -> Tuple[int, ...]:
        """Static byte-length buckets for the string-typed expressions in
        ``exprs`` (host sync only when plain string columns exist).
        ``direct``: batch columns match the bound ordinals; otherwise (a
        fused chain below) any string passed through from a source string
        column, so the max over all source string columns is a safe
        bound."""
        lens = []
        source_max = None
        for b in exprs:
            if isinstance(b.dtype, (T.StringType, T.BinaryType)):
                if direct and isinstance(b, E.BoundReference):
                    col = batch.columns[b.ordinal]
                    if col.is_dict:
                        # dict columns carry a STATIC length bound — the
                        # one case string keys need no host sync at all
                        m = col.dictv.max_len
                    else:
                        m = int(max_string_len(StrV(col.offsets, col.chars, col.validity)))
                else:
                    if source_max is None:
                        ms = [
                            (c.dictv.max_len if c.is_dict else
                             int(max_string_len(
                                 StrV(c.offsets, c.chars, c.validity))))
                            for c in batch.columns if c.is_string
                        ]
                        source_max = max(ms) if ms else 64
                    m = source_max
                lens.append(max(4, choose_capacity(max(1, m), 4)))
        return tuple(lens)

    def _str_max_lens(self, batch: ColumnarBatch, direct: bool) -> Tuple[int, ...]:
        """Static byte-length buckets for string group keys."""
        return self._exprs_str_max_lens(self._bound_keys, batch, direct)

    def _run_batch(self, batch: ColumnarBatch, ops: Sequence[str],
                   value_exprs: Sequence[Optional[E.Expression]],
                   chain=(), live=None, nonnull=None,
                   donate_input: bool = False) -> ColumnarBatch:
        """Aggregate one (source) batch into a [keys..., buffers...] batch,
        fusing any fusable child execs into the same XLA program. The group
        count stays a device scalar — no sync. ``live``: optional (cap,)
        bool mask overriding the batch's prefix row count (used by the
        sync-free merge, where live rows are NOT a prefix).
        ``donate_input``: only the streaming per-batch UPDATE path sets
        it — merge callers re-dispatch the same partials under
        with_oom_retry_nosplit, so their inputs are never dead (the
        agg_merge verdict in plugin/donation.py)."""
        cap = batch.capacity  # batches carry their bucket even zero-column
        sml = self._str_max_lens(batch, direct=not chain)
        # string-typed min/max inputs need a static byte bound for the
        # rank sort (one per such input, in op order)
        minmax_strs = [
            e for op, e in zip(ops, value_exprs)
            if op in ("min", "max") and e is not None
            and isinstance(e.dtype, (T.StringType, T.BinaryType))
        ]
        svml = self._exprs_str_max_lens(minmax_strs, batch,
                                        direct=not chain)
        from ..conf import IMPROVED_FLOAT_OPS

        if nonnull is None:  # cold callers (merge, zero-row grand agg)
            from ..plugin.plananalysis import entry_nonnull_flags

            nonnull = entry_nonnull_flags(batch.schema, self.conf)
        sides = [e.side_vals() for e in chain]
        from .base import _donation

        don = _donation()
        mask = (don.dispatch_mask("agg_update", batch, self.conf)
                if donate_input else ())
        fn = _agg_pipeline(
            chain, tuple(self._bound_keys), self._key_dtypes(),
            tuple(value_exprs), tuple(ops), batch_signature(batch), cap, sml,
            approx_float_sum=self.conf.get(IMPROVED_FLOAT_OPS),
            sides=sides, str_val_max_lens=svml, nonnull=nonnull,
            strategy=self.resolved_strategy(cap), donate=mask,
        )
        nr = (live if live is not None
              else count_scalar(batch.num_rows_lazy))
        if mask:
            # split-and-retry re-dispatches this batch on OOM, so the
            # guard snapshots its planes and restores them on failure
            with don.guard("agg_update", batch, op=self.node_name,
                           conf=self.conf,
                           metric=self.metric("donatedBytes")):
                keys, aggs, nseg = fn(vals_of_batch(batch), nr, sides)
        else:
            keys, aggs, nseg = fn(vals_of_batch(batch), nr, sides)
        vals = list(keys) + list(aggs)
        return batch_from_vals(vals, self._buffer_schema, nseg)

    #: sync-free merges stack partials at CAPACITY; above this many stacked
    #: rows the dead-row blowup outweighs the saved host RTT (low-
    #: cardinality aggregates over many batches), so the synced path wins
    _SYNC_FREE_MERGE_MAX_ROWS = 1 << 24

    def _merge_fixed_width(self, partials: List[ColumnarBatch]) -> ColumnarBatch:
        """Sync-free merge for fixed-width buffer schemas: partials stack
        at capacity on device with a live mask, so row counts never leave
        the device (a host pull costs a host round trip per batch)."""
        caps = [max(1, b.capacity) for b in partials]
        out_cap = choose_capacity(sum(caps), self.conf.shape_bucket_min)
        with self.section("merge.concat"):
            cols, mask, total = concat_ops.concat_padded_cols(
                [vals_of_batch(b) for b in partials],
                [count_scalar(b.num_rows_lazy) for b in partials], out_cap)
        merged_in = batch_from_vals(cols, self._buffer_schema, total)
        nk = len(self._key_fields)
        merge_exprs: List[Optional[E.Expression]] = [
            E.BoundReference(nk + j, f.dataType, True)
            for j, f in enumerate(self._buf_fields)
        ]
        saved_bound = self._bound_keys
        self._bound_keys = [
            E.BoundReference(i, f.dataType, f.nullable)
            for i, f in enumerate(self._key_fields)
        ]
        try:
            with self.section("merge.reduce"):
                return self._run_batch(
                    merged_in, self._merge_ops, merge_exprs, live=mask)
        finally:
            self._bound_keys = saved_bound

    def _merge(self, partials: List[ColumnarBatch],
               span=NO_SPAN) -> ColumnarBatch:
        """Concat partial batches and re-aggregate with merge ops
        (reference: concatenateBatches + merge pass, aggregate.scala:451-476).
        A single partial passes through untouched (dict-encoded group keys
        stay encoded). Two or more merge at the rows they hold: one pull of
        their row counts, then ONE program (``_merge_concat``) cuts each
        partial to the bucket of its rows, expands its dictionary keys
        there and splices them; ``span`` gets the slots that program took
        and how many partials it cut."""
        if len(partials) == 1:
            return partials[0]
        str_cols = [
            j for j, f in enumerate(self._buffer_schema.fields)
            if isinstance(f.dataType, (T.StringType, T.BinaryType))
        ]
        import jax as _jx

        # the sync-free merge stacks partials at CAPACITY to spare a host
        # RTT per batch — the right trade only over a high-latency device
        # link. On the CPU backend the pull is free and the synced path
        # merges at the REAL row counts (~group-count rows, not millions)
        if (not str_cols
                and _jx.default_backend() != "cpu"
                and sum(max(1, b.capacity) for b in partials)
                <= self._SYNC_FREE_MERGE_MAX_ROWS):
            return self._merge_fixed_width(partials)
        from .base import host_pull

        with self.section("merge.lengths"):
            head = [b.num_rows_lazy for b in partials]
        # the one place the merge waits for the device: every update
        # dispatched so far has to finish before the counts arrive
        with self.section("merge.pull"):
            lengths = [int(x) for x in host_pull(head)]
        for b, n in zip(partials, lengths):
            if not isinstance(b.num_rows_lazy, int):
                b._num_rows = n
                for c in b.columns:
                    c.length = n
        bucket = self.conf.shape_bucket_min
        # a partial of no rows adds none; the merge keeps one to run on
        held = [(b, n) for b, n in zip(partials, lengths) if n] or [
            (partials[0], 0)]
        # each partial is taken at the bucket of the rows it holds, or
        # whole where they fill it
        takes = tuple(min(b.capacity, choose_capacity(n, bucket))
                      for b, n in held)
        total = sum(lengths)
        with self.section("merge.concat"):
            fn = _merge_concat(
                tuple(batch_signature(b) for b, _ in held), takes,
                choose_capacity(total, bucket))
            cols, _ = fn([vals_of_batch(b) for b, _ in held],
                         np.asarray([n for _, n in held], np.int32))
        span.set(slots=sum(takes),
                 cut=sum(t < b.capacity for t, (b, _) in zip(takes, held)))
        merged_in = batch_from_vals(cols, self._buffer_schema, total)
        nk = len(self._key_fields)
        merge_exprs: List[Optional[E.Expression]] = [
            E.BoundReference(nk + j, f.dataType, True)
            for j, f in enumerate(self._buf_fields)
        ]
        saved_bound = self._bound_keys
        self._bound_keys = [
            E.BoundReference(i, f.dataType, f.nullable)
            for i, f in enumerate(self._key_fields)
        ]
        try:
            with self.section("merge.reduce"):
                return self._run_batch(
                    merged_in, self._merge_ops, merge_exprs)
        finally:
            self._bound_keys = saved_bound

    def _eval_exprs(self) -> List[E.Expression]:
        """Result projection over [keys..., buffers...]."""
        exprs: List[E.Expression] = [
            E.BoundReference(i, f.dataType, f.nullable)
            for i, f in enumerate(self._key_fields)
        ]
        nk = len(self._key_fields)
        for f, (s, e) in zip(self._bound_funcs, self._buf_slices):
            refs = tuple(
                E.BoundReference(nk + j, self._buf_fields[j].dataType, True)
                for j in range(s, e)
            )
            exprs.append(f.evaluate(refs))
        return exprs

    def _evaluate(self, buffers: ColumnarBatch) -> ColumnarBatch:
        """Final projection from [keys..., buffers...] to results."""
        exprs = self._eval_exprs()
        from ..plugin.plananalysis import entry_nonnull_flags
        from .basic import _project_pipeline

        cap = buffers.columns[0].capacity if buffers.columns else 1
        fn = _project_pipeline(
            tuple(exprs), batch_signature(buffers), cap,
            entry_nonnull_flags(buffers.schema, self.conf))
        vals = fn(vals_of_batch(buffers), count_scalar(buffers.num_rows_lazy))
        return batch_from_vals(vals, self._schema, buffers.num_rows_lazy)

    # -- whole-stage fusion ------------------------------------------------
    def _can_fuse_stage(self) -> bool:
        """Fused scan→agg stages cover fixed-width keys/buffers updating
        straight from a source (string keys need a host max-length sync;
        FINAL mode consumes exchanged partials, not a scan)."""
        if self.mode == A.FINAL:
            return False
        return not any(
            isinstance(f.dataType, (T.StringType, T.BinaryType))
            for f in self._buffer_schema.fields
        )

    def _stage_fusion_on(self) -> bool:
        """Conf-gated, backend-adaptive (see sql.stageFusion): fusion buys
        fewer dispatches at the price of re-decoding pages every execution;
        on the CPU backend dispatch is free and the scan cache makes the
        separate decode a one-time cost, so AUTO skips fusion there."""
        from ..conf import STAGE_FUSION

        mode = self.conf.get(STAGE_FUSION)
        if mode != "AUTO":
            return mode == "ON"
        import jax

        return jax.default_backend() != "cpu"

    def _run_fused_stage(self, stage, chain) -> ColumnarBatch:
        """ONE jitted program for the whole stage: per-row-group parquet
        decode → fused child chain → update groupby → padded concat →
        merge groupby → (COMPLETE) result projection. Collapsing the stage
        to a single executable removes every intermediate program boundary
        — each boundary costs a dispatch/queue round trip on the TPU host
        link, and intermediate batches cost extra HBM passes (reference
        contrast: the GPU plan runs one kernel set per exec,
        aggregate.scala:341; TPU+XLA lets the whole stage fuse)."""
        from ..conf import IMPROVED_FLOAT_OPS
        from .base import side_signature

        approx = self.conf.get(IMPROVED_FLOAT_OPS)
        sides = [e.side_vals() for e in chain]
        chain_t = tuple(chain)
        rg_meta = []  # structural identity per row group
        all_args = []
        all_runs = []
        for n, cap, entries in stage:
            rg_meta.append((n, cap, tuple(k for (_, k, _, _) in entries)))
            all_args.append([list(a) for (a, _, _, _) in entries])
            all_runs.append([r for (_, _, r, _) in entries])
        eval_exprs = (tuple(self._eval_exprs())
                      if self.mode != A.PARTIAL else None)
        # one strategy per fused program: resolve at the LARGEST row-group
        # capacity — that is where the reduction cost sits, so a small
        # leading row group must not dictate the lowering for the big ones
        strategy = (self.resolved_strategy(max(c for (_, c, _) in stage))
                    if stage else None)
        key = (
            "stage", tuple(rg_meta),
            tuple(e.fusion_key() for e in chain_t),
            tuple(self._bound_keys), self._key_dtypes(),
            tuple(self._update_exprs), tuple(self._update_ops),
            tuple(self._merge_ops), eval_exprs, self.mode, approx,
            side_signature(sides), self.conf.shape_bucket_min, strategy,
        )
        def build():
            update_batch, finish = _fused_agg_trace(
                tuple(self._bound_keys), self._key_dtypes(),
                tuple(self._update_exprs), tuple(self._update_ops),
                tuple(self._merge_ops), eval_exprs, approx,
                self.conf.shape_bucket_min, chain_t, strategy=strategy)
            metas = tuple(rg_meta)
            runs_t = tuple(tuple(r) for r in all_runs)

            @program("agg_stage")
            def run(args_nested, side_args):
                from ..ops.filter_gather import live_of

                partial_sets = []
                for (n, cap, _), rg_args, rg_runs in zip(
                        metas, args_nested, runs_t):
                    cols: List[Val] = []
                    for a, r in zip(rg_args, rg_runs):
                        out = r(a)
                        if isinstance(out, DictV):
                            cols.append(out)  # dict-retained string decode
                        else:
                            cols.append(
                                ColV(out[0], out[1]) if len(out) == 2
                                else StrV(out[0], out[1], out[2]))
                    partial_sets.append(
                        update_batch(cols, live_of(n, cap), cap, side_args))
                return finish(partial_sets)

            return jax.jit(run)

        from .base import cached_pipeline

        fn = cached_pipeline(_AGG_CACHE, key, "agg_stage", build)
        vals, nseg = fn(all_args, sides)
        schema = (self._buffer_schema if self.mode == A.PARTIAL
                  else self._schema)
        return batch_from_vals(vals, schema, nseg)

    # -- whole-plan fusion: update+merge+eval as ONE program ---------------
    def _can_fuse_plan(self) -> bool:
        """The fused plan program covers fixed-width keys/buffers (string
        keys need a host max-length sync and the in-trace padded concat
        has no byte-pool splice). Unlike stage fusion it covers FINAL mode
        too — exchanged partials are just fixed-width batches here."""
        return not any(
            isinstance(f.dataType, (T.StringType, T.BinaryType))
            for f in self._buffer_schema.fields
        )

    def _fused_plan_on(self, nbatches: int) -> bool:
        """AGG_FUSED_PLAN gate. AUTO declines only multi-batch runs on the
        CPU backend: the in-trace merge stacks partials at CAPACITY to
        stay sync-free (the right trade over a high-latency device link),
        while the CPU backend's synced merge works at real row counts."""
        from ..conf import AGG_FUSED_PLAN

        mode = self.conf.get(AGG_FUSED_PLAN)
        if mode != "AUTO":
            return mode == "ON"
        import jax as _jx

        return nbatches == 1 or _jx.default_backend() != "cpu"

    def _run_fused_plan(self, batches: List[ColumnarBatch],
                        chain) -> ColumnarBatch:
        """ONE jitted program for the whole aggregate over its input
        batches: per-batch fused child chain -> key/value projection ->
        update groupby, a padded concat of the partials, the merge
        groupby, and (non-PARTIAL) the result projection. The update and
        merge passes of the round-5 engine were separate executables with
        the partial batches crossing a program boundary between them;
        collapsing them removes every intermediate dispatch/queue round
        trip AND the intermediate partials' extra HBM round trips, and
        batches dispatch as ONE async program — no host sync anywhere
        (group counts stay device scalars). Profiler evidence for why:
        see docs/tuning.md (the agg shape's device time was dominated by
        per-program dispatch gaps, not kernel time)."""
        from ..conf import IMPROVED_FLOAT_OPS
        from .base import side_signature

        approx = self.conf.get(IMPROVED_FLOAT_OPS)
        sides = [e.side_vals() for e in chain]
        chain_t = tuple(chain)
        sigs = tuple(batch_signature(b) for b in batches)
        caps = tuple(
            b.capacity if b.columns else choose_capacity(
                b.num_rows, self.conf.shape_bucket_min)
            for b in batches
        )
        eval_exprs = (tuple(self._eval_exprs())
                      if self.mode != A.PARTIAL else None)
        # one strategy per fused program, resolved at the LARGEST batch
        # capacity (a small first batch must not pick the lowering for
        # the big ones; see _run_fused_stage)
        strategy = self.resolved_strategy(max(caps)) if caps else None
        key = (
            "plan", sigs, caps, tuple(e.fusion_key() for e in chain_t),
            tuple(self._bound_keys), self._key_dtypes(),
            tuple(self._update_exprs), tuple(self._update_ops),
            tuple(self._merge_ops), eval_exprs, self.mode, approx,
            side_signature(sides), self.conf.shape_bucket_min, strategy,
        )
        def build():
            update_batch, finish = _fused_agg_trace(
                tuple(self._bound_keys), self._key_dtypes(),
                tuple(self._update_exprs), tuple(self._update_ops),
                tuple(self._merge_ops), eval_exprs, approx,
                self.conf.shape_bucket_min, chain_t, strategy=strategy)
            caps_t = caps

            @program("agg_plan")
            def run(all_cols, all_nr, side_args):
                from ..ops.filter_gather import live_of

                partial_sets = [
                    update_batch(cols, live_of(nr, cap), cap, side_args)
                    for cols, nr, cap in zip(all_cols, all_nr, caps_t)
                ]
                return finish(partial_sets)

            return jax.jit(run, donate_argnums=mask)

        from .base import _donation, cached_pipeline

        don = _donation()
        # argnum 0 is EVERY buffered batch's plane pytree: the mask is
        # non-empty only when all of them are donatable, because one
        # shared batch in the list poisons the whole dispatch
        mask = don.dispatch_mask("agg_plan", batches, self.conf)
        fn = cached_pipeline(_AGG_CACHE, key, "agg_plan", build,
                             donate=mask)
        all_nr = [count_scalar(b.num_rows_lazy) for b in batches]
        if mask:
            # the device-OOM fallback (flush_buffered) re-reads the
            # buffered batches, so the guard snapshots/restores them
            with don.guard("agg_plan", batches, op=self.node_name,
                           conf=self.conf,
                           metric=self.metric("donatedBytes")):
                vals, nseg = fn(
                    [vals_of_batch(b) for b in batches], all_nr, sides)
        else:
            vals, nseg = fn(
                [vals_of_batch(b) for b in batches], all_nr, sides)
        schema = (self._buffer_schema if self.mode == A.PARTIAL
                  else self._schema)
        return don.mark_exclusive(batch_from_vals(vals, schema, nseg))

    #: fused-plan guard: above this many stacked capacity rows the
    #: in-trace padded merge's dead-row blowup outweighs the saved
    #: dispatches, so the per-batch path (and its synced/sync-free merge
    #: choice) takes over
    _FUSED_PLAN_MAX_ROWS = 1 << 24
    #: fused-plan guard: the trace unrolls one update pass per batch and
    #: the cache key carries every batch's signature — past this many
    #: batches the compile blowup and near-zero cache reuse beat the
    #: saved dispatches
    _FUSED_PLAN_MAX_BATCHES = 16
    #: fused-plan guard: buffered INPUT batches (which may carry wide
    #: string columns even when the buffer schema is fixed-width) may pin
    #: at most this many bytes of device memory before the streaming
    #: per-batch path takes over
    _FUSED_PLAN_MAX_BYTES = 2 << 30

    # -- execution ---------------------------------------------------------
    def execute_partition(self, index: int) -> Iterator[ColumnarBatch]:
        partials: List[ColumnarBatch] = []
        ops = self._update_ops
        exprs = self._update_exprs
        # fuse any fusable execs below us into the update dispatch
        child = self.children[0]
        if child.fusable:
            source, chain = child.fused_source_chain()
        else:
            source, chain = child, ()
        if chain and any(
            op in ("min", "max") and e is not None
            and isinstance(e.dtype, (T.StringType, T.BinaryType))
            for op, e in zip(ops, exprs)
        ):
            # string min/max needs an EXACT byte bound for its rank sort.
            # Under a fused chain the bound is measured on the SOURCE
            # batch, which under-bounds a string computed by a projection
            # below us (concat/pad can grow past every source column and
            # the rank would compare only a prefix — silently wrong
            # winners). Run the chain as real execs instead: the value is
            # then a direct column of OUR input batch and its measured
            # max length is exact.
            source, chain = child, ()
        fsp = getattr(source, "fused_stage_plans", None)
        if fsp is not None and self._can_fuse_stage() and self._stage_fusion_on():
            stage = fsp(index)
            if stage:
                with self._agg_timed("stage") as span:
                    if span.on:
                        from ..io.parquet_device import stage_gathers

                        span.set(gathers=stage_gathers(stage))
                    out = self._run_fused_stage(stage, tuple(chain))
                yield self.record_batch(out)
                return
        # fused-plan buffering is INCREMENTAL: ineligible plans (OFF mode,
        # string keys/buffers) never buffer raw batches at all, and an
        # eligible run that outgrows the guards (rows, batch count,
        # AUTO-on-CPU multi-batch) flushes its buffer into streaming
        # per-batch updates — peak memory stays one input batch + partials
        # exactly as round 5, except for the bounded window the fused
        # program needs.
        from ..conf import AGG_FUSED_PLAN

        from .base import batch_bytes

        fp_mode = self.conf.get(AGG_FUSED_PLAN)
        use_fused = fp_mode != "OFF" and self._can_fuse_plan()
        batches: List[ColumnarBatch] = []
        cap_sum = 0
        byte_sum = 0
        # per-partition constant: the source schema's elision flags
        # (recomputing per batch would put a conf+schema walk on the
        # per-batch dispatch hot path)
        from ..memory.retry import is_device_oom, with_oom_retry
        from ..plugin.plananalysis import entry_nonnull_flags

        src_nonnull = entry_nonnull_flags(source.output_schema, self.conf)

        def update_with_retry(b):
            # the per-batch update under the OOM harness: a split hands
            # back one partial PER HALF — exactly what the merge path
            # already consumes (combine="list"), so the aggregate
            # completes on half-capacity update programs
            partials.extend(with_oom_retry(
                self.node_name,
                lambda piece: self._run_batch(
                    piece, ops, exprs, tuple(chain), nonnull=src_nonnull,
                    donate_input=True),
                b, self.conf, combine="list",
                on_pressure=getattr(source, "invalidate_prefetch", None)))

        def flush_buffered():
            for b in batches:
                with self._agg_timed("update"):
                    update_with_retry(b)
            batches.clear()

        for batch in source.execute_partition(index):
            nr = batch.num_rows_lazy
            if isinstance(nr, int) and nr == 0 and self.group_exprs and not chain:
                continue
            if not use_fused:
                with self._agg_timed("update"):
                    update_with_retry(batch)
                continue
            batches.append(batch)
            cap_sum += max(1, batch.capacity if batch.columns else 1)
            byte_sum += batch_bytes(batch)
            if (cap_sum > self._FUSED_PLAN_MAX_ROWS
                    or byte_sum > self._FUSED_PLAN_MAX_BYTES
                    or len(batches) > self._FUSED_PLAN_MAX_BATCHES
                    or not self._fused_plan_on(len(batches))):
                use_fused = False
                flush_buffered()
        if use_fused and batches:
            try:
                with self._agg_timed("plan"):
                    from .. import faults as _faults

                    if _faults.enabled():
                        # the fused whole-plan program is the aggregate's
                        # pipeline-dispatch boundary when it runs —
                        # injected OOMs must reach it (the recovery is
                        # the flush-to-streaming fallback below)
                        _faults.check(
                            "oom", self.node_name,
                            cap=max(b.capacity for b in batches))
                    out = self._run_fused_plan(batches, tuple(chain))
                yield self.record_batch(out)
                return
            except Exception as e:  # noqa: BLE001 - filtered below
                from ..memory.retry import OOM_RETRY_ENABLED

                if not is_device_oom(e) \
                        or not self.conf.get(OOM_RETRY_ENABLED):
                    # oomRetry.enabled off = the raw pre-recovery
                    # behavior everywhere, fallback included
                    raise
                # the whole-plan fused program (every batch stacked into
                # one trace) exhausted device memory: degrade to the
                # streaming per-batch path, whose updates run under the
                # retry/split harness individually
                from ..memory.retry import _emit_retry

                _emit_retry(self.node_name, "fused_plan_fallback", 1, 0)
                flush_buffered()
        if not partials:
            if self.group_exprs:
                return  # grouped aggregate over empty input -> no rows
            # grand aggregate over empty input still yields one row
            # (count=0, sum=null): reduce a zero-row batch
            child_schema = self.children[0].output_schema
            zb = ColumnarBatch.from_pydict(
                {f.name: [] for f in child_schema.fields}, child_schema
            )
            with self._agg_timed("update"):
                partials = [self._run_batch(zb, ops, exprs)]
        from ..memory.retry import with_oom_retry_nosplit

        def merge_and_eval():
            merged = self._merge(partials, span)
            if self.mode == A.PARTIAL:
                return merged
            with self.section("merge.eval"):
                return self._evaluate(merged)

        with self._agg_timed("merge") as span:
            span.set(partials=len(partials))
            # the merge consumes compacted partials (group-cardinality
            # sized, not input sized) — not meaningfully splittable, so
            # it gets the retry-only harness: spill + backoff, then the
            # typed TpuRetryOOM verdict
            out = with_oom_retry_nosplit(
                self.node_name + ".merge", merge_and_eval, self.conf)
        # the merged/evaluated output leaves this generator as its only
        # live reference (the partials list is never read again after
        # the yield), so downstream certified sites may donate it
        from .base import _donation

        yield self.record_batch(_donation().mark_exclusive(out))
