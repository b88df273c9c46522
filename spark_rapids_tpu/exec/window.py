"""Window exec.

Reference analog: GpuWindowExec (GpuWindowExec.scala:92) — one exec per
(partition by, order by) spec computing every window expression over it.
TPU re-design: ONE radix sort by (partition keys, order keys) and pure
O(n) scan kernels (ops/window.py) — no per-partition looping, no rolling
windows kernel library.

Until the exchange layer lands, the exec gathers its input to a single
partition (window semantics need all rows of a partition key together).
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import types as T
from ..columnar import ColumnarBatch
from ..conf import RapidsConf
from ..expr import aggregates as A
from ..expr import expressions as E
from ..expr import windows as W
from ..expr.eval import ColV, StrV, lower
from ..ops import filter_gather
from ..ops import window as window_ops
from ..ops.sort import (
    SortOrder,
    fixed_radix_keys,
    max_string_len,
    sort_with_radix_keys,
    string_chunk_keys,
)
from ..types import StructField, StructType
from ..columnar.column import choose_capacity
from .base import (
    TOTAL_TIME,
    TpuExec,
    batch_from_vals,
    batch_signature,
    count_scalar,
    program,
    timed,
    vals_of_batch,
)
from .join import _concat_all


class TpuWindowExec(TpuExec):
    def __init__(
        self,
        conf: RapidsConf,
        window_exprs: Sequence[W.WindowExpression],
        child: TpuExec,
    ):
        super().__init__(conf, [child])
        if not window_exprs:
            raise ValueError("window exec needs at least one window expression")
        self.window_exprs = list(window_exprs)
        spec = window_exprs[0].spec
        for we in window_exprs[1:]:
            if (we.spec.partition_by, we.spec.order_by, we.spec.orders) != (
                spec.partition_by, spec.order_by, spec.orders
            ):
                raise ValueError(
                    "one TpuWindowExec handles one (partition, order) spec")
        self.spec = spec
        cs = child.output_schema
        self._part_keys = [E.bind_references(k, cs) for k in spec.partition_by]
        self._order_keys = [E.bind_references(k, cs) for k in spec.order_by]
        self._orders = [SortOrder(a, nf) for a, nf in spec.orders] or [
            SortOrder(True, None) for _ in self._order_keys
        ]
        self._bound_funcs: List[E.Expression] = []
        fields = list(cs.fields)
        for we in self.window_exprs:
            f = we.func
            if isinstance(f, (W.Lead, W.Lag)) or isinstance(f, A.AggregateFunction):
                if getattr(f, "child", None) is not None:
                    f = dataclasses.replace(f, child=E.bind_references(f.child, cs))
            self._bound_funcs.append(f)
            fields.append(StructField(we.resolved_name(), f.dtype, True))
        self._schema = StructType(tuple(fields))
        self._jits = {}

    @property
    def output_schema(self):
        return self._schema

    @property
    def num_partitions(self):
        return 1

    def describe(self):
        names = ", ".join(we.resolved_name() for we in self.window_exprs)
        return f"TpuWindowExec [{names}]"

    def _str_lens(self, batch, keys) -> Tuple[int, ...]:
        lens = []
        for b in keys:
            if isinstance(b.dtype, (T.StringType, T.BinaryType)):
                if isinstance(b, E.BoundReference):
                    c = batch.columns[b.ordinal]
                    m = int(max_string_len(StrV(c.offsets, c.chars, c.validity)))
                else:
                    m = 64
                lens.append(max(4, choose_capacity(max(1, m), 4)))
        return tuple(lens)

    def execute_partition(self, index: int) -> Iterator[ColumnarBatch]:
        assert index == 0
        batch = _concat_all(self.conf, self.children[0])
        if batch is None:
            return
        cap = batch.capacity
        all_keys = self._part_keys + self._order_keys
        sml = self._str_lens(batch, all_keys)
        run = program("window")(self.window_fn(cap, sml))
        key = (batch_signature(batch), cap, sml)
        # the shared pipeline-cache guard: miss accounting + the
        # compiled-program cost plane ride cached_pipeline (xla_cost.py)
        from .base import cached_pipeline

        fn = cached_pipeline(self._jits, key, "window",
                             lambda: jax.jit(run), per_instance=True)
        with self.op_timed():
            vals = fn(
                vals_of_batch(batch), count_scalar(batch.num_rows_lazy))
        yield self.record_batch(
            batch_from_vals(vals, self._schema, batch.num_rows_lazy))

    def window_fn(self, cap: int, sml: Tuple[int, ...]):
        """The pure, trace-safe window body over (cols, num_rows) at
        capacity ``cap``: ONE radix sort by (partition, order) keys plus
        O(n) scan kernels, returning sorted child cols + one value column
        per window expression. Shared seam: the single-device path jits
        it directly; the mesh window stage (exec/mesh.TpuMeshWindowExec)
        runs the SAME body per shard after a hash exchange on the
        partition keys (window partitions are independent, so exchanging
        whole partitions onto shards preserves exact semantics)."""
        all_keys = self._part_keys + self._order_keys
        frame = self.spec.resolved_frame()
        range_frame = frame.frame_type == W.RANGE
        whole = frame.is_whole_partition or not self._order_keys
        bounded = frame.is_bounded_rows and not whole and not frame.is_running
        blo, bhi = frame.row_bounds() if bounded else (0, 0)
        # literal RANGE frame over the single numeric ORDER BY key value
        branged = (frame.is_bounded_range and not whole
                   and bool(self._order_keys))
        # DESC normalizes by NEGATING the key (exec below); "preceding"
        # flips direction with the key, so the offsets carry over as-is:
        # kj in [ki-hi, ki+(-lo)] <=> -kj in [-ki+lo, -ki+hi]
        rlo, rhi = frame.range_bounds() if branged else (None, None)

        def run(cols, num_rows):
            live = filter_gather.live_of(num_rows, cap)
            keys = [lower(k, cols, cap) for k in all_keys]
            dtypes = [k.dtype for k in all_keys]
            orders = [SortOrder(True, True)] * len(self._part_keys) + list(
                self._orders
            )
            perm, radix = sort_with_radix_keys(keys, dtypes, orders, live, sml)
            live_s = jnp.take(live, perm, mode="clip")
            sorted_cols = filter_gather.gather(cols, perm, live_s)

            # split the co-sorted radix arrays back into partition vs order
            counts = []
            si = 0
            for k, dt in zip(all_keys, dtypes):
                if isinstance(dt, (T.StringType, T.BinaryType)):
                    ml = sml[si] if si < len(sml) else 64
                    si += 1
                    counts.append(1 + max(1, (ml + 3) // 4))
                else:
                    counts.append(2)
            npart = sum(counts[: len(self._part_keys)])
            part_radix = tuple(radix[:npart])
            order_radix = tuple(radix[npart: sum(counts)])

            ps, pe, qs, qe, seg = window_ops.boundaries_from_radix(
                part_radix, order_radix, live_s)

            range_key = None
            if branged:
                rk = lower(self._order_keys[0], sorted_cols, cap)
                if not self._orders[0].ascending:
                    rk = ColV(-rk.data, rk.validity)  # ASC-normalize
                range_key = rk
                nf = self._orders[0].nulls_first
                range_nulls_first = (
                    self._orders[0].ascending if nf is None else nf)

            def ranged(op_, v_):
                return window_ops.bounded_range_agg(
                    op_, v_, range_key, ps, pe, qs, qe, live_s, rlo, rhi,
                    range_nulls_first)

            out = list(sorted_cols)
            for we, f in zip(self.window_exprs, self._bound_funcs):
                if isinstance(f, W.RowNumber):
                    out.append(window_ops.row_number(ps, live_s))
                elif isinstance(f, W.Rank):
                    out.append(window_ops.rank(ps, qs, live_s))
                elif isinstance(f, W.DenseRank):
                    out.append(window_ops.dense_rank(ps, qs, live_s))
                elif isinstance(f, (W.Lead, W.Lag)):
                    v = lower(f.child, sorted_cols, cap)
                    off = f.offset if isinstance(f, W.Lead) else -f.offset
                    dflt = (
                        lower(f.default, sorted_cols, cap)
                        if f.default is not None else None
                    )
                    out.append(window_ops.shift_in_partition(
                        v, off, ps, pe, live_s, dflt))
                elif isinstance(f, A.Average):
                    v = lower(E.Cast(f.child, T.DOUBLE), sorted_cols, cap)
                    if branged:
                        s = ranged("sum", v)
                        c = ranged("count", v)
                    elif bounded:
                        s = window_ops.bounded_row_agg(
                            "sum", v, ps, pe, live_s, blo, bhi)
                        c = window_ops.bounded_row_agg(
                            "count", v, ps, pe, live_s, blo, bhi)
                    else:
                        s = window_ops.running_agg(
                            "sum", v, seg, ps, qe, live_s, range_frame,
                            whole, pe)
                        c = window_ops.running_agg(
                            "count", v, seg, ps, qe, live_s, range_frame,
                            whole, pe)
                    data = s.data / jnp.where(c.data == 0, 1, c.data)
                    valid = s.validity & (c.data > 0)
                    out.append(ColV(jnp.where(valid, data, 0.0), valid))
                elif isinstance(f, A.AggregateFunction):
                    op = {
                        A.Count: "count", A.Sum: "sum",
                        A.Min: "min", A.Max: "max",
                    }[type(f)]
                    if isinstance(f, A.Count) and f.input is None:
                        op = "count_star"
                        v = None
                    else:
                        cast_to = f.dtype if isinstance(f, A.Sum) else None
                        e = E.Cast(f.child, cast_to) if cast_to else f.child
                        v = lower(e, sorted_cols, cap)
                    if branged:
                        out.append(ranged(op, v))
                    elif bounded:
                        out.append(window_ops.bounded_row_agg(
                            op, v, ps, pe, live_s, blo, bhi))
                    else:
                        out.append(window_ops.running_agg(
                            op, v, seg, ps, qe, live_s, range_frame,
                            whole, pe))
                else:
                    raise ValueError(f"unsupported window function {f}")
            return out

        return run
