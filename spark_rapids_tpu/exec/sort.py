"""Sort exec.

Reference analog: GpuSortExec (GpuSortExec.scala:51) — local per-partition
sort, or global sort (the reference range-partitions first; until the
exchange layer lands, global sorts gather to one partition, which is also
what a single-partition collect needs anyway). The kernel is ops/sort.py's
radix-key bitonic sort; batches within a partition concatenate first
(RequireSingleBatch coalesce goal in the reference).
"""
from __future__ import annotations

from typing import Dict, Iterator, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import types as T
from ..columnar import ColumnarBatch
from ..conf import RapidsConf
from ..expr import expressions as E
from ..expr.eval import StrV, lower
from ..ops import filter_gather
from ..ops.sort import SortOrder, max_string_len, sort_permutation
from ..types import StructType
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


#: the sort's programs, process-wide: a sort exec is built anew for every
#: query's plan, and a cache of its own would re-trace (and count a compile
#: miss for) the same program in every query — and, registered with the
#: pipeline caches' sweep, would keep that query's whole plan alive
_SORT_CACHE: Dict[tuple, object] = {}


class TpuSortExec(TpuExec):
    def __init__(
        self,
        conf: RapidsConf,
        sort_exprs: Sequence[E.Expression],
        orders: Sequence[Tuple[bool, object]],  # (ascending, nulls_first|None)
        child: TpuExec,
        global_sort: bool = True,
    ):
        super().__init__(conf, [child])
        self.sort_exprs = list(sort_exprs)
        self.orders = [SortOrder(a, nf) for a, nf in orders]
        self.global_sort = global_sort
        self._bound = [
            E.bind_references(e, child.output_schema) for e in self.sort_exprs
        ]

    @property
    def output_schema(self) -> StructType:
        return self.children[0].output_schema

    @property
    def num_partitions(self) -> int:
        return 1 if self.global_sort else self.children[0].num_partitions

    def describe(self):
        ks = ", ".join(
            f"{e}{'' if o.ascending else ' DESC'}"
            for e, o in zip(self.sort_exprs, self.orders)
        )
        return f"TpuSortExec [{ks}]" + ("" if self.global_sort else " (local)")

    def _gather_input(self, index: int):
        if self.global_sort:
            return _concat_all(self.conf, self.children[0])
        batches = [
            b for b in self.children[0].execute_partition(index)
            if b.num_rows > 0
        ]
        if not batches:
            return None
        if len(batches) == 1:
            return batches[0]
        from .basic import TpuCoalesceBatchesExec

        co = TpuCoalesceBatchesExec(self.conf, self.children[0], target_rows=1 << 62)
        return co._flush(batches)

    def _str_lens(self, batch) -> Tuple[int, ...]:
        lens = []
        for b in self._bound:
            if isinstance(b.dtype, (T.StringType, T.BinaryType)):
                if isinstance(b, E.BoundReference):
                    c = batch.columns[b.ordinal]
                    m = int(max_string_len(StrV(c.offsets, c.chars, c.validity)))
                else:
                    m = 64
                lens.append(max(4, choose_capacity(max(1, m), 4)))
        return tuple(lens)

    def _sort_batch(self, batch: ColumnarBatch) -> ColumnarBatch:
        """One sort dispatch over one batch (compiled per capacity/
        signature — a split-and-retry half compiles its own half-capacity
        program)."""
        cap = batch.capacity
        sml = self._str_lens(batch)
        # the program closes over what its key states and not over this
        # exec, which belongs to one query's plan
        bound, orders = tuple(self._bound), tuple(self.orders)

        def build():
            @program("sort")
            def run(cols, num_rows):
                live = filter_gather.live_of(num_rows, cap)
                keys = [lower(b, cols, cap) for b in bound]
                perm = sort_permutation(
                    keys, [b.dtype for b in bound], orders, live, sml)
                live_sorted = jnp.take(live, perm, mode="clip")
                return filter_gather.gather(cols, perm, live_sorted)

            return jax.jit(run)

        key = (bound, orders, batch_signature(batch), cap, sml)
        # the shared pipeline-cache guard: miss accounting + the
        # compiled-program cost plane ride cached_pipeline (xla_cost.py)
        from .base import cached_pipeline

        fn = cached_pipeline(_SORT_CACHE, key, "sort", build)
        vals = fn(
            vals_of_batch(batch), count_scalar(batch.num_rows_lazy))
        return batch_from_vals(
            vals, self.output_schema, batch.num_rows_lazy)

    def execute_partition(self, index: int) -> Iterator[ColumnarBatch]:
        batch = self._gather_input(index)
        if batch is None:
            return
        from ..memory.retry import concat_batches, with_oom_retry
        from .base import materialized_batch

        batch = materialized_batch(batch)  # chunk keys want plain bytes

        def combine(pieces):
            # split-and-retry re-join: the halves are each sorted but the
            # stitch is not globally ordered — re-sort the concatenation
            # (stable, so equal keys keep their piece order). The final
            # program runs at the stitched capacity; if THAT still OOMs
            # the harness escalates to the typed verdict.
            return self._sort_batch(concat_batches(self.conf, pieces))

        with self.op_timed("sort"):
            out = with_oom_retry(self.node_name, self._sort_batch, batch,
                                 self.conf, combine=combine)
        yield self.record_batch(out)
