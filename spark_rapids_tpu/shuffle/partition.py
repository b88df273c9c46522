"""Partitioning strategies + the device partition kernel.

Reference analog: GpuHashPartitioning.scala:29-121 (murmur3 + pmod +
``table.partition``), GpuRangePartitioning.scala / GpuRangePartitioner.scala
(sampled bounds), GpuRoundRobinPartitioning.scala, GpuSinglePartitioning.scala,
and GpuPartitioning.scala:45-110 (contiguousSplit slicing).

TPU re-design: instead of cudf's hash-table partition kernel, partitioning is
one stable ``lax.sort`` by (padding, partition_id) that co-sorts row ids; the
per-partition offsets fall out of a ``searchsorted`` over the sorted ids. The
whole thing is a single fused XLA program per (schema, capacity, P) — the
host syncs only the tiny (P+1,) offsets vector at the batch boundary, which
is where the reference syncs for contiguousSplit sizes too.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

from .. import types as T
from ..expr import expressions as E
from ..expr.eval import ColV, StrV, Val
from ..ops import hashing
from ..ops.filter_gather import gather, live_of
from ..ops.sort import SortOrder, fixed_radix_keys, string_chunk_keys


class Partitioning:
    """Base partitioning contract (reference: GpuPartitioning.scala)."""

    #: key-based partitionings define num_partitions and key_indices
    #: (column ordinals); callers read key_indices via getattr
    num_partitions: int
    #: the word a trace's ``.map`` span says which partitioning it ran by
    kind = "other"

    def partition_ids(self, cols: Sequence[Val], schema: T.StructType,
                      live: jax.Array, map_index: int,
                      str_max_lens: Sequence[int] = ()) -> jax.Array:
        """(cap,) int32 partition id per row (value ignored for dead rows).

        ``str_max_lens``: static per-batch byte-length bucket for each
        string key (in order of appearance) — the exchange syncs the real
        max per batch so long strings hash/compare over their full bytes.
        """
        raise NotImplementedError

    def describe(self) -> str:
        return type(self).__name__


def count_bounds_le(
    row_words: Sequence[jax.Array],
    bound_words: Sequence[jax.Array],
    n_bounds: int,
) -> jax.Array:
    """Per row: how many bounds compare <= it, lexicographically over
    parallel radix-word arrays — i.e. its range-partition id. Shared by the
    host exchange's RangePartitioning and the SPMD dist_sort."""
    cap = row_words[0].shape[0]
    pid = jnp.zeros(cap, jnp.int32)
    for b in range(n_bounds):
        le = jnp.ones(cap, jnp.bool_)
        lt = jnp.zeros(cap, jnp.bool_)
        for rw, bw in zip(row_words, bound_words):
            bv = bw[b]
            lt = lt | (le & (bv < rw))
            le = le & (bv == rw)
        pid = pid + (lt | le).astype(jnp.int32)
    return pid


@dataclasses.dataclass
class SinglePartitioning(Partitioning):
    """Everything to partition 0 (reference: GpuSinglePartitioning.scala)."""

    kind = "single"

    num_partitions: int = 1

    def partition_ids(self, cols, schema, live, map_index, str_max_lens=()):
        cap = live.shape[0]
        return jnp.zeros(cap, jnp.int32)

    def describe(self):
        return "SinglePartitioning"


@dataclasses.dataclass
class RoundRobinPartitioning(Partitioning):
    """Row-cyclic distribution (reference: GpuRoundRobinPartitioning.scala).

    Spark starts each task's cycle at a random position; here the start is
    the map partition index so results are deterministic and still spread.
    """

    kind = "round_robin"

    num_partitions: int

    def partition_ids(self, cols, schema, live, map_index, str_max_lens=()):
        cap = live.shape[0]
        idx = jnp.arange(cap, dtype=jnp.int32)
        return (idx + jnp.int32(map_index)) % jnp.int32(self.num_partitions)

    def describe(self):
        return f"RoundRobinPartitioning({self.num_partitions})"


@dataclasses.dataclass
class HashPartitioning(Partitioning):
    """Spark-bit-exact murmur3 pmod partitioning.

    ``key_indices`` index into the batch columns (expressions are bound by
    the planner before the exchange exec is built). String keys hash over
    their full bytes: the exchange passes the per-batch max byte length
    via ``str_max_lens``.
    """

    kind = "hash"

    key_indices: List[int]
    num_partitions: int

    def partition_ids(self, cols, schema, live, map_index, str_max_lens=()):
        key_cols = [cols[i] for i in self.key_indices]
        key_dts = [schema.fields[i].dataType for i in self.key_indices]
        h = hashing.murmur3(key_cols, key_dts, str_max_lens=str_max_lens)
        return hashing.partition_ids(h, self.num_partitions)

    def describe(self):
        return f"HashPartitioning(keys={self.key_indices}, n={self.num_partitions})"


@dataclasses.dataclass
class RangePartitioning(Partitioning):
    """Ordered partitioning against sampled bounds.

    Reference analog: GpuRangePartitioning.scala + GpuRangePartitioner's
    sampled bounds (SamplingUtils.scala). Bounds are sampled host-side by
    the exchange (the reference samples on the driver too) and handed in as
    per-key host value lists; rows compare lexicographically against each
    bound with full Spark ordering (nulls/NaN/-0.0) via the same radix-key
    encoding the sort kernel uses.
    """

    kind = "range"

    key_indices: List[int]
    orders: List[SortOrder]
    num_partitions: int
    #: per key: list of num_partitions-1 bound values (host, possibly None)
    bounds: Optional[List[List[object]]] = None

    def partition_ids(self, cols, schema, live, map_index, str_max_lens=()):
        assert self.bounds is not None, "bounds must be sampled before use"
        cap = live.shape[0]
        nb = self.num_partitions - 1
        if nb <= 0:
            return jnp.zeros(cap, jnp.int32)
        key_cols = [cols[i] for i in self.key_indices]
        key_dts = [schema.fields[i].dataType for i in self.key_indices]

        row_keys: List[jax.Array] = []   # per radix word: (cap,)
        bound_keys: List[jax.Array] = []  # per radix word: (nb,)
        si = 0
        for k, (colv, dt, order) in enumerate(
            zip(key_cols, key_dts, self.orders)
        ):
            bvals = self.bounds[k]
            if isinstance(colv, StrV):
                ml = (
                    str_max_lens[si]
                    if si < len(str_max_lens) else 64
                )
                si += 1
                row_keys.extend(string_chunk_keys(colv, order, ml))
                bound_keys.extend(
                    _string_bound_keys(bvals, order, ml))
            else:
                row_keys.extend(fixed_radix_keys(colv, dt, order))
                bound_keys.extend(_fixed_bound_keys(bvals, dt, order))

        # row r belongs to partition j iff bounds[j-1] <= r < bounds[j]
        return count_bounds_le(row_keys, bound_keys, nb)

    def describe(self):
        return f"RangePartitioning(keys={self.key_indices}, n={self.num_partitions})"


def _fixed_bound_keys(
    bvals: Sequence[object], dt: T.DataType, order: SortOrder
) -> List[jax.Array]:
    """Radix-encode host bound values with the same scheme as the rows."""
    import numpy as np

    nb = len(bvals)
    data = np.zeros(nb, dt.to_numpy())
    valid = np.zeros(nb, bool)
    for i, v in enumerate(bvals):
        if v is not None:
            data[i] = v
            valid[i] = True
    col = ColV(jnp.asarray(data), jnp.asarray(valid))
    return fixed_radix_keys(col, dt, order)


def _string_bound_keys(
    bvals: Sequence[object], order: SortOrder, max_len: int
) -> List[jax.Array]:
    import numpy as np

    nb = len(bvals)
    bufs = [
        (v.encode("utf-8") if isinstance(v, str) else (v or b""))
        for v in bvals
    ]
    offsets = np.zeros(nb + 1, np.int32)
    for i, b in enumerate(bufs):
        offsets[i + 1] = offsets[i] + len(b)
    chars = np.frombuffer(b"".join(bufs) or b"\0", np.uint8)
    valid = np.array([v is not None for v in bvals], bool)
    col = StrV(jnp.asarray(offsets), jnp.asarray(chars), jnp.asarray(valid))
    return string_chunk_keys(col, order, max_len)


# ---------------------------------------------------------------------------
# Device partition kernel (cudf table.partition analog)
# ---------------------------------------------------------------------------
_PARTITION_CACHE: Dict[tuple, Callable] = {}


def partition_cols(
    cols: Sequence[Val],
    pids: jax.Array,
    num_rows: Union[int, jax.Array],
    num_partitions: int,
) -> Tuple[List[Val], jax.Array]:
    """Stable-sort rows by partition id; return (sorted cols, offsets).

    ``offsets`` is (P+1,) int32: partition j occupies sorted rows
    [offsets[j], offsets[j+1]); offsets[P] is the live row count. Padding
    rows sort last and are excluded. Pure/trace-safe.
    """
    cap = pids.shape[0]
    live = live_of(num_rows, cap)
    pad_rank = (~live).astype(jnp.uint32)
    row_id = jnp.arange(cap, dtype=jnp.int32)
    sorted_ops = lax.sort(
        [pad_rank, pids.astype(jnp.uint32), row_id],
        num_keys=2,
        is_stable=True,
    )
    perm = sorted_ops[2]
    live_sorted = sorted_ops[0] == 0
    sorted_pids = jnp.where(
        live_sorted, sorted_ops[1].astype(jnp.int32), jnp.int32(num_partitions)
    )
    out_cols = gather(cols, perm, live_sorted)
    offsets = jnp.searchsorted(
        sorted_pids,
        jnp.arange(num_partitions + 1, dtype=jnp.int32),
        side="left",
    ).astype(jnp.int32)
    return out_cols, offsets
