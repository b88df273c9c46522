"""Columnar exchange execs: shuffle and broadcast.

Reference analog: GpuShuffleExchangeExecBase.doExecuteColumnar
(execution/GpuShuffleExchangeExec.scala:70,147) and
GpuBroadcastExchangeExecBase (execution/GpuBroadcastExchangeExec.scala:237).
The map side partitions each child batch with ONE fused device program
(partition-id compute + stable sort + offsets; shuffle/partition.py), syncs
only the (P+1,) offsets vector, slices device pieces, and writes them
through the transport SPI. The reduce side fetches its pieces and concats
them into one dense batch per partition (the GpuShuffleCoalesceExec role).
"""
from __future__ import annotations

import threading
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import types as T
from ..columnar import ColumnarBatch
from ..conf import (
    RapidsConf,
    SHUFFLE_COMPRESSION_CODEC,
    SHUFFLE_TRANSPORT_CLASS,
)
from ..expr.eval import StrV, Val
from ..ops import concat as concat_ops
from ..ops import filter_gather
from ..ops.sort import max_string_len
from ..shuffle.partition import Partitioning, RangePartitioning, partition_cols
from ..shuffle.transport import (
    DeviceShuffleTransport,
    SerializedShuffleTransport,
    ShufflePiece,
    ShuffleTransport,
    new_shuffle_id,
)
from ..types import StructType
from ..utils.locks import ordered_lock
from ..columnar.column import choose_capacity
from .base import (
    EXCHANGE_SCOPE_WORDS,
    TOTAL_TIME,
    TpuExec,
    batch_from_vals,
    batch_signature,
    count_scalar,
    program,
    timed,
    vals_of_batch,
)

PARTITION_SIZE = "partitionSize"  # reference metric (GpuExec.scala:27-60)
DATA_SIZE = "dataSize"
# per-shuffle transport metrics (the layer the per-op profiler skipped):
# wire bytes each way plus codec encode/decode time, pulled from the
# transport's cumulative stats() after map/fetch (reference analog: the
# RapidsShuffle* writeTime/fetchWaitTime/compression metrics)
SHUFFLE_BYTES_WRITTEN = "shuffleBytesWritten"
SHUFFLE_BYTES_FETCHED = "shuffleBytesFetched"
CODEC_ENCODE_TIME = "codecEncodeTime"
CODEC_DECODE_TIME = "codecDecodeTime"


def make_transport(conf: RapidsConf) -> ShuffleTransport:
    kind = conf.get(SHUFFLE_TRANSPORT_CLASS)
    if kind == "host":
        return SerializedShuffleTransport(conf.get(SHUFFLE_COMPRESSION_CODEC))
    if kind == "network":
        # conf-selected server/client transport (reference: transport
        # selection by conf, RapidsShuffleTransport.scala:328-411); the
        # process-wide server owns this worker's map output and fetches
        # merge every peer's pieces
        from ..conf import SHUFFLE_NETWORK_LISTEN_PORT, SHUFFLE_NETWORK_PEERS
        from ..shuffle.network import NetworkShuffleTransport, local_server

        remotes = []
        for p in conf.get(SHUFFLE_NETWORK_PEERS).split(","):
            p = p.strip()
            if p:
                host, sep, port = p.rpartition(":")
                if not sep or not host or not port:
                    raise ValueError(
                        "spark.rapids.tpu.shuffle.network.peers: invalid "
                        f"peer entry {p!r} (expected host:port)")
                try:
                    port_n = int(port)
                except ValueError:
                    raise ValueError(
                        "spark.rapids.tpu.shuffle.network.peers: invalid "
                        f"port in peer entry {p!r} (expected host:port)")
                remotes.append((host, port_n))
        return NetworkShuffleTransport(
            server=local_server(conf.get(SHUFFLE_NETWORK_LISTEN_PORT)),
            remotes=tuple(remotes),
            codec=conf.get(SHUFFLE_COMPRESSION_CODEC),
            owns_server=False)
    return DeviceShuffleTransport()


_SLICE_CACHE: Dict[tuple, object] = {}
#: the map side's programs, process-wide: an exchange is built anew for
#: every query's plan, and a cache of its own would re-trace (and count a
#: compile miss for) the same partition program in every query
_MAP_CACHE: Dict[tuple, object] = {}


def _piece_slicer(sig: tuple, pcap: int, ccaps: Tuple[int, ...]):
    """Jitted row-range slice at bucketed output shapes.

    Start/count are TRACED operands, so one compiled program serves every
    piece that lands in the same (capacity, char-cap) bucket — a naive
    ``data[a:b]`` would compile one XLA slice per distinct range.
    """
    key = (sig, pcap, ccaps)

    def build():
        @program("exchange_slice")
        def run(cols, start, n):
            with jax.named_scope(EXCHANGE_SCOPE_WORDS[0]):
                idx = jnp.arange(pcap, dtype=jnp.int32) + start
                valid_slot = jnp.arange(pcap, dtype=jnp.int32) < n
                return filter_gather.gather(cols, idx, valid_slot, ccaps)

        return jax.jit(run)

    from .base import cached_pipeline

    return cached_pipeline(_SLICE_CACHE, key, None, build,
                           max_entries=1024)


_PREFIX_CACHE: Dict[tuple, object] = {}


def _live_prefix(batch: ColumnarBatch, live_cap: int) -> ColumnarBatch:
    """What the map side partitions where a batch holds fewer rows than it
    was given slots: the batch cut to the first ``live_cap`` slots of
    every plane (``ops/concat.live_prefix``) in a program of its own."""
    key = (batch_signature(batch), live_cap)

    def build():
        @program("exchange_slice")
        def run(cols):
            with jax.named_scope(EXCHANGE_SCOPE_WORDS[0]):
                return concat_ops.live_prefix(cols, live_cap)

        return jax.jit(run)

    from .base import cached_pipeline

    fn = cached_pipeline(_PREFIX_CACHE, key, None, build, max_entries=1024)
    return batch_from_vals(
        fn(vals_of_batch(batch)), batch.schema, batch.num_rows)


def _vals_signature(vals: Sequence[Val]) -> tuple:
    sig = []
    for v in vals:
        if isinstance(v, StrV):
            sig.append(("s", int(v.offsets.shape[0]), int(v.chars.shape[0])))
        else:
            sig.append((str(v.data.dtype), int(v.data.shape[0])))
    return tuple(sig)


def _slice_piece(
    vals: Sequence[Val], a: int, b: int,
    str_bounds: Sequence[Tuple[int, int]],
) -> ShufflePiece:
    """Device-slice rows [a, b) of partition-sorted columns into a piece
    at power-of-two capacity (strings re-based to offset 0 by the gather).

    ``str_bounds[i]`` = (byte_start, byte_end) for the i-th string column
    (host ints synced at the map boundary)."""
    n = b - a
    byte_lens = tuple(bb - ba for ba, bb in str_bounds)
    pcap = choose_capacity(max(1, n))
    ccaps = tuple(choose_capacity(max(1, bl), 128) for bl in byte_lens)
    fn = _piece_slicer(_vals_signature(vals), pcap, ccaps)
    out = fn(vals, jnp.int32(a), jnp.int32(n))
    return ShufflePiece(out, n, byte_lens)


def _piece_bytes(piece: ShufflePiece) -> int:
    """Device bytes of a piece's planes, from their shapes."""
    return sum(int(a.size) * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(piece.vals))


_CONCAT_CACHE: Dict[tuple, object] = {}


def concat_pieces(
    pieces: Sequence[ShufflePiece], schema: StructType
) -> ColumnarBatch:
    """Concat shuffle pieces into one dense batch with ONE jitted program
    per shape set (row/byte counts are traced operands, so arbitrary piece
    sizes reuse the same executable)."""
    lengths = [p.n for p in pieces]
    n_str = len(pieces[0].byte_lens)
    out_cap = choose_capacity(max(1, sum(lengths)))
    out_char_caps = tuple(
        choose_capacity(max(1, sum(p.byte_lens[k] for p in pieces)), 128)
        for k in range(n_str)
    )
    sigs = tuple(_vals_signature(p.vals) for p in pieces)
    key = (sigs, out_cap, out_char_caps)

    def build():
        @program("exchange_concat")
        def run(col_parts, counts, byte_counts):
            with jax.named_scope(EXCHANGE_SCOPE_WORDS[0]):
                return concat_ops.concat_pieces_traced(
                    col_parts, counts, byte_counts, out_cap, out_char_caps)

        return jax.jit(run)

    from .base import cached_pipeline

    fn = cached_pipeline(_CONCAT_CACHE, key, None, build,
                         max_entries=1024)
    cols, _n = fn(
        [p.vals for p in pieces],
        [jnp.int32(p.n) for p in pieces],
        [[jnp.int32(b) for b in p.byte_lens] for p in pieces],
    )
    return batch_from_vals(cols, schema, sum(lengths))


class TpuShuffleExchangeExec(TpuExec):
    """Repartition child output by a Partitioning through the transport."""

    def __init__(self, conf: RapidsConf, child: TpuExec,
                 partitioning: Partitioning,
                 transport: Optional[ShuffleTransport] = None):
        super().__init__(conf, [child])
        self.partitioning = partitioning
        self.transport = transport or make_transport(conf)
        self.shuffle_id = new_shuffle_id()
        self._map_done = False
        self._consumed: set = set()
        self._map_lock = ordered_lock("exec.exchange_map", reentrant=True)
        self.metrics[PARTITION_SIZE] = self.metric(PARTITION_SIZE)
        self.metrics[DATA_SIZE] = self.metric(DATA_SIZE)

    @property
    def output_schema(self) -> StructType:
        return self.children[0].output_schema

    @property
    def num_partitions(self) -> int:
        return self.partitioning.num_partitions

    def describe(self):
        return f"TpuShuffleExchangeExec {self.partitioning.describe()}"

    # -- map side ----------------------------------------------------------
    def _part_cache_key(self) -> tuple:
        p = self.partitioning
        if isinstance(p, RangePartitioning) and p.bounds is not None:
            return (p.describe(), tuple(tuple(b) for b in p.bounds),
                    tuple((o.ascending, o.nulls_first) for o in p.orders))
        return (p.describe(),)

    def _key_str_lens(self, batch: ColumnarBatch) -> Tuple[int, ...]:
        """Per-batch byte-length bucket for each STRING key column, so
        hashing/range-comparison covers full strings (one tiny host sync,
        same place TpuSortExec syncs its string bounds)."""
        lens = []
        for i in getattr(self.partitioning, "key_indices", ()):
            c = batch.columns[i]
            if c.is_string:
                m = int(max_string_len(StrV(c.offsets, c.chars, c.validity)))
                lens.append(max(4, choose_capacity(max(1, m), 4)))
        return tuple(lens)

    def _map_fn(self, sig: tuple, cap: int, schema: StructType,
                sml: Tuple[int, ...]):
        P = self.num_partitions
        key = (sig, cap, P, sml, schema, self._part_cache_key())

        def build():
            part = self.partitioning

            @program("exchange")
            def run(cols, num_rows, map_index):
                with jax.named_scope(EXCHANGE_SCOPE_WORDS[0]):
                    live = filter_gather.live_of(num_rows, cap)
                    pids = part.partition_ids(
                        cols, schema, live, map_index, str_max_lens=sml)
                    sorted_cols, offsets = partition_cols(
                        cols, pids, num_rows, P)
                    byte_offs = [
                        jnp.take(c.offsets, offsets, mode="clip")
                        for c in sorted_cols if isinstance(c, StrV)
                    ]
                    return sorted_cols, offsets, byte_offs

            return jax.jit(run)

        # the shared pipeline-cache guard: miss accounting + the
        # compiled-program cost plane ride cached_pipeline (xla_cost.py)
        # — the shuffle map kernel is often the bandwidth-dominant
        # program and must not be invisible to the roofline report
        from .base import cached_pipeline

        return cached_pipeline(_MAP_CACHE, key, "exchange", build)

    def _sample_range_bounds(self, parts: List[List[ColumnarBatch]]) -> int:
        """Sample key values host-side and set the range bounds
        (reference: GpuRangePartitioner.sketch/determineBounds). Returns
        the number of rows sampled."""
        part = self.partitioning
        assert isinstance(part, RangePartitioning)
        if part.bounds is not None:
            return 0
        from ..cpu.plan import _SparkOrderKey

        from .base import vals_of_batch

        samples: List[tuple] = []
        for batches in parts:
            for b in batches:
                n = b.num_rows
                if n == 0:
                    continue
                take = min(n, 128)
                step = max(1, n // take)
                # gather the strided sample ON DEVICE, read back only it
                # (a full column readback here would be O(rows) transfer
                # for an O(128) sample)
                idx = jnp.asarray(range(0, n, step), jnp.int32)
                key_vals = [vals_of_batch(b)[i] for i in part.key_indices]
                sampled = filter_gather.gather(
                    key_vals, idx, jnp.ones(idx.shape[0], jnp.bool_))
                from .base import batch_from_vals

                sb = batch_from_vals(
                    sampled,
                    T.StructType(tuple(
                        b.schema.fields[i] for i in part.key_indices)),
                    idx.shape[0],
                )
                hosts = sb.host_columns()
                for r in range(idx.shape[0]):
                    samples.append(tuple(
                        (None if not h.validity[r] else
                         (h.data[r].item()
                          if hasattr(h.data[r], "item") else h.data[r]))
                        for h in hosts
                    ))
        P = part.num_partitions
        if not samples:
            part.bounds = [[None] * (P - 1) for _ in part.key_indices]
            return 0
        orders = part.orders
        samples.sort(key=lambda row: tuple(
            _SparkOrderKey(v, o.ascending, o.nulls_first_resolved)
            for v, o in zip(row, orders)
        ))
        bounds_rows = []
        for j in range(1, P):
            bounds_rows.append(samples[min(len(samples) - 1,
                                           j * len(samples) // P)])
        part.bounds = [
            [row[k] for row in bounds_rows]
            for k in range(len(part.key_indices))
        ]
        return len(samples)

    def _run_map_side(self) -> None:
        with self._map_lock:
            if self._map_done:
                return
            child = self.children[0]
            schema = self.output_schema
            str_col_ix = [
                j for j, f in enumerate(schema.fields)
                if isinstance(f.dataType, (T.StringType, T.BinaryType))
            ]
            needs_sample = (
                isinstance(self.partitioning, RangePartitioning)
                and self.partitioning.bounds is None
            )
            if needs_sample:
                parts = [
                    list(child.execute_partition(p))
                    for p in range(child.num_partitions)
                ]
                # the child has run; what follows is the sampling's own:
                # a gather and a pull an input, and the host's sort
                with self.op_timed("sample") as span:
                    samples = self._sample_range_bounds(parts)
                    span.set(samples=samples,
                             inputs=sum(len(bs) for bs in parts),
                             bounds=self.num_partitions - 1)
                batch_iter = [
                    (p, b) for p, bs in enumerate(parts) for b in bs
                ]
            else:
                batch_iter = (
                    (p, b)
                    for p in range(child.num_partitions)
                    for b in child.execute_partition(p)
                )
            from ..memory.retry import named_oom

            P = self.num_partitions
            self.partition_rows = [0] * P
            wrote0 = self.transport.bytes_written()
            inputs = slots = cut = 0
            with self.op_timed("map", partitions=P,
                               kind=self.partitioning.kind) as span, \
                    named_oom(f"{self.node_name}.map"):
                # exchange map-side staging (partition sort + piece
                # slicing) sits outside the per-batch retry harness: a
                # device allocation failure here is a named
                # TpuOutOfDeviceMemory, not a bare XLA traceback
                for map_id, batch in batch_iter:
                    if not batch.columns:
                        continue
                    inputs += 1
                    # the map program sorts and gathers every slot it is
                    # given, so it is given the bucket of the rows the
                    # batch HOLDS: an aggregate's or a filter's output
                    # keeps its input's capacity for a handful of rows.
                    # Where the count is a device scalar this is the
                    # wait for the child's program that the pull of the
                    # offsets made, one dispatch earlier
                    n = batch.num_rows
                    if n == 0:
                        continue
                    live_cap = choose_capacity(n, self.conf.shape_bucket_min)
                    if live_cap < batch.capacity:
                        batch = _live_prefix(batch, live_cap)
                        cut += 1
                    slots += batch.capacity
                    # dict-encoded columns materialize at the shuffle
                    # boundary: pieces serialize/slice the plain Arrow
                    # layout and peers don't share dictionaries
                    from .base import materialized_batch

                    batch = materialized_batch(batch)
                    cap = batch.capacity
                    fn = self._map_fn(
                        batch_signature(batch), cap, schema,
                        self._key_str_lens(batch))
                    sorted_cols, offsets, byte_offs = fn(
                        vals_of_batch(batch),
                        count_scalar(batch.num_rows_lazy),
                        jnp.int32(map_id),
                    )
                    # ONE host sync for the (P+1,) offsets (+ string bytes)
                    from .base import host_pull

                    off_h, *boffs_h = host_pull([offsets, *byte_offs])
                    for j in range(P):
                        a, b = int(off_h[j]), int(off_h[j + 1])
                        if a == b:
                            continue
                        str_bounds = [
                            (int(bo[j]), int(bo[j + 1])) for bo in boffs_h
                        ]
                        piece = _slice_piece(sorted_cols, a, b, str_bounds)
                        self.transport.write(
                            self.shuffle_id, map_id, j, piece, schema)
                        self.metrics[PARTITION_SIZE].add(b - a)
                        # per-reduce-partition row stats: the AQE reader
                        # re-plans from these (reference: MapOutputStats
                        # feeding ShuffledBatchRDD's partition specs)
                        self.partition_rows[j] += b - a
                # what the map side handed the transport: the pieces'
                # bytes and rows over all of its input batches; and what
                # its program partitioned: the slots, and the inputs it
                # took under their capacity
                span.set(bytes=self.transport.bytes_written() - wrote0,
                         rows=sum(self.partition_rows), inputs=inputs,
                         slots=slots, cut=cut)
            self.metrics[DATA_SIZE].set(self.transport.bytes_written())
            self._note_transport_stats()
            self._map_done = True

    def _note_transport_stats(self) -> None:
        """Refresh the per-shuffle transport metrics from the transport's
        cumulative counters (set, not add: stats() is already a running
        total, and AQE readers share this exchange's transport)."""
        st = self.transport.stats()
        self.metric(SHUFFLE_BYTES_WRITTEN, "bytes").set(st["bytes_written"])
        self.metric(SHUFFLE_BYTES_FETCHED, "bytes").set(st["bytes_fetched"])
        if st["encode_ns"]:
            self.metric(CODEC_ENCODE_TIME, "ns").set(st["encode_ns"])
        if st["decode_ns"]:
            self.metric(CODEC_DECODE_TIME, "ns").set(st["decode_ns"])

    # -- reduce side -------------------------------------------------------
    def execute_partition(self, index: int) -> Iterator[ColumnarBatch]:
        self._run_map_side()
        pieces = self.transport.fetch(self.shuffle_id, index)
        self._note_transport_stats()
        # the consumed-set transition runs under the map latch: parallel
        # reduce partitions otherwise race the len() check-then-act —
        # two threads can both see the set full and double-release the
        # transport, or a late add lands after clear() and wedges the
        # NEXT execution's release forever
        with self._map_lock:
            self._consumed.add(index)
            if len(self._consumed) >= self.num_partitions:
                # every reduce partition fetched once: drop the cached
                # pieces (the reference ties shuffle buffer lifetime to
                # the stage) and reset the map latch so a re-execution
                # rebuilds them
                self.transport.release(self.shuffle_id)
                self._consumed.clear()
                self._map_done = False
        if pieces:
            yield self.record_batch(self.reduce(pieces))

    def reduce(self, pieces: List[ShufflePiece]) -> ColumnarBatch:
        """One reduce partition's pieces as one dense batch (the adaptive
        reads hand their pieces in too, so the reduce side has one span
        whoever reads)."""
        from ..memory.retry import named_oom

        with self.op_timed("reduce") as span, \
                named_oom(f"{self.node_name}.reduce"):
            if span.on:
                span.set(partitions=len(pieces),
                         rows=sum(p.n for p in pieces),
                         bytes=sum(_piece_bytes(p) for p in pieces))
            return concat_pieces(pieces, self.output_schema)


# ---------------------------------------------------------------------------
# AQE-lite: post-exchange stats -> re-planned reads
# ---------------------------------------------------------------------------
class TpuAQEShuffleReadExec(TpuExec):
    """Adaptive shuffle read: COALESCES small reduce partitions and SPLITS
    skewed ones using the exchange's materialized per-partition row stats.

    Reference analog: GpuCustomShuffleReaderExec.scala + ShuffledBatchRDD's
    CoalescedPartitionSpec / PartialReducerPartitionSpec (:31-157). Specs:
      ("range", lo, hi)     read reduce partitions [lo, hi) concatenated
      ("slice", rid, j, k)  read slice j of k of reduce partition rid
                            (pieces grouped by cumulative rows — the
                            skewed-join split; only valid where the
                            consumer tolerates a partition appearing in
                            several tasks, i.e. the join PROBE side)
    """

    def __init__(self, conf: RapidsConf, exchange: TpuShuffleExchangeExec,
                 specs: List[tuple]):
        super().__init__(conf, [exchange])
        self.specs = specs
        self._consumed: set = set()

    @property
    def output_schema(self) -> StructType:
        return self.children[0].output_schema

    @property
    def num_partitions(self) -> int:
        return len(self.specs)

    def describe(self):
        nr = sum(1 for s in self.specs if s[0] == "range")
        ns = len(self.specs) - nr
        return f"TpuAQEShuffleReadExec({nr} coalesced, {ns} skew slices)"

    def execute_partition(self, index: int) -> Iterator[ColumnarBatch]:
        ex: TpuShuffleExchangeExec = self.children[0]  # type: ignore
        ex._run_map_side()
        spec = self.specs[index]
        pieces: List[ShufflePiece] = []
        if spec[0] == "range":
            _, lo, hi = spec
            for rid in range(lo, hi):
                pieces.extend(ex.transport.fetch(ex.shuffle_id, rid))
        else:
            _, rid, j, k = spec
            allp = ex.transport.fetch(ex.shuffle_id, rid)
            pieces = _slice_pieces_by_rows(allp, j, k)
        ex._note_transport_stats()
        self._consumed.add(index)
        if len(self._consumed) >= len(self.specs):
            ex.transport.release(ex.shuffle_id)
            self._consumed.clear()
            ex._map_done = False
        if pieces:
            yield self.record_batch(ex.reduce(pieces))


def _slice_pieces_by_rows(
    pieces: List[ShufflePiece], j: int, k: int
) -> List[ShufflePiece]:
    """Split a piece list into k row-balanced groups; return group j.
    (The reference splits skewed partitions by MAP ranges —
    PartialReducerPartitionSpec; grouping whole pieces is the same cut.)"""
    total = sum(p.n for p in pieces)
    bounds = [total * i // k for i in range(k + 1)]
    out = []
    acc = 0
    for p in pieces:
        mid = acc + p.n // 2
        if bounds[j] <= mid < bounds[j + 1]:
            out.append(p)
        acc += p.n
    return out


def plan_aqe_coalesce(
    conf: RapidsConf, exchange: TpuShuffleExchangeExec
) -> "TpuAQEShuffleReadExec":
    """Coalesce-only re-plan (safe for FINAL aggregates: merging whole
    key-disjoint partitions keeps them key-disjoint)."""
    from ..conf import AQE_TARGET_ROWS

    exchange._run_map_side()
    rows = exchange.partition_rows
    target = conf.get(AQE_TARGET_ROWS)
    specs: List[tuple] = []
    lo = 0
    acc = 0
    for p, r in enumerate(rows):
        if acc > 0 and acc + r > target:
            specs.append(("range", lo, p))
            lo, acc = p, 0
        acc += r
    if lo < len(rows):
        specs.append(("range", lo, len(rows)))
    return TpuAQEShuffleReadExec(conf, exchange, specs)


def plan_aqe_join_pair(
    conf: RapidsConf,
    left_ex: TpuShuffleExchangeExec,
    right_ex: TpuShuffleExchangeExec,
    probe_left: bool = True,
) -> Tuple["TpuAQEShuffleReadExec", "TpuAQEShuffleReadExec"]:
    """Joint re-plan of a co-partitioned join's two exchanges: specs stay
    index-ALIGNED so partition p of one side still meets partition p of
    the other. Skewed PROBE partitions split into row-balanced slices,
    each paired with the full matching build partition (reference:
    OptimizeSkewedJoin + ShuffledBatchRDD:31-157); small pairs coalesce.
    """
    from ..conf import AQE_SKEW_FACTOR, AQE_TARGET_ROWS

    left_ex._run_map_side()
    right_ex._run_map_side()
    probe_ex = left_ex if probe_left else right_ex
    build_ex = right_ex if probe_left else left_ex
    prows = probe_ex.partition_rows
    target = conf.get(AQE_TARGET_ROWS)
    factor = conf.get(AQE_SKEW_FACTOR)
    nz = sorted(r for r in prows if r > 0) or [0]
    median = nz[len(nz) // 2]
    skew_at = max(int(median * factor), target)

    probe_specs: List[tuple] = []
    build_specs: List[tuple] = []
    run_lo = None
    run_rows = 0

    def flush_run(hi):
        nonlocal run_lo, run_rows
        if run_lo is not None:
            probe_specs.append(("range", run_lo, hi))
            build_specs.append(("range", run_lo, hi))
            run_lo, run_rows = None, 0

    for p, r in enumerate(prows):
        if r > skew_at:
            flush_run(p)
            k = max(2, -(-r // target))
            for j in range(k):
                probe_specs.append(("slice", p, j, k))
                build_specs.append(("range", p, p + 1))
            continue
        if run_lo is None:
            run_lo = p
        elif run_rows + r > target:
            flush_run(p)
            run_lo = p
        run_rows += r
    flush_run(len(prows))

    probe_read = TpuAQEShuffleReadExec(conf, probe_ex, probe_specs)
    build_read = TpuAQEShuffleReadExec(conf, build_ex, build_specs)
    return ((probe_read, build_read) if probe_left
            else (build_read, probe_read))


class TpuLazyAQEReadExec(TpuExec):
    """Defers AQE spec planning to first touch: stats exist only after the
    exchange's map side materializes (reference: AQE re-optimizes at query
    stage boundaries). Coalesce-only unless a joint join resolver is
    supplied."""

    def __init__(self, conf: RapidsConf, exchange: TpuShuffleExchangeExec,
                 resolver=None):
        super().__init__(conf, [exchange])
        self._resolver = resolver
        self._inner: Optional[TpuAQEShuffleReadExec] = None

    def _resolve(self) -> TpuAQEShuffleReadExec:
        if self._inner is None:
            if self._resolver is not None:
                self._inner = self._resolver()
            else:
                self._inner = plan_aqe_coalesce(
                    self.conf, self.children[0])  # type: ignore[arg-type]
        return self._inner

    @property
    def output_schema(self) -> StructType:
        return self.children[0].output_schema

    @property
    def num_partitions(self) -> int:
        from .base import in_planning

        if self._inner is None and in_planning():
            # plan-time heuristics must NOT materialize the stage (review
            # finding: a downstream sort's partition-count check was
            # executing the whole stage during plan conversion)
            return self.children[0].num_partitions
        return self._resolve().num_partitions

    def describe(self):
        if self._inner is not None:
            return f"TpuLazyAQEReadExec -> {self._inner.describe()}"
        return "TpuLazyAQEReadExec (unplanned)"

    def execute_partition(self, index: int) -> Iterator[ColumnarBatch]:
        yield from self._resolve().execute_partition(index)


def lazy_aqe_join_pair(
    conf: RapidsConf,
    left_ex: TpuShuffleExchangeExec,
    right_ex: TpuShuffleExchangeExec,
    probe_left: bool = True,
) -> Tuple[TpuLazyAQEReadExec, TpuLazyAQEReadExec]:
    """Two lazy reads over a co-partitioned join pair that resolve their
    (index-aligned) specs JOINTLY on first touch."""
    state: Dict[str, tuple] = {}

    def resolve_pair():
        if "pair" not in state:
            state["pair"] = plan_aqe_join_pair(
                conf, left_ex, right_ex, probe_left)
        return state["pair"]

    return (
        TpuLazyAQEReadExec(conf, left_ex, lambda: resolve_pair()[0]),
        TpuLazyAQEReadExec(conf, right_ex, lambda: resolve_pair()[1]),
    )


class TpuBroadcastExchangeExec(TpuExec):
    """Materialize the child into one batch every consumer partition reads.

    Reference analog: GpuBroadcastExchangeExecBase
    (GpuBroadcastExchangeExec.scala:237) — the build side is concatenated
    once and shared; on one host "broadcast" is reuse of the same
    device-resident batch (serialized through the host path only when the
    host transport is configured, mirroring the serialize-for-driver step).
    """

    def __init__(self, conf: RapidsConf, child: TpuExec):
        super().__init__(conf, [child])
        self._built: Optional[ColumnarBatch] = None
        self._lock = threading.Lock()

    @property
    def output_schema(self) -> StructType:
        return self.children[0].output_schema

    @property
    def num_partitions(self) -> int:
        return 1

    def describe(self):
        return "TpuBroadcastExchangeExec"

    def materialize(self) -> Optional[ColumnarBatch]:
        with self._lock:
            if self._built is None:
                from .join import _concat_all

                built = _concat_all(self.conf, self.children[0])
                if (
                    built is not None
                    and self.conf.get(SHUFFLE_TRANSPORT_CLASS) == "host"
                ):
                    from ..shuffle.serializer import (
                        deserialize_batch,
                        serialize_batch,
                    )

                    built = deserialize_batch(serialize_batch(
                        built, self.conf.get(SHUFFLE_COMPRESSION_CODEC)))
                if built is not None:
                    # broadcast batches are registered spillable, like the
                    # reference's SerializeConcatHostBuffersDeserializeBatch
                    # living in the catalog (GpuBroadcastExchangeExec.scala);
                    # only the handle keeps a reference, so a spill really
                    # frees the device copy
                    from ..memory import SpillableColumnarBatch
                    from .. import xla_cost as _xc

                    # scoped registration: materialize() runs on first
                    # consumer pull, outside op_timed, so the ledger
                    # needs the op pushed explicitly
                    with _xc.op_scope(self.node_name):
                        self._spillable = SpillableColumnarBatch(
                            built, ledger_kind="plan_state")
                self._built = True  # latch: build attempted
            if getattr(self, "_spillable", None) is not None:
                return self._spillable.get_batch()
            return None

    def execute_partition(self, index: int) -> Iterator[ColumnarBatch]:
        b = self.materialize()
        if b is not None:
            yield self.record_batch(b)
