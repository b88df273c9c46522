"""File-source scan exec: splits -> device batches.

Reference analog: GpuFileSourceScanExec.scala (569) + PartitionReaderIterator
+ ColumnarPartitionReaderWithPartitionValues (constant partition columns).
The host half (footer parse, prune, column-chunk read) happened in the
scanner; here each split's arrow table uploads buffer-level and partition
values append as constant device columns.
"""
from __future__ import annotations

import threading
from typing import Iterator, List, Optional, Sequence, Tuple

import numpy as np

from .. import types as T
from ..columnar import ColumnarBatch
from ..columnar.column import DeviceColumn
from ..conf import RapidsConf
from ..types import StructType
from ..columnar.column import choose_capacity
from .base import TpuExec, carry, queued

SCAN_TIME = "scanTime"  # reference metric name (GpuMetricNames)
DECODE_TIME = "tpuDecodeTime"

# Serving-path prefetch pool: host_prefetch() submits whole-split reads
# here. DISTINCT from the srtpu-pqdec chunk-decode pool on purpose — a
# split read fans out chunk decodes onto that pool, so running the outer
# task on the same bounded pool could occupy every worker with waiters
# (classic nested-pool deadlock). Two workers is enough: the point is
# overlap with the device phase, not parallel split storms.
_PREFETCH_POOL = None
_PREFETCH_POOL_LOCK = threading.Lock()


def _prefetch_pool():
    global _PREFETCH_POOL
    if _PREFETCH_POOL is None:
        with _PREFETCH_POOL_LOCK:
            if _PREFETCH_POOL is None:
                from concurrent.futures import ThreadPoolExecutor

                _PREFETCH_POOL = ThreadPoolExecutor(
                    max_workers=2, thread_name_prefix="srtpu-prefetch")
    return _PREFETCH_POOL


def constant_string_column(value, n: int, cap: int) -> DeviceColumn:
    """One value repeated n times (partition-value column) — O(1) python."""
    import jax.numpy as jnp

    if value is None:
        return DeviceColumn(
            T.STRING, n, None, jnp.zeros(cap, bool),
            offsets=jnp.zeros(cap + 1, jnp.int32),
            chars=jnp.zeros(1, jnp.uint8))
    b = str(value).encode("utf-8")
    L = len(b)
    ccap = choose_capacity(max(1, L * n), 128)
    offsets = np.minimum(np.arange(cap + 1, dtype=np.int64) * L,
                         L * n).astype(np.int32)
    chars = np.zeros(ccap, np.uint8)
    if L:
        chars[: L * n] = np.frombuffer(b * n, np.uint8)
    valid = np.zeros(cap, bool)
    valid[:n] = True
    return DeviceColumn(
        T.STRING, n, None, jnp.asarray(valid),
        offsets=jnp.asarray(offsets), chars=jnp.asarray(chars))


class MeshShardedScanExec(TpuExec):
    """Leaf over PER-SHARD host column arrays — the decoded form a
    data-parallel scan hands the mesh. Partition ``i`` is shard ``i``'s
    data: ``stage_mesh_planes`` uploads it straight to mesh device
    ``i % n`` as that device's slice of a NamedSharding-committed global
    array (io/mesh_stage.stage_sharded — no host gather, decode of shard
    k+1 overlapping the upload of shard k). Off-mesh execution builds
    ordinary device batches, so the same exec drives the 1-device
    baseline of the bench mesh lane.

    ``parts``: one entry per partition — a list of (data, validity)
    numpy pairs (schema order) plus the live row count."""

    def __init__(self, conf: RapidsConf, parts, schema: StructType):
        super().__init__(conf)
        self._parts = [
            (list(arrays), int(rows)) for arrays, rows in parts
        ]
        self._schema = schema

    @property
    def output_schema(self) -> StructType:
        return self._schema

    @property
    def num_partitions(self) -> int:
        return max(1, len(self._parts))

    def describe(self):
        return f"MeshShardedScanExec({len(self._parts)} shard parts)"

    def partition_rows(self):
        """Static per-partition row counts (the plananalysis mesh
        forecast's input)."""
        return [rows for _, rows in self._parts]

    def mesh_stage_items(self):
        """Per-item row counts the sharded-scan staging will round-robin
        (None = the fast path would decline; forecast mirrors runtime)."""
        from ..io import mesh_stage as MS

        if not MS.stageable_schema(self._schema):
            return None
        return self.partition_rows()

    def stage_mesh_planes(self, mesh, n_shards: int, conf, on_shard=None):
        from ..io import mesh_stage as MS

        if not MS.stageable_schema(self._schema):
            return None
        assign = MS.round_robin(len(self._parts), n_shards)
        rows_per_shard = [
            sum(self._parts[i][1] for i in idxs) for idxs in assign
        ]

        def decode_shard(s: int) -> "MS.ShardPayload":
            arrays = []
            total = rows_per_shard[s]
            for j, f in enumerate(self._schema.fields):
                dt = f.dataType.to_numpy()
                d = np.empty(total, dt)
                v = np.empty(total, bool)
                pos = 0
                for i in assign[s]:
                    part, rows = self._parts[i]
                    data, valid = part[j]
                    d[pos:pos + rows] = data[:rows]
                    v[pos:pos + rows] = valid[:rows]
                    pos += rows
                arrays.append((d, v))
            return MS.ShardPayload(arrays, total)

        return MS.stage_sharded(
            mesh, n_shards, self._schema, decode_shard, rows_per_shard,
            self.conf.shape_bucket_min, on_shard=on_shard)

    def execute_partition(self, index: int) -> Iterator[ColumnarBatch]:
        import jax.numpy as jnp

        if index >= len(self._parts):
            return
        arrays, n = self._parts[index]
        if n == 0:
            return
        cap = choose_capacity(max(1, n))
        cols = []
        for f, (data, valid) in zip(self._schema.fields, arrays):
            d = np.zeros(cap, f.dataType.to_numpy())
            v = np.zeros(cap, bool)
            d[:n] = data[:n]
            v[:n] = valid[:n]
            cols.append(DeviceColumn(
                f.dataType, n, jnp.asarray(d), jnp.asarray(v)))
        yield self.record_batch(ColumnarBatch(cols, self._schema, n))


class TpuFileSourceScanExec(TpuExec):
    """Columnar scan over a file scanner's splits (one split = one
    partition; the MULTITHREADED reader prefetches neighbors)."""

    def __init__(self, conf: RapidsConf, scanner, fmt: str):
        super().__init__(conf)
        self.scanner = scanner
        self.fmt = fmt
        self._prefetch = None  # MULTITHREADED reader futures
        self._prefetch_dev = None  # host_prefetch device-path futures
        #: splits already drained — a prefetch table rebuilt after an
        #: OOM-pressure invalidation must not resubmit (and then retain)
        #: reads nobody will consume again
        self._consumed_splits: set = set()
        self.metrics[SCAN_TIME] = self.metric(SCAN_TIME)
        self.metrics[DECODE_TIME] = self.metric(DECODE_TIME)

    @property
    def output_schema(self) -> StructType:
        return self.scanner.schema

    @property
    def num_partitions(self) -> int:
        return max(1, self.scanner.num_splits())

    def describe(self):
        return f"TpuFileSourceScanExec {self.fmt} {getattr(self.scanner, 'path', '')}"

    def _read_split(self, index: int):
        """Split read, optionally through the MULTITHREADED prefetcher:
        cloud-path scans buffer EVERY split in a thread pool on first
        touch so later partitions find their bytes already fetched
        (reference: MultiFileCloudParquetPartitionReader
        GpuParquetScan.scala:1299-1333). The serving path's
        host_prefetch() fills the same future table ahead of the drain,
        so an already-started prefetch is consumed whatever the reader
        type."""
        rt = getattr(self.scanner, "reader_type", lambda: "PERFILE")()
        self._consumed_splits.add(index)
        if rt != "MULTITHREADED" and self._prefetch is None:
            return self._host_read_split(index)
        if self._prefetch is None:
            from concurrent.futures import ThreadPoolExecutor

            from ..conf import PARQUET_MULTITHREAD_READ_NUM_THREADS

            pool = ThreadPoolExecutor(
                max_workers=self.conf.get(PARQUET_MULTITHREAD_READ_NUM_THREADS),
                thread_name_prefix="srtpu-scan")
            # splits already drained (this one included) stay None: a
            # table rebuilt after invalidate_prefetch must not resubmit
            # reads nobody will consume again
            read = carry(self._host_read_split)
            self._prefetch = [
                pool.submit(queued(read), i)
                if i not in self._consumed_splits else None
                for i in range(self.scanner.num_splits())
            ]
            pool.shutdown(wait=False)
        fut = self._prefetch[index]
        self._prefetch[index] = None  # free the decoded table once consumed
        if fut is None:  # consumed marker, or invalidated mid-drain
            return self._host_read_split(index)
        return fut.result()

    def _host_read_split(self, index: int):
        """The plain reader: every column of the split decodes on the
        host (pyarrow). Named and counted like the per-column fallback
        of the device path (io/parquet_device.py)."""
        with self.section("host_decode",
                          columns=len(self.output_schema.fields)):
            return self.scanner.read_split_i(index)

    def _prefetch_split_device(self, index: int):
        """host_prefetch's task: the same phases, on a pool thread."""
        with self.section("prefetch"):
            return self.scanner.read_split_device(index)

    def _attach_partition_cols(self, batch: ColumnarBatch, pvals):
        schema = self.output_schema
        pkeys = list(getattr(self.scanner, "partition_cols", ()))
        if not pkeys:
            return batch
        pmap = dict(pvals)
        n, cap = batch.num_rows, max(batch.capacity, 1)
        cols = list(batch.columns)
        for k in pkeys:
            cols.append(constant_string_column(pmap.get(k), n, cap))
        return ColumnarBatch(cols, schema, n)

    def _mesh_row_groups(self):
        """Flat (path, row_group, rows) list for mesh round-robin — the
        sharded scan places row group i on shard i % n. None when the
        scanner's splits don't expose row groups (csv) or a row group's
        metadata is unreadable."""
        splits = getattr(self.scanner, "splits", None)
        if splits is None:
            return None
        try:
            import pyarrow.parquet as pq

            out = []
            mds = {}
            for sp in splits():
                rgs = getattr(sp, "row_groups", None)
                if rgs is None:
                    return None
                md = mds.get(sp.path)
                if md is None:
                    md = mds[sp.path] = pq.ParquetFile(sp.path).metadata
                for rg in rgs:
                    out.append((sp.path, rg, md.row_group(rg).num_rows))
            return out
        except Exception:
            return None

    def stage_mesh_planes(self, mesh, n_shards: int, conf, on_shard=None):
        """Data-parallel parquet ingestion: row groups round-robined
        across mesh shards, each shard's groups host-decoded on a worker
        thread while the previous shard's padded planes upload to ITS
        device (io/mesh_stage.stage_sharded) — PR 7's decode→upload
        pipeline extended across devices. Fixed-width file columns only
        (partition-value columns are strings and keep the generic path).
        Every call decodes and uploads the whole table: the device scan
        cache holds default-device batches and is not consulted.
        Residency across queries comes from ``DataFrame.cache()``: the
        cached relation (exec/basic.TpuInMemoryTableScanExec) calls this
        once and keeps the planes. Spans as on one chip: ``host_decode``
        a shard (``columns`` = column chunks, ``names``) on the worker's
        thread, ``upload`` a shard (``bytes``)."""
        from ..io import mesh_stage as MS

        if getattr(self.scanner, "partition_cols", None):
            return None
        schema = self.output_schema
        if not MS.stageable_schema(schema):
            return None
        rgs = self._mesh_row_groups()
        if rgs is None:
            return None
        assign = MS.round_robin(len(rgs), n_shards)
        rows_per_shard = [
            sum(rgs[i][2] for i in idxs) for idxs in assign
        ]
        columns = [f.name for f in schema.fields]

        def decode_shard(s: int) -> "MS.ShardPayload":
            import pyarrow.parquet as pq

            from ..io.arrow_convert import _np_from_arrow_array

            with self.section("host_decode",
                              columns=len(columns) * len(assign[s]),
                              names=",".join(columns), shard=s):
                by_path = {}
                for i in assign[s]:
                    path, rg, _ = rgs[i]
                    by_path.setdefault(path, []).append(rg)
                tables = [
                    pq.ParquetFile(p).read_row_groups(g, columns=columns)
                    for p, g in by_path.items()
                ]
                total = rows_per_shard[s]
                arrays = []
                for j, f in enumerate(schema.fields):
                    d = np.empty(total, f.dataType.to_numpy())
                    v = np.empty(total, bool)
                    pos = 0
                    for t in tables:
                        arr = t.column(j).combine_chunks()
                        data, valid = _np_from_arrow_array(arr, f.dataType)
                        n = len(t)
                        d[pos:pos + n] = data[:n]
                        v[pos:pos + n] = valid[:n]
                        pos += n
                    arrays.append((d, v))
            return MS.ShardPayload(arrays, total)

        with self.op_timed("decode", DECODE_TIME):
            return MS.stage_sharded(
                mesh, n_shards, schema, decode_shard, rows_per_shard,
                self.conf.shape_bucket_min, on_shard=on_shard)

    def partition_rows(self):
        """Static per-split row counts from parquet metadata (None when
        unknowable) — the plananalysis mesh forecast's input."""
        rgs = self._mesh_row_groups()
        if rgs is None:
            return None
        per = [0] * self.scanner.num_splits()
        for i, sp in enumerate(self.scanner.splits()):
            per[i] = sum(r for p, rg, r in rgs
                         if p == sp.path and rg in sp.row_groups)
        return per

    def mesh_stage_items(self):
        """Per-ROW-GROUP rows the sharded scan round-robins (the mesh
        forecast's mirror of stage_mesh_planes' eligibility + placement;
        None = the fast path would decline)."""
        from ..io import mesh_stage as MS

        if getattr(self.scanner, "partition_cols", None):
            return None
        if not MS.stageable_schema(self.output_schema):
            return None
        rgs = self._mesh_row_groups()
        if rgs is None:
            return None
        return [r for _, _, r in rgs]

    def fused_stage_plans(self, index: int):
        """Stage fusion: hand the consumer exec the traced per-row-group
        decode programs so scan→…→aggregate compiles to ONE executable
        (each extra program in a dependency chain pays a dispatch/queue
        round trip on the TPU host link). None = use execute_partition."""
        if index >= self.scanner.num_splits():
            return None
        fn = getattr(self.scanner, "device_stage_plans", None)
        if fn is None:
            return None
        with self.op_timed("plan", SCAN_TIME) as span:
            stage = fn(index)
            if stage is not None:
                # one span a split the fused stage takes: over a query
                # the counts add up to its scan partitions
                span.set(splits=1, row_groups=len(stage))
            return stage

    def host_prefetch(self) -> None:
        """Serving-path phase split: start every split's host decode (+
        staged upload dispatch on the device path) on the prefetch pool
        NOW, before the caller blocks on the TPU semaphore — host work
        of an admitted query overlaps the running query's device
        compute. The drain consumes the futures instead of re-reading."""
        n = self.scanner.num_splits()
        if n == 0:
            return
        if hasattr(self.scanner, "read_split_device"):
            if self._prefetch_dev is None:
                read_dev = carry(self._prefetch_split_device)
                self._prefetch_dev = [
                    _prefetch_pool().submit(queued(read_dev), i)
                    if i not in self._consumed_splits else None
                    for i in range(n)
                ]
        elif self._prefetch is None:
            read = carry(self._host_read_split)
            self._prefetch = [
                _prefetch_pool().submit(queued(read), i)
                if i not in self._consumed_splits else None
                for i in range(n)
            ]

    def invalidate_prefetch(self) -> None:
        """OOM-pressure hook (memory/retry.py ``on_pressure``): cancel
        pending prefetch futures and drop the tables — the device path's
        futures hold STAGED device uploads, exactly the residency an OOM
        recovery wants back. Already-running futures finish and are
        garbage-collected; the drain falls back to direct re-reads, so
        results are identical either way."""
        for futs in (self._prefetch_dev, self._prefetch):
            if futs:
                for f in futs:
                    if f is not None:
                        f.cancel()
        self._prefetch_dev = None
        self._prefetch = None

    def execute_partition(self, index: int) -> Iterator[ColumnarBatch]:
        from ..io.arrow_convert import arrow_to_batch

        if index >= self.scanner.num_splits():
            return
        # TPU-side page decode (reference: GPU decode via Table.readParquet,
        # GpuParquetScan.scala:1157): host uploads encoded bytes, XLA
        # kernels expand dictionary/RLE pages on-device
        if hasattr(self.scanner, "read_split_device"):
            with self.op_timed("decode", DECODE_TIME) as span:
                if span.on:
                    span.set(splits=1, row_groups=len(
                        self.scanner.splits()[index].row_groups))
                self._consumed_splits.add(index)
                fut = None
                if self._prefetch_dev is not None:
                    fut = self._prefetch_dev[index]
                    self._prefetch_dev[index] = None
                if fut is not None:
                    dev, pvals = fut.result()
                else:
                    dev, pvals = self.scanner.read_split_device(index)
            if dev is not None:
                for b in dev:
                    yield self.record_batch(
                        self._attach_partition_cols(b, pvals))
                return
        from ..memory.retry import named_oom

        with self.op_timed("read", SCAN_TIME):
            table, pvals = self._read_split(index)
        with self.op_timed("decode", DECODE_TIME), \
                named_oom(f"{self.node_name}.decode"):
            # scan staging sits OUTSIDE the retry harness (there is no
            # input batch to split yet): a device allocation failure
            # uploading the decoded split surfaces as the named
            # TpuOutOfDeviceMemory instead of a bare XLA traceback
            schema = self.output_schema
            # the schema only carries the partition keys common to every
            # file (scanner.partition_cols); a split may report extra keys
            # on ragged layouts — select by schema key, not raw count
            pkeys = list(getattr(self.scanner, "partition_cols", ()))
            file_fields = schema.fields[: len(schema.fields) - len(pkeys)]
            batch = arrow_to_batch(
                table, T.StructType(tuple(file_fields)))
            batch = self._attach_partition_cols(batch, pvals)
        yield self.record_batch(batch)
