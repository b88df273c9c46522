"""Basic physical operators: scan-from-memory, project, filter, range,
union, limit, expand, coalesce-batches.

Reference analog: basicPhysicalOperators.scala (GpuProjectExec:48,
GpuFilter:113-129, GpuRangeExec:187, GpuUnionExec:315, GpuCoalesceExec:353),
limit.scala:51, GpuExpandExec.scala:67, GpuCoalesceBatches.scala.

TPU re-design notes:
  * Filter fuses condition evaluation AND row compaction into one jitted
    program — the cudf path launches a kernel per expression node plus a
    filter kernel; here XLA sees the whole thing.
  * Every pipeline is cached per (expression tree, input layout signature)
    so ragged batch sizes reuse executables via capacity bucketing.
"""
from __future__ import annotations

from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import types as T
from ..columnar import ColumnarBatch, DeviceColumn
from ..columnar.column import column_from_pylist
from ..conf import MAX_READER_BATCH_SIZE_ROWS, RapidsConf
from ..expr import expressions as E
from ..expr.eval import ColV, StrV, lower
from ..ops import filter_gather
from ..types import StructField, StructType
from ..columnar.column import choose_capacity
from .base import (
    NUM_OUTPUT_BATCHES,
    NUM_OUTPUT_ROWS,
    TpuExec,
    batch_from_vals,
    batch_signature,
    program,
    vals_of_batch,
)


def _output_schema_for(exprs: Sequence[E.Expression], child: StructType) -> StructType:
    fields = []
    for i, e in enumerate(exprs):
        name = e.name if isinstance(e, E.Alias) else (
            e.name if isinstance(e, E.UnresolvedAttribute) else f"col{i}"
        )
        bound = E.bind_references(e, child)
        fields.append(StructField(name, bound.dtype, bound.nullable))
    return StructType(tuple(fields))


class InMemoryScanExec(TpuExec):
    """Leaf over already-device-resident batches (test/data source seam).

    Under ``spark.rapids.tpu.sql.inMemoryScan.hostResident`` the cached
    representation lives on the HOST (the faithful Spark ``.cache()``
    semantics — the cache survives the query) and every execute uploads
    fresh device planes. Fresh uploads have exactly one reference — the
    executing query — so they are marked exclusive and every certified
    downstream site may donate them (plugin/donation.py). The default
    device-resident mode retains device batches across executes and
    therefore never marks them: donating a retained plane would delete
    the cache out from under the next query."""

    def __init__(self, conf: RapidsConf, partitions: Sequence[Sequence[ColumnarBatch]],
                 schema: StructType):
        super().__init__(conf)
        self._partitions = [list(p) for p in partitions]
        self._schema = schema
        from ..conf import SCAN_HOST_RESIDENT

        self._host_resident = bool(conf.get(SCAN_HOST_RESIDENT))
        self._host_planes: Optional[List[List[Optional[tuple]]]] = None

    @property
    def output_schema(self):
        return self._schema

    @property
    def num_partitions(self):
        return len(self._partitions)

    def _snapshot_to_host(self) -> List[List[Optional[tuple]]]:
        """One-time demotion of the cached batches to host numpy planes
        (one batched pull per batch through the sanctioned sync point).
        Dict-encoded batches stay device-resident — their dictionary
        pools are shared, so they could never donate anyway. Built into
        a local and assigned whole by the caller: concurrent partition
        executors may both compute it (idempotent — source batches are
        immutable), but neither ever observes a partial list."""
        from .base import host_pull

        out: List[List[Optional[tuple]]] = []
        for part in self._partitions:
            rows: List[Optional[tuple]] = []
            for b in part:
                if any(c.is_dict for c in b.columns):
                    rows.append(None)
                    continue
                planes = []
                for c in b.columns:
                    planes.append(tuple(
                        getattr(c, s, None)
                        for s in ("data", "validity", "offsets", "chars")))
                pulled = host_pull(
                    [a for ps in planes for a in ps if a is not None])
                it = iter(pulled)
                rows.append((b.num_rows, b.capacity, tuple(
                    tuple(next(it) if a is not None else None for a in ps)
                    for ps in planes)))
            out.append(rows)
        return out

    def _upload(self, b: ColumnarBatch, snap: tuple) -> ColumnarBatch:
        import jax.numpy as jnp

        from ..plugin import donation as _donation

        num_rows, _cap, planes = snap
        cols = []
        for c, (data, validity, offsets, chars) in zip(b.columns, planes):
            cols.append(DeviceColumn(
                c.dtype, num_rows,
                None if data is None else jnp.asarray(data),
                jnp.asarray(validity),
                None if offsets is None else jnp.asarray(offsets),
                None if chars is None else jnp.asarray(chars)))
        return _donation.mark_exclusive(
            ColumnarBatch(cols, self._schema, num_rows))

    def execute_partition(self, index: int) -> Iterator[ColumnarBatch]:
        if self._host_resident:
            if self._host_planes is None:
                self._host_planes = self._snapshot_to_host()
            for b, snap in zip(self._partitions[index],
                               self._host_planes[index]):
                yield self.record_batch(
                    b if snap is None else self._upload(b, snap))
            return
        for b in self._partitions[index]:
            yield self.record_batch(b)

    def partition_rows(self):
        """Static per-partition row counts (batch num_rows are host ints)
        — the plananalysis mesh forecast's input for host-staged sources."""
        return [
            sum(int(b.num_rows) for b in p) for p in self._partitions
        ]

    @staticmethod
    def from_pydict(conf: RapidsConf, data, schema: StructType,
                    num_partitions: int = 1) -> "InMemoryScanExec":
        batch = ColumnarBatch.from_pydict(data, schema)
        if num_partitions == 1:
            return InMemoryScanExec(conf, [[batch]], schema)
        rows = batch.to_rows()
        chunks: List[List[ColumnarBatch]] = []
        n = len(rows)
        per = (n + num_partitions - 1) // num_partitions
        from ..columnar.batch import batch_from_rows

        for i in range(num_partitions):
            part = rows[i * per: (i + 1) * per]
            chunks.append([batch_from_rows(part, schema)] if part else [])
        return InMemoryScanExec(conf, chunks, schema)


class TpuInMemoryTableScanExec(TpuExec):
    """Serves a plan marked by ``DataFrame.cache()`` from the device
    (reference: the ``InMemoryTableScanExec`` replacement over
    ``ParquetCachedBatchSerializer``'s columnar cache). The session's
    ``sql/cache.CachedRelation`` holds what is resident; this exec fills it
    from the child plan on the first action and serves it on every later
    one, whichever query's plan the exec belongs to.

    One device: the child plan's batches, kept as they came (claimed from
    the donation protocol: a retained plane is never donated). A mesh
    (``shuffle.mode`` other than host, more than one device, a child that
    can stage itself: ``stage_mesh_planes``): the relation IS the child's
    ``StagedPlanes``, global arrays under ``row_sharding(mesh)`` with one
    shard a device, staged once and handed to every mesh stage as they
    are; partition ``i`` of this exec is then shard ``i``. Resident bytes
    are booked per device with the ``BufferCatalog``; cached shards are not
    evicted or spilled, so a fill that does not fit the budget fails by
    name before it uploads.

    Spans: ``.fill`` (``rows``, ``bytes``, ``shards``; the child scan's own
    spans nest under it) and ``.serve`` (``hits``: 1 when the relation was
    resident before the call; ``rows`` and ``bytes`` served from residency,
    0 on the call that filled; ``source=cached``)."""

    #: what a mesh stage's forecast and actuals call these planes
    mesh_stage_source = "cached"

    def __init__(self, conf: RapidsConf, child: TpuExec, relation,
                 files_key: tuple = ()):
        super().__init__(conf, [child])
        self.relation = relation
        self.files_key = files_key
        self._n_shards = self._mesh_shards()

    def _mesh_shards(self) -> int:
        """Shards of a mesh-resident relation; 0 = device batches."""
        rel = self.relation
        if rel.planes is not None:
            return len(rel.planes.counts)
        if rel.batches is not None:
            return 0
        from ..parallel.mesh import get_mesh
        from .mesh import mesh_available

        if not mesh_available(self.conf):
            return 0
        items = getattr(self.children[0], "mesh_stage_items", None)
        if items is None or items() is None:
            return 0
        n = int(get_mesh(conf=self.conf).devices.size)
        return n if n > 1 else 0

    @property
    def output_schema(self):
        return self.children[0].output_schema

    @property
    def num_partitions(self):
        return self._n_shards or self.children[0].num_partitions

    def describe(self):
        return f"TpuInMemoryTableScanExec({self.relation.describe()})"

    def host_prefetch(self) -> None:
        # a resident relation reads no file; a mesh fill stages for itself
        if not self.relation.filled and not self._n_shards:
            super().host_prefetch()

    # -- the forecast's view (exec/mesh.forecast_mesh_staging) -------------
    def mesh_stage_items(self):
        if not self._n_shards:
            return None
        return self.children[0].mesh_stage_items()

    def partition_rows(self):
        if self._n_shards:
            planes = self.relation.planes
            return None if planes is None else [
                int(c) for c in planes.counts]
        pr = getattr(self.children[0], "partition_rows", None)
        return pr() if pr is not None else None

    # -- fill ---------------------------------------------------------------
    def _fill_planes(self, mesh, n_shards: int, conf, on_shard) -> None:
        from ..io import mesh_stage as MS
        from ..memory.catalog import BufferCatalog
        from ..memory.retry import named_oom

        child = self.children[0]
        op = f"{self.node_name}.fill"
        fc = MS.forecast_staging(
            child.mesh_stage_items(), n_shards,
            self.conf.shape_bucket_min, self.output_schema.fields)
        ids = [int(d.id) for d in mesh.devices.reshape(-1)]
        BufferCatalog.get().check_resident_fit(
            dict(zip(ids, fc["staged_bytes"])), op)
        with self.section("fill") as span, named_oom(op):
            planes = child.stage_mesh_planes(
                mesh, n_shards, conf, on_shard=on_shard)
            if planes is None:
                raise RuntimeError(
                    f"{child.node_name} forecast a sharded scan and "
                    "declined to stage it")
            rows = int(planes.counts.sum())
            self.relation.store(
                planes=planes, rows=rows,
                per_device=dict(zip(ids, planes.staged_bytes)),
                files_key=self.files_key)
            span.set(rows=rows, bytes=int(self.relation.bytes),
                     shards=n_shards)

    def _fill_batches(self) -> None:
        from ..memory.catalog import BufferCatalog
        from ..memory.retry import named_oom
        from ..plugin import donation as _donation
        from .base import batch_arrays

        child = self.children[0]
        op = f"{self.node_name}.fill"
        with self.section("fill") as span, named_oom(op):
            parts: List[List[ColumnarBatch]] = []
            sizes: List[Tuple[int, int]] = []
            for p in range(child.num_partitions):
                kept = [_donation.claim(b)
                        for b in child.execute_partition(p)]
                parts.append(kept)
                sizes.append((
                    sum(int(b.num_rows) for b in kept),
                    sum(int(a.size) * a.dtype.itemsize
                        for b in kept for a in batch_arrays(b))))
            rows = sum(r for r, _ in sizes)
            nbytes = sum(b for _, b in sizes)
            per_device = {int(jax.devices()[0].id): nbytes}
            BufferCatalog.get().check_resident_fit(per_device, op)
            self.relation.store(
                batches=parts, part_sizes=sizes, rows=rows,
                per_device=per_device, files_key=self.files_key)
            span.set(rows=rows, bytes=int(nbytes), shards=1)

    def _serve(self, hit: bool, rows: int, nbytes: int, shards: int):
        """The ``.serve`` span of one hand-over, and its always-on twins."""
        self.relation.hits += int(hit)
        self.metric("cacheHits").add(int(hit))
        self.metric("cachedBytes", "bytes").set(self.relation.bytes)
        return self.section(
            "serve", hits=int(hit), rows=rows if hit else 0,
            bytes=nbytes if hit else 0, shards=shards, source="cached")

    # -- serve ----------------------------------------------------------------
    def _resident_planes(self, mesh, n_shards: int, conf, on_shard=None):
        """(the relation's planes, were they resident before this call);
        the first call stages the child scan, under ``.fill``."""
        rel = self.relation
        with rel.lock:
            hit = rel.planes is not None
            if not hit:
                self._fill_planes(mesh, n_shards, conf, on_shard)
            return rel.planes, hit

    def stage_mesh_planes(self, mesh, n_shards: int, conf, on_shard=None):
        """The mesh stages' hand-over: the resident planes as they are —
        no host decode, no ``device_put``, no copy, no donation. The first
        action answers from the planes it has just cached."""
        if not self._n_shards or n_shards != self._n_shards:
            return None
        planes, hit = self._resident_planes(mesh, n_shards, conf, on_shard)
        with self._serve(hit, self.relation.rows, self.relation.bytes,
                         n_shards):
            return planes._replace(
                source=self.mesh_stage_source,
                uploaded_bytes=0 if hit else planes.uploaded_bytes)

    def _shard_batch(self, planes, index: int) -> ColumnarBatch:
        """Shard ``index`` of mesh-resident planes as a batch on the
        default device, for a consumer that is not a mesh stage."""
        n, cap = int(planes.counts[index]), planes.cap
        home = jax.devices()[0]
        cols = []
        for j, f in enumerate(self.output_schema.fields):
            pair = []
            for plane in planes.cols[2 * j: 2 * j + 2]:
                shard = next(s for s in plane.addressable_shards
                             if (s.index[0].start or 0) == index * cap)
                pair.append(jax.device_put(shard.data, home))
            cols.append(DeviceColumn(f.dataType, n, pair[0], pair[1]))
        return ColumnarBatch(cols, self.output_schema, n)

    def execute_partition(self, index: int) -> Iterator[ColumnarBatch]:
        rel = self.relation
        if self._n_shards:
            from ..parallel.mesh import get_mesh

            planes, hit = self._resident_planes(
                get_mesh(conf=self.conf), self._n_shards, self.conf)
            n = int(planes.counts[index])
            with self._serve(hit, n, int(planes.staged_bytes[index]), 1):
                batch = self._shard_batch(planes, index) if n else None
            if batch is not None:
                yield self.record_batch(batch)
            return
        with rel.lock:
            hit = rel.batches is not None
            if not hit:
                self._fill_batches()
            batches = list(rel.batches[index])
        with self._serve(hit, *rel.part_sizes[index], 1):
            pass
        for b in batches:
            yield self.record_batch(b)


_PROJECT_CACHE: dict = {}


def _project_pipeline(exprs: Tuple[E.Expression, ...], sig: tuple, cap: int,
                      nonnull: Tuple[bool, ...] = (),
                      donate: Tuple[int, ...] = ()):
    """Standalone projection program. ``nonnull``: the plan analyzer's
    validity-elision flags for the input columns — flagged columns swap
    their stored validity plane for the iota-derived liveness mask
    (ops/filter_gather.elide_validity); the compiled fn takes
    ``(cols, num_rows)`` either way so call sites stay uniform."""
    key = (exprs, sig, cap, nonnull)

    def build():
        @program("project")
        def run(cols, num_rows):
            if nonnull and any(nonnull):
                live = filter_gather.live_of(num_rows, cap)
                cols = filter_gather.elide_validity(cols, live, nonnull)
            return [lower(e, cols, cap) for e in exprs]

        return jax.jit(run, donate_argnums=donate)

    from .base import cached_pipeline

    return cached_pipeline(_PROJECT_CACHE, key, "project", build,
                           donate=donate)


class TpuProjectExec(TpuExec):
    """reference: GpuProjectExec (basicPhysicalOperators.scala:48-61).

    Fusable: a project never dispatches alone if its neighbors fuse too.
    Partition-context expressions (rand / monotonically_increasing_id /
    spark_partition_id / input_file_name, plus hash() over strings, which
    needs a host-synced byte bound) evaluate at the exec boundary as
    appended input columns — the same treatment Spark gives
    nondeterministic expressions by pinning them in their own Project —
    and such a project does not fuse."""

    def __init__(self, conf: RapidsConf, exprs: Sequence[E.Expression], child: TpuExec):
        super().__init__(conf, [child])
        self.exprs = list(exprs)
        self._schema = _output_schema_for(self.exprs, child.output_schema)
        self._bound = tuple(
            E.bind_references(e, child.output_schema) for e in self.exprs
        )
        self._ctx_exprs = self._collect_ctx_exprs()

    def _collect_ctx_exprs(self):
        """Distinct context subexpressions, in first-appearance order.
        Equal nodes share one column — Spark semantics: two rand(5) calls
        draw the same per-row sequence (same seeded generator)."""
        out = []

        def walk(e):
            if isinstance(e, E.NONDETERMINISTIC_CONTEXT_EXPRS) or (
                isinstance(e, E.Murmur3Hash)
                and any(T.is_string(c.dtype) for c in e.exprs)
            ):
                if e not in out:
                    out.append(e)
                return
            for c in e.children:
                walk(c)

        for b in self._bound:
            walk(b)
        return tuple(out)

    @property
    def fusable(self):  # type: ignore[override]
        return not self._ctx_exprs

    @property
    def output_schema(self):
        return self._schema

    def describe(self):
        return f"TpuProjectExec [{', '.join(map(str, self.exprs))}]"

    def fusion_key(self):
        return ("project", self._bound)

    def lower_batch(self, cols, live, cap, side=()):
        return [lower(e, cols, cap) for e in self._bound], live

    def execute_partition(self, index: int) -> Iterator[ColumnarBatch]:
        # per-batch timing/tracing happens inside run_fused_chain /
        # _execute_with_context (an outer wrapper here would also bill the
        # CONSUMER's time between yields to this exec)
        from .base import run_fused_chain

        if self._ctx_exprs:
            yield from self._execute_with_context(index)
        else:
            yield from run_fused_chain(self, index)

    # -- partition-context evaluation --------------------------------------
    def _source_file(self, index: int) -> str:
        """File path for input_file_name: walk single-child row-preserving
        execs down to a file scan (partition indices pass through 1:1)."""
        node: TpuExec = self.children[0]
        while True:
            scanner = getattr(node, "scanner", None)
            if scanner is not None and hasattr(scanner, "splits"):
                splits = scanner.splits()
                return splits[index].path if index < len(splits) else ""
            kids = node.children
            if len(kids) != 1 or not getattr(node, "fusable", False):
                return ""  # not a file scan source (Spark returns "")
            node = kids[0]

    def _ctx_columns(self, batch, index: int, row_base, cap: int, fpath: str):
        """Materialize one DeviceColumn per context expression."""
        import jax.numpy as jnp

        from ..expr.nondet import rand_double_jax
        from ..ops import hashing
        from ..ops.sort import max_string_len
        from .base import count_scalar
        from .scan import constant_string_column

        cols = []
        fields = []
        n = batch.num_rows_lazy
        idx64 = jnp.arange(cap, dtype=jnp.int64)
        for k, e in enumerate(self._ctx_exprs):
            if isinstance(e, E.SparkPartitionID):
                c = DeviceColumn(
                    T.INT, n, jnp.full(cap, index, jnp.int32),
                    jnp.ones(cap, jnp.bool_))
            elif isinstance(e, E.MonotonicallyIncreasingID):
                base = (jnp.int64(index) << 33) + count_scalar(
                    row_base).astype(jnp.int64)
                c = DeviceColumn(
                    T.LONG, n, base + idx64, jnp.ones(cap, jnp.bool_))
            elif isinstance(e, E.Rand):
                rows = count_scalar(row_base).astype(jnp.int64) + idx64
                c = DeviceColumn(
                    T.DOUBLE, n, rand_double_jax(e.seed, index, rows),
                    jnp.ones(cap, jnp.bool_))
            elif isinstance(e, E.InputFileName):
                nn = n if isinstance(n, int) else cap
                c = constant_string_column(fpath, nn, cap)
            else:  # Murmur3Hash with string children
                vals = [lower(x, vals_of_batch(batch), cap)
                        for x in e.exprs]
                smls = [
                    max(4, int(max_string_len(v)))
                    for v in vals if hasattr(v, "offsets")
                ]
                h = hashing.murmur3(
                    vals, [x.dtype for x in e.exprs], e.seed, smls)
                c = DeviceColumn(T.INT, n, h, jnp.ones(cap, jnp.bool_))
            cols.append(c)
            fields.append(StructField(f"_ctx{k}", c.dtype, False))
        return cols, fields

    def _execute_with_context(self, index: int) -> Iterator[ColumnarBatch]:
        from .base import count_scalar

        child = self.children[0]
        child_schema = child.output_schema
        nbase = len(child_schema.fields)
        subst = {e: i for i, e in enumerate(self._ctx_exprs)}

        def rewrite(node):
            i = subst.get(node)
            if i is not None:
                return E.BoundReference(
                    nbase + i, node.dtype, node.nullable)
            return node

        rewritten = tuple(b.transform(rewrite) for b in self._bound)
        fpath = self._source_file(index)
        row_base = 0
        for batch in child.execute_partition(index):
            with self.op_timed("ctx"):
                cap = batch.capacity
                extra_cols, extra_fields = self._ctx_columns(
                    batch, index, row_base, cap, fpath)
                ext = ColumnarBatch(
                    list(batch.columns) + extra_cols,
                    StructType(tuple(child_schema.fields) + tuple(extra_fields)),
                    batch.num_rows_lazy)
                from .base import _donation
                from .base import count_scalar as _cs

                don = _donation()
                # ext shares the child batch's planes; the appended ctx
                # columns are fresh by construction, so the dispatch may
                # donate exactly when the CHILD batch is donatable (the
                # loop reads only its scalar row count afterwards)
                nr_lazy = batch.num_rows_lazy
                mask = don.dispatch_mask("project", batch, self.conf)
                fn = _project_pipeline(
                    rewritten, batch_signature(ext), cap, donate=mask)
                if mask:
                    # no retry harness wraps this dispatch, so the
                    # snapshot leg of the guard is skipped: nothing
                    # re-reads the planes on failure
                    with don.guard("project", ext, op=self.node_name,
                                   snapshot=False,
                                   metric=self.metric("donatedBytes")):
                        vals = fn(vals_of_batch(ext), _cs(nr_lazy))
                else:
                    vals = fn(vals_of_batch(ext), _cs(nr_lazy))
                out = don.mark_exclusive(
                    batch_from_vals(vals, self._schema, nr_lazy))
            yield self.record_batch(out)
            nr = batch.num_rows_lazy
            row_base = (row_base + nr if isinstance(nr, int)
                        and isinstance(row_base, int)
                        else count_scalar(row_base) + count_scalar(nr))


class TpuFilterExec(TpuExec):
    """reference: GpuFilterExec/GpuFilter (basicPhysicalOperators.scala:113-172).

    Condition eval + row compaction lower into the fused stage; the surviving
    row count stays on device (cudf syncs for it — we don't have to)."""

    fusable = True
    sparsifies = True

    def __init__(self, conf: RapidsConf, condition: E.Expression, child: TpuExec):
        super().__init__(conf, [child])
        self.condition = condition
        self._bound = E.bind_references(condition, child.output_schema)

    @property
    def output_schema(self):
        return self.children[0].output_schema

    def describe(self):
        return f"TpuFilterExec [{self.condition}]"

    def fusion_key(self):
        return ("filter", self._bound)

    def lower_batch(self, cols, live, cap, side=()):
        c = lower(self._bound, cols, cap)
        return cols, live & c.data & c.validity

    def execute_partition(self, index: int) -> Iterator[ColumnarBatch]:
        from .base import run_fused_chain

        yield from run_fused_chain(self, index)


class TpuRangeExec(TpuExec):
    """reference: GpuRangeExec (basicPhysicalOperators.scala:187)."""

    def __init__(self, conf: RapidsConf, start: int, end: int, step: int = 1,
                 num_slices: int = 1, name: str = "id"):
        super().__init__(conf)
        if step == 0:
            raise ValueError("step must not be 0")
        self.start, self.end, self.step = start, end, step
        self.num_slices = num_slices
        self._schema = StructType((StructField(name, T.LONG, False),))

    @property
    def output_schema(self):
        return self._schema

    @property
    def num_partitions(self):
        return self.num_slices

    def execute_partition(self, index: int) -> Iterator[ColumnarBatch]:
        total = max(0, -(-(self.end - self.start) // self.step))
        per = (total + self.num_slices - 1) // self.num_slices if total else 0
        lo = index * per
        hi = min(total, (index + 1) * per)
        max_rows = self.conf.get(MAX_READER_BATCH_SIZE_ROWS)
        pos = lo
        while pos < hi:
            n = min(max_rows, hi - pos)
            cap = choose_capacity(n, self.conf.shape_bucket_min)
            base = self.start + pos * self.step
            data = jnp.arange(cap, dtype=jnp.int64) * self.step + base
            live = jnp.arange(cap, dtype=jnp.int32) < n
            data = jnp.where(live, data, 0)
            col = DeviceColumn(T.LONG, n, data, live)
            yield self.record_batch(ColumnarBatch([col], self._schema, n))
            pos += n


class TpuUnionExec(TpuExec):
    """reference: GpuUnionExec (basicPhysicalOperators.scala:315)."""

    def __init__(self, conf: RapidsConf, children: Sequence[TpuExec]):
        super().__init__(conf, children)
        self._schema = children[0].output_schema

    @property
    def output_schema(self):
        return self._schema

    @property
    def num_partitions(self):
        return sum(c.num_partitions for c in self.children)

    def execute_partition(self, index: int) -> Iterator[ColumnarBatch]:
        for c in self.children:
            if index < c.num_partitions:
                for b in c.execute_partition(index):
                    yield self.record_batch(b)
                return
            index -= c.num_partitions
        raise IndexError(index)


class TpuLocalLimitExec(TpuExec):
    """reference: GpuBaseLimitExec (limit.scala:51) — per-partition limit."""

    def __init__(self, conf: RapidsConf, limit: int, child: TpuExec):
        super().__init__(conf, [child])
        self.limit = limit

    @property
    def output_schema(self):
        return self.children[0].output_schema

    def execute_partition(self, index: int) -> Iterator[ColumnarBatch]:
        remaining = self.limit
        for batch in self.children[0].execute_partition(index):
            if remaining <= 0:
                return
            if batch.num_rows <= remaining:
                remaining -= batch.num_rows
                yield self.record_batch(batch)
                if remaining == 0:
                    return  # don't pull (compute) another child batch
            else:
                vals, count = filter_gather.slice_cols(
                    vals_of_batch(batch), 0, choose_capacity(remaining, self.conf.shape_bucket_min),
                    jnp.int32(min(remaining, batch.num_rows)),
                )
                out = batch_from_vals(vals, self.output_schema, remaining)
                remaining = 0
                yield self.record_batch(out)
                return


class TpuCollectLimitExec(TpuExec):
    """Global limit: one output partition draining children in order until
    ``limit`` rows (reference: GpuCollectLimitMeta limit.scala:126)."""

    def __init__(self, conf: RapidsConf, limit: int, child: TpuExec):
        super().__init__(conf, [child])
        self.limit = limit

    @property
    def output_schema(self):
        return self.children[0].output_schema

    @property
    def num_partitions(self):
        return 1

    def execute_partition(self, index: int) -> Iterator[ColumnarBatch]:
        remaining = self.limit
        child = self.children[0]
        for p in range(child.num_partitions):
            for batch in child.execute_partition(p):
                if remaining <= 0:
                    return
                n = batch.num_rows
                if n <= remaining:
                    remaining -= n
                    yield self.record_batch(batch)
                    if remaining == 0:
                        return  # don't pull (compute) another child batch
                else:
                    vals, count = filter_gather.slice_cols(
                        vals_of_batch(batch), 0,
                        choose_capacity(remaining, self.conf.shape_bucket_min),
                        jnp.int32(remaining),
                    )
                    out = batch_from_vals(vals, self.output_schema, remaining)
                    remaining = 0
                    yield self.record_batch(out)
                    return


class TpuExpandExec(TpuExec):
    """reference: GpuExpandExec (GpuExpandExec.scala:67) — each input batch
    is projected once per projection group (rollup/cube lowering)."""

    def __init__(self, conf: RapidsConf, projections: Sequence[Sequence[E.Expression]],
                 output_names: Sequence[str], child: TpuExec):
        super().__init__(conf, [child])
        self.projections = [list(p) for p in projections]
        child_schema = child.output_schema
        first = [E.bind_references(e, child_schema) for e in self.projections[0]]
        self._schema = StructType(tuple(
            StructField(n, e.dtype, True) for n, e in zip(output_names, first)
        ))
        self._bound = [
            tuple(E.bind_references(e, child_schema) for e in p)
            for p in self.projections
        ]

    @property
    def output_schema(self):
        return self._schema

    def execute_partition(self, index: int) -> Iterator[ColumnarBatch]:
        from ..plugin.plananalysis import entry_nonnull_flags
        from .base import count_scalar

        nonnull = entry_nonnull_flags(
            self.children[0].output_schema, self.conf)
        for batch in self.children[0].execute_partition(index):
            cap = batch.capacity
            sig = batch_signature(batch)
            vals_in = vals_of_batch(batch)
            for bound in self._bound:
                with self.op_timed():
                    fn = _project_pipeline(bound, sig, cap, nonnull)
                    vals = fn(vals_in, count_scalar(batch.num_rows))
                    out = batch_from_vals(vals, self._schema, batch.num_rows)
                yield self.record_batch(out)


class TpuCoalesceBatchesExec(TpuExec):
    """reference: GpuCoalesceBatches (GpuCoalesceBatches.scala:398-571) —
    concatenate small batches up to a target size before heavy operators."""

    def __init__(self, conf: RapidsConf, child: TpuExec,
                 target_rows: Optional[int] = None):
        super().__init__(conf, [child])
        self.target_rows = target_rows or conf.get(MAX_READER_BATCH_SIZE_ROWS)

    @property
    def output_schema(self):
        return self.children[0].output_schema

    def _flush(self, pending: List[ColumnarBatch]) -> Optional[ColumnarBatch]:
        if not pending:
            return None
        # ONE multi-batch stitch engine-wide: the same helper re-joins
        # split-and-retry pieces (memory/retry.py), so the concat
        # invariants (dict materialization, char-cap bucketing,
        # zero-column row carry) cannot drift between the two paths
        from ..memory.retry import concat_batches

        return concat_batches(self.conf, pending)

    def execute_partition(self, index: int) -> Iterator[ColumnarBatch]:
        pending: List[ColumnarBatch] = []
        rows = 0
        for batch in self.children[0].execute_partition(index):
            if batch.num_rows == 0:
                continue
            pending.append(batch)
            rows += batch.num_rows
            if rows >= self.target_rows:
                with self.op_timed():
                    out = self._flush(pending)
                pending, rows = [], 0
                if out is not None:
                    yield self.record_batch(out)
        with self.op_timed():
            out = self._flush(pending)
        if out is not None:
            yield self.record_batch(out)
