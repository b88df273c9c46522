"""Mesh-lowered SPMD stages: whole plan fragments as ONE shard_map program
over the device mesh, fed by a sharded scan.

Reference analog: the accelerated shuffle path the planner actually selects
(RapidsShuffleInternalManager.scala:58-150 + the UCX transport): there, a
PARTIAL aggregate, a device-cached shuffle write, an RDMA fetch, and a FINAL
aggregate are four separately-scheduled stages. Here the planner lowers the
whole exchange-bounded stage — partial aggregate -> all_to_all -> final
merge -> result projection, or local-sort -> sampled range exchange -> merge
sort, or hash-exchange both sides -> local join — into ONE jitted SPMD
computation over a jax.sharding.Mesh (parallel/distributed.py), with child
partition i living on mesh shard i % n. XLA schedules the ICI collectives
against compute; nothing touches the host between the child batches and the
stage output.

Fixed-width columns cross the mesh as data/validity planes; STRING columns
cross as offsets/chars/validity planes with the chars riding the
collective's byte-plane all_to_all (parallel/collective.py) — the same
type-agnostic contract as the reference's UCX transport
(RapidsShuffleClient.scala:35-98). Staging computes a static max byte
length per string column, so string GROUP KEYS must be direct column
references (computed string keys have no staged bound and stay on the
single-host exchange, as do binary columns).

Whole-plan SPMD (round 6): a fixed-width filter/project chain between the
stage and its source is ABSORBED into the shard_map program (the execs'
own ``lower_batch`` hooks run per shard, exactly the single-device fused
chain's seam), and a source exposing ``stage_mesh_planes`` (sharded scans:
io/mesh_stage.py — in-memory shard sources, round-robined parquet row
groups) feeds the program with per-shard committed device batches instead
of the host-gathered staging path. The post-PARTIAL aggregate exchange is
sliced to the group cardinality (``shuffle.mesh.aggExchangeCapacity`` +
overflow retry) and the sort exchange granule to ~2x the fair share
(``shuffle.mesh.exchangeBucketFactor``), so the all_to_all surface scales
with what actually crosses the wire, not n_shards x input capacity.
"""
from __future__ import annotations

import functools
from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.mesh import mesh_jit_kwargs, shard_map

from .. import types as T
from ..columnar import ColumnarBatch, DeviceColumn
from ..conf import RapidsConf
from ..expr import aggregates as A
from ..expr import expressions as E
from ..expr.eval import ColV, lower
from ..ops import groupby as groupby_ops
from ..ops.sort import SortOrder
from ..parallel import distributed as D
from ..parallel.mesh import AXIS, get_mesh, row_sharding
from ..types import StructField, StructType
from ..utils.bucketing import bucket_rows
from . import aggregate as XA
from .base import TpuExec, program

P = jax.sharding.PartitionSpec


def _np_of(arr) -> np.ndarray:
    from .base import host_pull

    return np.asarray(host_pull(arr))


class StagedChild:
    """What a mesh stage consumes: flat global planes + counts + layout,
    the absorbed in-program chain steps, and the staging telemetry the
    plananalysis cross-check compares against its forecast."""

    __slots__ = ("cols", "counts", "cap", "layout", "smls", "steps",
                 "staged_bytes", "source", "h2d_bytes")

    def __init__(self, cols, counts, cap, layout, smls, steps=(),
                 staged_bytes=(), source="host", h2d_bytes=0):
        self.cols = cols
        self.counts = counts
        self.cap = cap
        self.layout = layout
        self.smls = smls
        self.steps = tuple(steps)
        self.staged_bytes = tuple(staged_bytes)
        self.source = source
        #: bytes THIS staging sent to the devices (0: resident planes)
        self.h2d_bytes = h2d_bytes

    def steps_sig(self) -> tuple:
        return tuple(s.fusion_key() for s in self.steps)


class _MeshStage(TpuExec):
    """Base: stage child partitions onto the mesh, run one SPMD program,
    emit one output partition per shard."""

    def __init__(self, conf: RapidsConf, children: Sequence[TpuExec]):
        super().__init__(conf, children)
        self.mesh = get_mesh(conf=conf)
        self.n_shards = int(self.mesh.devices.size)
        self._outputs: Optional[List[Optional[ColumnarBatch]]] = None
        #: staging/execution actuals per materialized child, keyed like the
        #: plananalysis mesh forecast ("cap", "per_shard_rows",
        #: "staged_bytes", "source") + "per_chip_ns"/"programs" run-wide —
        #: the cross-check's measured side
        self.mesh_actuals: dict = {}

    @property
    def num_partitions(self) -> int:
        return self.n_shards

    def reset_for_rerun(self) -> None:
        """Drop materialized outputs so the stage re-stages and re-runs
        (the bench mesh lane times staging+execution per iteration; the
        compiled SPMD program stays cached)."""
        self._outputs = None

    # -- whole-plan absorption --------------------------------------------
    def _absorb_chain(self, child: TpuExec):
        """Peel fixed-width filter/project execs off ``child`` so they run
        INSIDE the shard_map program (their own ``lower_batch`` hooks —
        the same seam the single-device fused chain uses). Absorption is
        conservative: every schema the chain touches must be fixed-width
        (string/dict columns keep the host-fed path, whose staging knows
        their byte bounds) and each exec must be fusable (partition-
        context expressions pin their project at the exec boundary).
        Returns (base child, steps bottom-up)."""
        from ..conf import MESH_WHOLE_PLAN
        from .basic import TpuFilterExec, TpuProjectExec

        if not self.conf.get(MESH_WHOLE_PLAN):
            return child, ()
        steps: List[TpuExec] = []
        node = child
        while isinstance(node, (TpuFilterExec, TpuProjectExec)):
            if not getattr(node, "fusable", False):
                break
            below = node.children[0].output_schema
            if not all(T.is_fixed_width(f.dataType)
                       for f in node.output_schema.fields):
                break
            if not all(T.is_fixed_width(f.dataType) for f in below.fields):
                break
            steps.append(node)
            node = node.children[0]
        steps.reverse()
        return node, tuple(steps)

    @staticmethod
    def _apply_steps(steps, cols, live, cap):
        """Run absorbed chain steps per shard (trace-time). Returns
        (cols, live-mask) — filters sparsify via the mask (the distributed
        kernels take a mask as their row count), projects rewrite cols."""
        for st in steps:
            cols, live = st.lower_batch(cols, live, cap)
        return cols, live

    # -- staging -----------------------------------------------------------
    def _on_shard_staged(self, s: int, rows: int, nbytes: int,
                         secs: float) -> None:
        """Per-shard staging telemetry: the transfer event gains a shard
        lane (Perfetto shows one upload track per chip) and the live
        plane counts rows per device."""
        from .. import events as EV
        from .. import obs as _obs

        if EV.enabled():
            EV.emit("transfer", direction="h2d", bytes=nbytes,
                    site="mesh_stage", shard=s)
        if _obs.enabled():
            _obs.inc("tpu_mesh_staged_rows", rows, device=str(s))
            _obs.inc("tpu_transfer_bytes", nbytes, direction="h2d")

    def _stage_child(self, child: TpuExec) -> StagedChild:
        """Stage ``child`` onto the mesh: absorb the fixed-width chain,
        then either the child's own planes (a sharded scan stages them
        with no host gather; a cached relation hands over the planes it
        keeps on the devices) or the generic host-gather staging. The
        span ``<Exec>.stage`` carries what this hand-over sent to the
        devices (``h2d_bytes``: 0 from resident planes), where the planes
        came from (``source``) and how the rows lie over the shards
        (``shards``, ``shard_rows_max``, ``shard_rows_sum``)."""
        with self.section("stage") as span:
            staged = self._stage_child_planes(child)
            if span.on:
                rows = [int(c) for c in staged.counts]
                span.set(h2d_bytes=int(staged.h2d_bytes),
                         source=staged.source, shards=len(rows),
                         shard_rows_max=max(rows, default=0),
                         shard_rows_sum=sum(rows))
        self.metric("h2dBytes", "bytes").add(int(staged.h2d_bytes))
        return staged

    def _stage_child_planes(self, child: TpuExec) -> StagedChild:
        base, steps = self._absorb_chain(child)
        fast = getattr(base, "stage_mesh_planes", None)
        if fast is not None:
            staged = fast(self.mesh, self.n_shards, self.conf,
                          on_shard=self._on_shard_staged)
            if staged is not None:
                return StagedChild(
                    list(staged.cols), staged.counts, staged.cap,
                    staged.layout, staged.smls, steps,
                    staged.staged_bytes, source=staged.source,
                    h2d_bytes=staged.uploaded_bytes)
        cols, counts, cap, layout, smls, staged_bytes = \
            self._stage_host(base)
        return StagedChild(cols, counts, cap, layout, smls, steps,
                           staged_bytes, source="host",
                           h2d_bytes=sum(staged_bytes))

    def _stage_host(self, child: TpuExec):
        """Materialize every child partition and lay rows onto the mesh:
        returns (flat global arrays, per-shard counts, per-shard cap,
        layout, str_max_lens). Child partition p maps to shard p % n.

        layout[i] is ("f",) for a fixed column or ("s", char_cap) for a
        string column (offsets/chars/validity planes); str_max_lens[i] is
        0 for fixed columns and the bucketed max byte length for string
        columns (a STATIC bound the sort / hash kernels need, computed
        host-side here — staging already touches every byte)."""
        schema = child.output_schema
        per_shard: List[List[ColumnarBatch]] = [[] for _ in range(self.n_shards)]
        for p in range(child.num_partitions):
            for b in child.execute_partition(p):
                per_shard[p % self.n_shards].append(b)
        counts = np.zeros(self.n_shards, np.int32)
        rows_per_shard = [
            sum(int(b.num_rows) for b in bs) for bs in per_shard
        ]
        cap = bucket_rows(max(max(rows_per_shard), 1),
                          self.conf.shape_bucket_min)
        fields = schema.fields
        ncols = len(fields)
        is_str = [T.is_string(f.dataType) for f in fields]
        # gather host views once (dict-encoded strings materialize: the
        # mesh planes splice raw offset/chars byte pools across shards)
        from .base import materialized_batch

        host: List[List[tuple]] = [[] for _ in range(self.n_shards)]
        for s, bs in enumerate(per_shard):
            for b in bs:
                b = materialized_batch(b)
                n = int(b.num_rows)
                row = []
                for c in b.columns:
                    if c.is_string:
                        row.append((
                            _np_of(c.offsets), _np_of(c.chars),
                            _np_of(c.validity), n))
                    else:
                        row.append((_np_of(c.data), _np_of(c.validity), n))
                host[s].append(row)
            counts[s] = sum(int(b.num_rows) for b in bs)
        # per string column: per-shard byte totals -> common char cap + sml
        layout: List[tuple] = []
        smls: List[int] = []
        for j in range(ncols):
            if not is_str[j]:
                layout.append(("f",))
                smls.append(0)
                continue
            max_bytes = 1
            max_len = 1
            for s in range(self.n_shards):
                tot = 0
                for row in host[s]:
                    offs, _, _, n = row[j]
                    tot += int(offs[n])
                    if n:
                        max_len = max(
                            max_len, int((offs[1:n + 1] - offs[:n]).max()))
                max_bytes = max(max_bytes, tot)
            ccap = bucket_rows(max_bytes, 128)
            layout.append(("s", ccap))
            smls.append(max(4, bucket_rows(max_len, 4)))
        # build global planes
        planes: List[np.ndarray] = []
        for j in range(ncols):
            if layout[j][0] == "f":
                d = np.zeros((self.n_shards, cap), fields[j].dataType.to_numpy())
                v = np.zeros((self.n_shards, cap), bool)
                for s in range(self.n_shards):
                    pos = 0
                    for row in host[s]:
                        data, valid, n = row[j]
                        d[s, pos:pos + n] = data[:n]
                        v[s, pos:pos + n] = valid[:n]
                        pos += n
                planes.extend([d, v])
            else:
                ccap = layout[j][1]
                o = np.zeros((self.n_shards, cap + 1), np.int32)
                ch = np.zeros((self.n_shards, ccap), np.uint8)
                v = np.zeros((self.n_shards, cap), bool)
                for s in range(self.n_shards):
                    pos = 0
                    bpos = 0
                    for row in host[s]:
                        offs, chars, valid, n = row[j]
                        nb = int(offs[n])
                        o[s, pos + 1: pos + n + 1] = bpos + offs[1: n + 1]
                        ch[s, bpos: bpos + nb] = chars[:nb]
                        v[s, pos:pos + n] = valid[:n]
                        pos += n
                        bpos += nb
                    o[s, pos + 1:] = bpos
                planes.extend([o, ch, v])
        sh = row_sharding(self.mesh)
        out = [jax.device_put(a.reshape(-1), sh) for a in planes]
        # host-staged planes are uniform by construction: every shard's
        # slice is the same 1/n_shards of each global plane
        per_shard_bytes = sum(a.nbytes for a in planes) // self.n_shards
        staged_bytes = (per_shard_bytes,) * self.n_shards
        for s, r in enumerate(rows_per_shard):
            # per-chip staging lane (a skewed shard shows up immediately)
            self._on_shard_staged(s, r, staged_bytes[s], 0.0)
        return out, counts, cap, tuple(layout), tuple(smls), staged_bytes

    @staticmethod
    def _cols_of_flat(colflat: Sequence[jax.Array], layout) -> List:
        """Per-shard flat planes -> ColV/StrV column list (inside
        shard_map: a string column is offsets/chars/validity planes)."""
        from ..expr.eval import StrV

        cols: List = []
        gi = 0
        for lay in layout:
            if lay[0] == "f":
                cols.append(ColV(colflat[gi], colflat[gi + 1]))
                gi += 2
            else:
                cols.append(
                    StrV(colflat[gi], colflat[gi + 1], colflat[gi + 2]))
                gi += 3
        return cols

    @staticmethod
    def _flatten_vals(outs) -> Tuple[List[jax.Array], Tuple[tuple, ...]]:
        """Column values -> flat planes + an output layout for _emit."""
        from ..expr.eval import StrV

        flat: List[jax.Array] = []
        layout: List[tuple] = []
        for o in outs:
            if isinstance(o, StrV):
                flat.extend([o.offsets, o.chars, o.validity])
                layout.append(("s",))
            else:
                flat.extend([o.data, o.validity])
                layout.append(("f",))
        return flat, tuple(layout)

    def _emit(self, schema: StructType, global_cols: Sequence[jax.Array],
              counts: np.ndarray, cap: int,
              layout=None) -> List[Optional[ColumnarBatch]]:
        """Split flat global outputs back into per-shard batches. Shapes
        per shard derive from each plane's global size / n_shards."""
        if layout is None:
            layout = tuple(
                ("s",) if T.is_string(f.dataType) else ("f",)
                for f in schema.fields)
        outs: List[Optional[ColumnarBatch]] = []
        for s in range(self.n_shards):
            n = int(counts[s])
            cols = []
            gi = 0
            for f, lay in zip(schema.fields, layout):
                if lay[0] == "f":
                    d, v = global_cols[gi], global_cols[gi + 1]
                    gi += 2
                    per = d.shape[0] // self.n_shards
                    cols.append(DeviceColumn(
                        f.dataType, n, d[s * per:(s + 1) * per],
                        v[s * per:(s + 1) * per]))
                else:
                    o, ch, v = (global_cols[gi], global_cols[gi + 1],
                                global_cols[gi + 2])
                    gi += 3
                    po = o.shape[0] // self.n_shards
                    pc = ch.shape[0] // self.n_shards
                    pv = v.shape[0] // self.n_shards
                    cols.append(DeviceColumn(
                        f.dataType, n, None, v[s * pv:(s + 1) * pv],
                        offsets=o[s * po:(s + 1) * po],
                        chars=ch[s * pc:(s + 1) * pc]))
            outs.append(ColumnarBatch(cols, schema, n))
        return outs

    def forecast_mesh_staging(self, child: TpuExec) -> Optional[dict]:
        """The plananalysis per-shard forecast for staging ``child``:
        cap / per-shard rows / staged bytes, computed with the SAME
        helpers the runtime staging paths use (io/mesh_stage) over the
        same chain absorption and item→shard placement — so forecast and
        actual can only diverge through a code change both sides see.
        None when the source's row counts aren't statically known."""
        from ..io import mesh_stage as MS

        base, steps = self._absorb_chain(child)
        items = None
        fn = getattr(base, "mesh_stage_items", None)
        if fn is not None:
            items = fn()
        source = (getattr(base, "mesh_stage_source", "sharded_scan")
                  if items is not None else "host")
        if items is None:
            pr = getattr(base, "partition_rows", None)
            if pr is None:
                return None
            items = pr()
            if items is None:
                return None
        fields = base.output_schema.fields
        out = MS.forecast_staging(
            items, self.n_shards, self.conf.shape_bucket_min, fields)
        out.update({
            "source": source,
            "n_shards": self.n_shards,
            "absorbed_steps": [s.node_name for s in steps],
            "columns": [
                (f.name, f.dataType.simpleString) for f in fields
            ],
        })
        return out

    def _record_staging(self, staged: StagedChild, which: str = "") -> None:
        key = f"staging{('_' + which) if which else ''}"
        self.mesh_actuals[key] = {
            "cap": staged.cap,
            "per_shard_rows": [int(c) for c in staged.counts],
            "staged_bytes": list(staged.staged_bytes),
            "source": staged.source,
        }

    def _record_run(self, outs, dispatch_ns: int) -> None:
        """Per-chip completion lanes: block on each shard's output buffers
        in shard order and emit one device-lane op_span per chip (track
        '<op> [chip k]' in Perfetto). Polling is sequential, so each value
        is an UPPER bound on that chip's completion — exact per-chip
        device occupancy needs the device profiler; these lanes show skew
        and make all n chips visible on the timeline."""
        import time as _time

        from .. import events as EV
        from .. import obs as _obs

        per_chip: List[int] = []
        for s in range(self.n_shards):
            for a in outs:
                shards = getattr(a, "addressable_shards", None)
                if shards is not None and s < len(shards):
                    jax.block_until_ready(shards[s].data)
            per_chip.append(_time.perf_counter_ns() - dispatch_ns)
        self.mesh_actuals["per_chip_ns"] = per_chip
        if EV.enabled():
            for s, dur in enumerate(per_chip):
                EV.emit("op_span", op=self.node_name, section="spmd",
                        start=dispatch_ns, dur=dur, lane="device", shard=s)
        if _obs.enabled():
            for s, dur in enumerate(per_chip):
                _obs.inc("tpu_mesh_shard_seconds", dur / 1e9,
                         device=str(s))

    def _note_program_miss(self) -> None:
        self.mesh_actuals["programs"] = (
            self.mesh_actuals.get("programs", 0) + 1)

    # -- forecast hooks (plugin/plananalysis.forecast_mesh) ----------------
    mesh_site = "mesh"

    def mesh_program_bound(self, cap: int) -> int:
        """Upper bound on compiled SPMD programs for one materialization
        (1 + capacity-overflow retries). Subclasses with retry loops
        override with the same doubling arithmetic the loop runs."""
        return 1

    @staticmethod
    def _doubling_bound(start: int, cap: int) -> int:
        """Programs a double-until-cap retry loop can compile: the first
        attempt plus one per doubling until the cap disables slicing."""
        n, g = 1, start
        while 0 < g < cap:
            g = min(g * 2, cap)
            n += 1
        return n

    def _materialize(self) -> None:
        raise NotImplementedError

    def execute_partition(self, index: int) -> Iterator[ColumnarBatch]:
        if self._outputs is None:
            with self.op_timed():
                self._materialize()
        b = self._outputs[index]
        if b is not None and b.num_rows > 0:
            yield self.record_batch(b)

    def describe(self):
        return f"{self.node_name}(mesh={self.n_shards})"


_PROGRAM_CACHE: dict = {}

#: the largest per-shard capacity (a power of two) that the mesh aggregate
#: updates in one piece. A shard with more slots is updated chunk by chunk
#: inside the SPMD program (one loop over slices of the planes, a slice's
#: partial cut to ``shuffle.mesh.aggExchangeCapacity`` groups, the slices'
#: partial rows crossing the exchange unmerged), so the update's
#: temporaries follow the chunk and not the shard: at 2^27 slots a shard
#: (TPC-DS SF100 store_sales over four chips) the v5e compiler refuses the
#: one-piece update (21 GB of temporaries beside 3.2 GB of planes) and
#: takes 16 chunks of 2^23, the capacity the first four-chip runs had
#: (tests/test_tpu_compile.py asks). A constant, not a conf: one value is
#: in use, and tests shrink it by patching this name.
AGG_UPDATE_CHUNK_ROWS = 1 << 23


def _cached_program(key, builder, site: Optional[str] = None,
                    on_miss=None):
    from .base import cached_pipeline

    def build():
        if on_miss is not None:
            on_miss()
        return builder()

    return cached_pipeline(_PROGRAM_CACHE, key, site, build,
                           max_entries=256)


class TpuMeshAggregateExec(_MeshStage):
    """partial-agg -> hash all_to_all -> final merge -> result projection,
    one SPMD program (reference plan: GpuHashAggregateExec(PARTIAL) ->
    GpuShuffleExchangeExec -> GpuHashAggregateExec(FINAL)).

    The buffer layout / update-merge op split is borrowed from a PARTIAL
    TpuHashAggregateExec (never executed — only its bound metadata)."""

    def __init__(self, conf, group_exprs, agg_exprs, child):
        _MeshStage.__init__(self, conf, [child])
        self.group_exprs = list(group_exprs)
        self.agg_exprs = list(agg_exprs)
        plan = XA.TpuHashAggregateExec(
            conf, group_exprs, agg_exprs, child, mode=A.PARTIAL)
        self._key_fields = plan._key_fields
        self._bound_keys = plan._bound_keys
        self._bound_funcs = plan._bound_funcs
        self._buf_fields = plan._buf_fields
        self._buf_slices = plan._buf_slices
        self._update_exprs = plan._update_exprs
        self._update_ops = plan._update_ops
        self._merge_ops = plan._merge_ops
        fields = list(self._key_fields)
        for ae, f in zip(self.agg_exprs, self._bound_funcs):
            fields.append(StructField(ae.resolved_name(), f.dtype, True))
        self._schema = StructType(tuple(fields))

    def _key_dtypes(self):
        return tuple(f.dataType for f in self._key_fields)

    @property
    def output_schema(self):
        return self._schema

    def describe(self):
        keys = ", ".join(str(k) for k in self.group_exprs)
        return f"TpuMeshAggregateExec(mesh={self.n_shards}, keys=[{keys}])"

    mesh_site = "mesh_agg"

    def mesh_program_bound(self, cap: int) -> int:
        from ..conf import MESH_AGG_EXCHANGE_CAP

        g = min(bucket_rows(self.conf.get(MESH_AGG_EXCHANGE_CAP),
                            self.conf.shape_bucket_min), cap)
        return self._doubling_bound(g, cap)

    def _materialize(self) -> None:
        import time as _time

        child = self.children[0]
        staged = self._stage_child(child)
        self._record_staging(staged)
        global_cols, counts, cap = staged.cols, staged.counts, staged.cap
        layout, smls, steps = staged.layout, staged.smls, staged.steps
        nk = len(self._key_fields)
        key_dtypes = list(self._key_dtypes())
        bound_keys = tuple(self._bound_keys)
        update_exprs = tuple(self._update_exprs)
        update_ops = tuple(self._update_ops)
        merge_ops = tuple(self._merge_ops)
        buf_fields = tuple(self._buf_fields)
        bound_funcs = tuple(self._bound_funcs)
        buf_slices = tuple(self._buf_slices)
        n_shards = self.n_shards
        mesh = self.mesh
        # static byte bound per STRING group key: the referenced source
        # column's staged max (planner gates string keys to direct refs;
        # absorbed chains are fixed-width so smls stay aligned)
        key_smls = tuple(
            smls[b.ordinal]
            for b in bound_keys
            if isinstance(b, E.BoundReference) and T.is_string(b.dtype)
            and not steps and b.ordinal < len(smls)
        )
        # post-PARTIAL exchange capacity: slice the partial output to the
        # group cardinality before it crosses ICI (overflow retries with
        # the cap doubled; string keys disable slicing inside dist_groupby)
        from ..conf import MESH_AGG_EXCHANGE_CAP

        gcap = min(
            bucket_rows(self.conf.get(MESH_AGG_EXCHANGE_CAP),
                        self.conf.shape_bucket_min),
            cap)
        if key_smls or any(lay[0] != "f" for lay in layout):
            gcap = 0  # strings cross at full capacity (no slicing)
        # a shard larger than the update chunk is updated chunk by chunk
        # (the update's temporaries follow the chunk, not the shard)
        chunk = AGG_UPDATE_CHUNK_ROWS

        def update_inputs(cols, live, rows):
            """One piece of a shard through the absorbed chain, to the
            update's keys and values. The one-chip programs' scope words
            (exec/base): ``fused_chain``, then ``agg_update``."""
            with jax.named_scope("fused_chain"):
                cols, live = self._apply_steps(steps, cols, live, rows)
            with jax.named_scope("agg_update"):
                keys = [lower(b, cols, rows) for b in bound_keys]
                vals = [
                    None if e is None else lower(e, cols, rows)
                    for e in update_exprs
                ]
            return keys, vals, live

        def chunked_partials(colflat, n, group_cap, pieces, reports):
            """The PARTIAL aggregate of a large shard, a chunk at a time:
            one loop over ``pieces`` slices of the planes, each slice's
            groups compacted and cut to ``group_cap`` rows; a slice past
            the shard's ``n`` rows holds no row and skips its update.
            Returns the partial rows of all slices (keys, buffers, live
            mask) and whether every slice's groups fitted;
            ``reports["update"]`` says how a slice's aggregate lowers."""
            report: dict = {}

            def update(at, report):
                # a slice of the resident planes, not a reshaped copy
                cols = self._cols_of_flat(
                    [jax.lax.dynamic_slice(p, (at * chunk,), (chunk,))
                     for p in colflat], layout)
                live = at * chunk + jnp.arange(
                    chunk, dtype=jnp.int32) < n
                keys, vals, live = update_inputs(cols, live, chunk)
                with jax.named_scope("agg_update"):
                    pk, pa, pn = groupby_ops.groupby_agg(
                        keys, key_dtypes, vals, list(update_ops), live,
                        (), report=report)
                out = []
                for c in list(pk) + list(pa):
                    out.extend([c.data[:group_cap],
                                c.validity[:group_cap]])
                # the slice's flag leaves the loop as a result of its own
                return (tuple(out), jnp.minimum(pn, group_cap),
                        pn <= group_cap,
                        report.pop("float_detour", jnp.bool_(False)))

            def empty(at):
                # what a slice with no live row comes to: no group, no
                # detour, and it fitted
                planes, count, _, detour = jax.tree.map(
                    lambda s: jnp.zeros(s.shape, s.dtype), partial_shapes)
                return planes, count, jnp.bool_(True), detour

            partial_shapes = jax.eval_shape(
                lambda at: update(at, {}), jnp.int32(0))

            def one(at):
                # a real branch (the loop keeps it one): a slice of
                # padding costs a compare, not an update
                return jax.lax.cond(
                    at * chunk < n, lambda at: update(at, report), empty,
                    at)

            planes, counts, fits, detours = jax.lax.map(
                one, jnp.arange(pieces, dtype=jnp.int32))
            if report:
                reports["update"] = dict(
                    report, float_detour=jnp.any(detours))
            rows = [ColV(planes[2 * i].reshape(-1),
                         planes[2 * i + 1].reshape(-1))
                    for i in range(len(planes) // 2)]
            live = (jnp.arange(group_cap, dtype=jnp.int32)[None, :]
                    < counts[:, None]).reshape(-1)
            return rows[:nk], rows[nk:], live, jnp.all(fits)

        while True:
            out_layouts: dict = {}
            group_cap = 0 if gcap >= cap else gcap
            pieces = cap // chunk if 0 < group_cap < chunk < cap else 1
            # chunks that hold a row, over all shards: the others skip
            # their update inside the program
            pieces_live = pieces if pieces == 1 else sum(
                -(-int(c) // chunk) for c in counts)
            self.mesh_actuals["update_chunks"] = pieces
            self.mesh_actuals["update_chunks_live"] = pieces_live

            def build(group_cap=group_cap, out_layouts=out_layouts,
                      pieces=pieces):
                by_chunk = pieces > 1

                @program("mesh_agg")
                def shard_fn(*flat):
                    *colflat, cnt = flat
                    n = cnt[0]
                    fitted = None
                    reports: dict = {}
                    if by_chunk:
                        # the chunks' partial rows cross as they are:
                        # dist_groupby's FINAL half merges them
                        keys, vals, live, fitted = chunked_partials(
                            colflat, n, group_cap, pieces, reports)
                    else:
                        cols = self._cols_of_flat(colflat, layout)
                        live = jnp.arange(cap, dtype=jnp.int32) < n
                        keys, vals, live = update_inputs(cols, live, cap)
                    rkeys, raggs, rn, ok = D.dist_groupby(
                        keys, key_dtypes, vals, list(update_ops),
                        list(merge_ops), live, AXIS, n_shards,
                        str_max_lens=key_smls,
                        group_cap=0 if by_chunk else group_cap,
                        partials=by_chunk, reports=reports)
                    if fitted is not None:
                        ok = ok & (jax.lax.psum(
                            fitted.astype(jnp.int32), AXIS) == n_shards)
                    # how the two halves' first hash tier lowers (a half
                    # that bypasses the hash tiers reports nothing)
                    halves = [r for r in reports.values() if r]
                    detour = jnp.bool_(False)
                    for r in halves:
                        detour = detour | r["float_detour"]
                    if halves:
                        out_layouts["lowering"] = dict(
                            float_sums_fixed=min(
                                r["float_sums_fixed"] for r in halves),
                            row_scatters=sum(
                                r["row_scatters"] for r in halves))
                    # result projection over [keys..., buffers...] per shard
                    allv = list(rkeys) + list(raggs)
                    rcap = allv[0].validity.shape[0] if allv else 1
                    exprs: List[E.Expression] = [
                        E.BoundReference(i, f.dataType, f.nullable)
                        for i, f in enumerate(self._key_fields)
                    ]
                    for f, (s, e) in zip(bound_funcs, buf_slices):
                        refs = tuple(
                            E.BoundReference(
                                nk + j, buf_fields[j].dataType, True)
                            for j in range(s, e)
                        )
                        exprs.append(f.evaluate(refs))
                    with jax.named_scope("project"):
                        outs = [lower(x, allv, rcap) for x in exprs]
                    flat_out, out_lay = self._flatten_vals(outs)
                    out_layouts["lay"] = out_lay
                    flat_out.append(rn.reshape(1))
                    # the shard's flags: its exchange fitted; a float
                    # sum's detour ran (ops/bucket_reduce)
                    flat_out.append(jnp.stack([ok, detour]))
                    return tuple(flat_out)

                nin = len(global_cols)
                fn = shard_map(
                    shard_fn, mesh=mesh,
                    in_specs=tuple([P(AXIS)] * nin + [P(AXIS)]),
                    out_specs=P(AXIS),
                )
                return jax.jit(fn, **mesh_jit_kwargs()), out_layouts

            sig = tuple((str(a.dtype), a.shape) for a in global_cols)
            fn, out_layouts = _cached_program(
                ("agg", self.fusion_sig(), staged.steps_sig(), sig, cap,
                 n_shards, key_smls, group_cap, pieces),
                build, site="mesh_agg", on_miss=self._note_program_miss)
            cnt_in = jax.device_put(
                np.asarray(counts, np.int32), row_sharding(mesh))
            # rows of one exchanged block: the sliced partial, every
            # chunk's sliced partial, or the whole shard
            xbytes = self.exchange_bytes(pieces * group_cap or cap)
            t0 = _time.perf_counter_ns()
            with self.section("spmd", exchange_bytes=xbytes,
                              exchange_cap=group_cap or cap,
                              update_chunks=pieces,
                              update_chunks_live=pieces_live) as span:
                res = fn(*global_cols, cnt_in)
                # known once the program is traced: how its aggregates
                # lower (float_sums_fixed, row_scatters)
                lowering = out_layouts.get("lowering", {})
                span.set(**lowering)
            self.mesh_actuals.update(lowering)
            self.metric("exchangeBytes", "bytes").add(xbytes)
            *out_cols, out_counts, flags = res
            if group_cap:
                with self.section("overflow_pull") as span:
                    oks, detours = _np_of(flags).reshape(n_shards, 2).T
                    fits = bool(np.all(oks))
                    detoured = int(np.sum(detours))
                    span.set(float_detour=detoured)
                self.mesh_actuals["float_detour"] = detoured
            if group_cap == 0 or fits:
                self._record_run(list(out_cols) + [out_counts], t0)
                self.mesh_actuals["exchange_cap"] = group_cap or cap
                self.mesh_actuals["exchange_bytes"] = xbytes
                break
            # a shard had more groups than the exchange cap: double it
            # (the aggregate analog of the join's output-capacity retry)
            gcap = min(gcap * 2, cap)
        out_lay = out_layouts.get("lay") or tuple(
            ("s",) if T.is_string(f.dataType) else ("f",)
            for f in self._schema.fields)
        with self.section("emit"):
            self._outputs = self._emit(
                self._schema, list(out_cols), _np_of(out_counts), 0,
                layout=out_lay)

    def exchange_bytes(self, xrows: int) -> int:
        """Bytes one run of the program hands to ``all_to_all`` over all
        shards: every shard sends ``n_shards`` blocks of ``xrows`` rows of
        the partial's columns (keys and buffers, a data and a validity
        plane each; a string key's byte plane is not counted) and its
        block counts. From the shapes, so the same on every backend."""
        row = sum(np.dtype(f.dataType.to_numpy()).itemsize + 1
                  for f in list(self._key_fields) + list(self._buf_fields)
                  if T.is_fixed_width(f.dataType))
        n = self.n_shards
        return n * (n * xrows * row + n * 4)

    def fusion_sig(self):
        return (
            tuple(self._bound_keys), tuple(self._update_exprs),
            tuple(self._update_ops), tuple(self._merge_ops),
        )


class TpuMeshSortExec(_MeshStage):
    """local sort -> sampled range all_to_all -> merge sort, one SPMD
    program (reference plan: GpuRangePartitioning exchange + GpuSortExec);
    output partition i globally precedes partition i+1."""

    def __init__(self, conf, sort_ordinals: Sequence[int],
                 orders: Sequence[Tuple[bool, bool]], child: TpuExec):
        _MeshStage.__init__(self, conf, [child])
        self.key_indices = list(sort_ordinals)
        self.orders = [SortOrder(a, nf) for a, nf in orders]
        self._schema = child.output_schema

    @property
    def output_schema(self):
        return self._schema

    mesh_site = "mesh_sort"

    def mesh_program_bound(self, cap: int) -> int:
        from ..conf import MESH_EXCHANGE_BUCKET_FACTOR

        factor = self.conf.get(MESH_EXCHANGE_BUCKET_FACTOR)
        if factor <= 0 or self.n_shards <= 1:
            return 1
        b = min(bucket_rows(max(int(cap * factor / self.n_shards), 1),
                            self.conf.shape_bucket_min), cap)
        return self._doubling_bound(b, cap)

    def _materialize(self) -> None:
        import time as _time

        child = self.children[0]
        staged = self._stage_child(child)
        self._record_staging(staged)
        global_cols, counts, cap = staged.cols, staged.counts, staged.cap
        layout, smls, steps = staged.layout, staged.smls, staged.steps
        key_dtypes = [
            self._schema.fields[i].dataType for i in self.key_indices
        ]
        n_shards, mesh = self.n_shards, self.mesh
        key_ix, orders = list(self.key_indices), list(self.orders)
        key_smls = tuple(
            smls[i] for i in key_ix
            if T.is_string(self._schema.fields[i].dataType) and not steps
            and i < len(smls))
        # exchange granule: the sampled range bounds spread rows roughly
        # evenly, so ~factor x fair share per target keeps the receive
        # surface O(cap) instead of O(n_shards x cap); skew overflows the
        # block and retries with the granule doubled
        from ..conf import MESH_EXCHANGE_BUCKET_FACTOR

        factor = self.conf.get(MESH_EXCHANGE_BUCKET_FACTOR)
        bcap = 0
        if factor > 0 and n_shards > 1 and all(
                lay[0] == "f" for lay in layout):
            bcap = min(
                bucket_rows(max(int(cap * factor / n_shards), 1),
                            self.conf.shape_bucket_min),
                cap)

        while True:
            out_layouts: dict = {}
            bucket_cap = 0 if bcap >= cap else bcap

            def build(bucket_cap=bucket_cap, out_layouts=out_layouts):
                @program("mesh_sort")
                def shard_fn(*flat):
                    *colflat, cnt = flat
                    cols = self._cols_of_flat(colflat, layout)
                    live = jnp.arange(cap, dtype=jnp.int32) < cnt[0]
                    cols, live = self._apply_steps(steps, cols, live, cap)
                    out, rn, ok = D.dist_sort(
                        cols, key_ix, key_dtypes, orders, live, AXIS,
                        n_shards, str_max_lens=key_smls,
                        bucket_cap=bucket_cap)
                    flat_out, out_lay = self._flatten_vals(out)
                    out_layouts["lay"] = out_lay
                    flat_out.append(rn.reshape(1))
                    flat_out.append(ok.reshape(1))
                    return tuple(flat_out)

                nin = len(global_cols)
                return jax.jit(shard_map(
                    shard_fn, mesh=mesh,
                    in_specs=tuple([P(AXIS)] * (nin + 1)),
                    out_specs=P(AXIS)), **mesh_jit_kwargs()), out_layouts

            sig = tuple((str(a.dtype), a.shape) for a in global_cols)
            fn, out_layouts = _cached_program(
                ("sort", tuple(key_ix),
                 tuple((o.ascending, o.nulls_first) for o in orders),
                 staged.steps_sig(), sig, n_shards, key_smls, bucket_cap),
                build, site="mesh_sort", on_miss=self._note_program_miss)
            cnt_in = jax.device_put(
                np.asarray(counts, np.int32), row_sharding(mesh))
            t0 = _time.perf_counter_ns()
            res = fn(*global_cols, cnt_in)
            *out_cols, out_counts, oks = res
            if bucket_cap == 0 or bool(np.all(_np_of(oks))):
                self._record_run(list(out_cols) + [out_counts], t0)
                self.mesh_actuals["exchange_cap"] = bucket_cap or cap
                break
            bcap = min(bcap * 2, cap)
        out_lay = out_layouts.get("lay") or tuple(
            ("s",) if T.is_string(f.dataType) else ("f",)
            for f in self._schema.fields)
        self._outputs = self._emit(
            self._schema, list(out_cols), _np_of(out_counts), 0,
            layout=out_lay)


class TpuMeshWindowExec(_MeshStage):
    """hash all_to_all on the PARTITION keys -> per-shard window, one SPMD
    program (reference plan: GpuShuffleExchangeExec(HashPartitioning)
    feeding GpuWindowExec). Window partitions are independent, so placing
    every row of a partition key on one shard preserves exact semantics;
    the per-shard body is the SAME traceable window kernel the
    single-device exec jits (exec/window.TpuWindowExec.window_fn — one
    radix sort + O(n) scans). Fixed-width columns with direct
    partition-key references only (the planner gates)."""

    def __init__(self, conf, window_exprs, child):
        _MeshStage.__init__(self, conf, [child])
        from .window import TpuWindowExec

        self._plan = TpuWindowExec(conf, window_exprs, child)
        self._schema = self._plan.output_schema
        self._part_ords = [b.ordinal for b in self._plan._part_keys]
        self._part_dtypes = [b.dtype for b in self._plan._part_keys]

    @property
    def output_schema(self):
        return self._schema

    def describe(self):
        names = ", ".join(
            we.resolved_name() for we in self._plan.window_exprs)
        return f"TpuMeshWindowExec(mesh={self.n_shards}, [{names}])"

    mesh_site = "mesh_window"

    def mesh_program_bound(self, cap: int) -> int:
        from ..conf import MESH_EXCHANGE_BUCKET_FACTOR

        factor = self.conf.get(MESH_EXCHANGE_BUCKET_FACTOR)
        if factor <= 0 or self.n_shards <= 1:
            return 1
        b = min(bucket_rows(max(int(cap * factor / self.n_shards), 1),
                            self.conf.shape_bucket_min), cap)
        return self._doubling_bound(b, cap)

    def _materialize(self) -> None:
        import time as _time

        from ..ops import hashing
        from ..parallel.collective import all_to_all_exchange

        child = self.children[0]
        staged = self._stage_child(child)
        self._record_staging(staged)
        global_cols, counts, cap = staged.cols, staged.counts, staged.cap
        layout, steps = staged.layout, staged.steps
        n_shards, mesh = self.n_shards, self.mesh
        part_ords = list(self._part_ords)
        part_dtypes = list(self._part_dtypes)
        window_fn = self._plan.window_fn
        from ..conf import MESH_EXCHANGE_BUCKET_FACTOR

        factor = self.conf.get(MESH_EXCHANGE_BUCKET_FACTOR)
        bcap = 0
        if factor > 0 and n_shards > 1 and all(
                lay[0] == "f" for lay in layout):
            bcap = min(
                bucket_rows(max(int(cap * factor / n_shards), 1),
                            self.conf.shape_bucket_min),
                cap)

        while True:
            out_layouts: dict = {}
            bucket_cap = 0 if bcap >= cap else bcap

            def build(bucket_cap=bucket_cap, out_layouts=out_layouts):
                @program("mesh_window")
                def shard_fn(*flat):
                    *colflat, cnt = flat
                    cols = self._cols_of_flat(colflat, layout)
                    live = jnp.arange(cap, dtype=jnp.int32) < cnt[0]
                    cols, live = self._apply_steps(steps, cols, live, cap)
                    kc = [cols[i] for i in part_ords]
                    h = hashing.murmur3(kc, part_dtypes)
                    pids = hashing.partition_ids(h, n_shards)
                    recvd, rn, ok = all_to_all_exchange(
                        cols, pids, live, AXIS, n_shards,
                        bucket_cap=bucket_cap)
                    rcap = recvd[0].validity.shape[0]
                    out = window_fn(rcap, ())(recvd, rn)
                    flat_out, out_lay = self._flatten_vals(out)
                    out_layouts["lay"] = out_lay
                    flat_out.append(rn.reshape(1))
                    flat_out.append(ok.reshape(1))
                    return tuple(flat_out)

                nin = len(global_cols)
                return jax.jit(shard_map(
                    shard_fn, mesh=mesh,
                    in_specs=tuple([P(AXIS)] * (nin + 1)),
                    out_specs=P(AXIS)), **mesh_jit_kwargs()), out_layouts

            sig = tuple((str(a.dtype), a.shape) for a in global_cols)
            fn, out_layouts = _cached_program(
                ("window", tuple(part_ords),
                 repr(tuple(self._plan._bound_funcs)),
                 repr(tuple(self._plan._order_keys)),
                 tuple((o.ascending, o.nulls_first)
                       for o in self._plan._orders),
                 staged.steps_sig(), sig, n_shards, bucket_cap),
                build, site="mesh_window", on_miss=self._note_program_miss)
            cnt_in = jax.device_put(
                np.asarray(counts, np.int32), row_sharding(mesh))
            t0 = _time.perf_counter_ns()
            res = fn(*global_cols, cnt_in)
            *out_cols, out_counts, oks = res
            if bucket_cap == 0 or bool(np.all(_np_of(oks))):
                self._record_run(list(out_cols) + [out_counts], t0)
                self.mesh_actuals["exchange_cap"] = bucket_cap or cap
                break
            bcap = min(bcap * 2, cap)
        out_lay = out_layouts.get("lay") or tuple(
            ("s",) if T.is_string(f.dataType) else ("f",)
            for f in self._schema.fields)
        self._outputs = self._emit(
            self._schema, list(out_cols), _np_of(out_counts), 0,
            layout=out_lay)


class TpuMeshHashJoinExec(_MeshStage):
    """hash all_to_all both sides -> local join, one SPMD program
    (reference plan: two GpuShuffleExchangeExecs feeding
    GpuShuffledHashJoinExec). Inner equi-joins, no residual condition."""

    def __init__(self, conf, left: TpuExec, right: TpuExec,
                 left_ordinals: Sequence[int], right_ordinals: Sequence[int]):
        _MeshStage.__init__(self, conf, [left, right])
        self.left_ix = list(left_ordinals)
        self.right_ix = list(right_ordinals)
        lf = left.output_schema.fields
        rf = right.output_schema.fields
        self._schema = StructType(tuple(lf) + tuple(rf))
        self._key_dtypes = [
            left.output_schema.fields[i].dataType for i in self.left_ix
        ]

    @property
    def output_schema(self):
        return self._schema

    mesh_site = "mesh_join"

    def mesh_program_bound(self, cap: int) -> int:
        return 8  # the output-capacity retry limit of _materialize

    def _materialize(self) -> None:
        import time as _time

        left, right = self.children
        lstaged = self._stage_child(left)
        rstaged = self._stage_child(right)
        self._record_staging(lstaged, "left")
        self._record_staging(rstaged, "right")
        l_cols, l_counts, lcap = lstaged.cols, lstaged.counts, lstaged.cap
        llay, lsml, lsteps = lstaged.layout, lstaged.smls, lstaged.steps
        r_cols, r_counts, rcap = rstaged.cols, rstaged.counts, rstaged.cap
        rlay, rsml, rsteps = rstaged.layout, rstaged.smls, rstaged.steps
        if lsteps or rsteps:
            lsml = tuple(0 for _ in left.output_schema.fields)
            rsml = tuple(0 for _ in right.output_schema.fields)
        n_shards, mesh = self.n_shards, self.mesh
        l_ix, r_ix, kd = list(self.left_ix), list(self.right_ix), list(
            self._key_dtypes)
        lf = left.output_schema.fields
        rf = right.output_schema.fields
        out_cap = bucket_rows(
            max(lcap, rcap) * 2, self.conf.shape_bucket_min)
        # string keys compare via chunk keys: the byte bound must be
        # SHARED by both sides (same word count per key)
        key_smls = tuple(
            max(lsml[li], rsml[ri])
            for li, ri in zip(l_ix, r_ix)
            if T.is_string(lf[li].dataType)
        )
        # per-shard byte pools for string outputs: the post-exchange pool
        # is n_shards x the staged local pool; 1:1 joins fit, fan-out
        # retries double alongside out_cap
        base_ccaps = tuple(
            [lay[1] * n_shards for lay in llay if lay[0] == "s"]
            + [lay[1] * n_shards for lay in rlay if lay[0] == "s"])
        ccap_scale = 1
        # per-side exchange granule (~factor x fair share): hash
        # partitioning spreads keys evenly, so the receive surface stays
        # O(cap); a skewed side overflows and the retry below doubles the
        # granule along with the output capacity
        from ..conf import MESH_EXCHANGE_BUCKET_FACTOR

        factor = self.conf.get(MESH_EXCHANGE_BUCKET_FACTOR)

        def bcap_of(cap_side, lay):
            if factor <= 0 or n_shards <= 1 or any(
                    L[0] != "f" for L in lay):
                return 0
            return min(
                bucket_rows(max(int(cap_side * factor / n_shards), 1),
                            self.conf.shape_bucket_min),
                cap_side)

        l_bcap = bcap_of(lcap, llay)
        r_bcap = bcap_of(rcap, rlay)

        for attempt in range(8):
            xcaps = (0 if l_bcap >= lcap else l_bcap,
                     0 if r_bcap >= rcap else r_bcap)
            out_ccaps = tuple(
                bucket_rows(c * ccap_scale, 128) for c in base_ccaps)

            def build(out_cap=out_cap, out_ccaps=out_ccaps, xcaps=xcaps):
                @program("mesh_join")
                def shard_fn(*flat):
                    nlp = sum(2 if lay[0] == "f" else 3 for lay in llay)
                    lflat = flat[:nlp]
                    rflat = flat[nlp:-2]
                    lcnt, rcnt = flat[-2], flat[-1]
                    lc = self._cols_of_flat(lflat, llay)
                    rc = self._cols_of_flat(rflat, rlay)
                    ln_, rn_ = lcnt[0], rcnt[0]
                    if lsteps:
                        from ..ops.filter_gather import filter_cols

                        live = jnp.arange(lcap, dtype=jnp.int32) < ln_
                        lc, live = self._apply_steps(lsteps, lc, live, lcap)
                        lc, ln_ = filter_cols(lc, live, None)
                    if rsteps:
                        from ..ops.filter_gather import filter_cols

                        live = jnp.arange(rcap, dtype=jnp.int32) < rn_
                        rc, live = self._apply_steps(rsteps, rc, live, rcap)
                        rc, rn_ = filter_cols(rc, live, None)
                    out, cnt, ok = D.dist_hash_join(
                        lc, l_ix, rc, r_ix, kd, ln_, rn_,
                        AXIS, n_shards, out_cap,
                        key_str_max_lens=key_smls,
                        out_char_caps=out_ccaps,
                        exchange_bucket_caps=xcaps)
                    flat_out, out_lay = self._flatten_vals(out)
                    out_layouts["lay"] = out_lay
                    flat_out.append(cnt.reshape(1))
                    flat_out.append(ok.reshape(1))
                    return tuple(flat_out)

                nin = len(l_cols) + len(r_cols) + 2
                return jax.jit(shard_map(
                    shard_fn, mesh=mesh,
                    in_specs=tuple([P(AXIS)] * nin),
                    out_specs=P(AXIS)), **mesh_jit_kwargs()), out_layouts

            out_layouts: dict = {}
            sig = (
                tuple((str(a.dtype), a.shape) for a in l_cols),
                tuple((str(a.dtype), a.shape) for a in r_cols),
            )
            fn, out_layouts = _cached_program(
                ("join", tuple(l_ix), tuple(r_ix),
                 lstaged.steps_sig(), rstaged.steps_sig(), sig, out_cap,
                 n_shards, key_smls, out_ccaps, xcaps),
                build, site="mesh_join", on_miss=self._note_program_miss)
            sh = row_sharding(mesh)
            t0 = _time.perf_counter_ns()
            res = fn(*l_cols, *r_cols,
                     jax.device_put(np.asarray(l_counts, np.int32), sh),
                     jax.device_put(np.asarray(r_counts, np.int32), sh))
            *out_cols, out_counts, oks = res
            if bool(np.all(_np_of(oks))):
                self._record_run(list(out_cols) + [out_counts], t0)
                out_lay = out_layouts.get("lay") or tuple(
                    ("s",) if T.is_string(f.dataType) else ("f",)
                    for f in self._schema.fields)
                self._outputs = self._emit(
                    self._schema, list(out_cols), _np_of(out_counts), 0,
                    layout=out_lay)
                return
            # overflow: double the per-shard output capacity AND the
            # exchange granules and recompile — the ok flag does not say
            # which surface overflowed, so every capacity grows together
            # (the reference's bounce-buffer windowing retries similarly)
            out_cap *= 2
            ccap_scale *= 2
            if l_bcap:
                l_bcap = min(l_bcap * 2, lcap)
            if r_bcap:
                r_bcap = min(r_bcap * 2, rcap)
        raise RuntimeError("mesh join output capacity retry limit exceeded")


# ---------------------------------------------------------------------------
# planner eligibility
# ---------------------------------------------------------------------------
def mesh_mode(conf: RapidsConf) -> str:
    from ..conf import SHUFFLE_MODE

    return conf.get(SHUFFLE_MODE)


def mesh_available(conf: RapidsConf) -> bool:
    mode = mesh_mode(conf)
    if mode == "host":
        return False
    if mode == "ici":
        return True
    from ..parallel.mesh import device_count

    return device_count() > 1


def fixed_width_schema(schema: StructType) -> bool:
    return all(T.is_fixed_width(f.dataType) for f in schema.fields)
