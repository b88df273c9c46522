"""Equi-join execs (hash-join family on TPU).

Reference analog: GpuHashJoin.doJoin (execution/GpuHashJoin.scala:158-263) —
build-side table concat + per-stream-batch cudf join; join types inner/left/
right/full/semi/anti (doJoinLeftRight :265). TPU re-design: the build side
is concatenated and radix-SORTED once (ops/join.py), each probe batch runs a
fused count+expand program, and the only host syncs are the build size and
one match-total per probe batch (cudf syncs output sizes at the same
boundaries).

Right joins run as left joins with the sides swapped and the output columns
re-permuted, like the reference's buildSide handling.
"""
from __future__ import annotations

from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import types as T
from ..columnar import ColumnarBatch
from ..conf import RapidsConf
from ..expr import expressions as E
from ..expr.eval import ColV, StrV, Val, lower
from ..ops import concat as concat_ops
from ..ops import filter_gather
from ..ops import join as join_ops
from ..ops.sort import max_string_len, sort_with_radix_keys, SortOrder
from ..types import StructField, StructType
from ..columnar.column import choose_capacity


class _SpillableBuild:
    """Join build side as catalog-registered spillable buffers: the sorted
    build columns + radix words + liveness round-trip device<->host under
    pressure and re-materialize at probe time (reference:
    SpillableColumnarBatch around the concatenated build table)."""

    def __init__(self, cols, words, live):
        from ..memory import ACTIVE_BATCHING_PRIORITY, SpillableVals
        from ..memory.catalog import SpillableHandle

        # ledger_kind="plan_state": the build side is retained with the
        # exec instance for re-execution — designed to outlive queries,
        # so the leak sentinel must not flag it
        self._cols = SpillableVals(cols, ACTIVE_BATCHING_PRIORITY,
                                   ledger_kind="plan_state")
        aux = {f"w{i}": w for i, w in enumerate(words)}
        aux["live"] = live
        self._aux = SpillableHandle(aux, ACTIVE_BATCHING_PRIORITY,
                                    ledger_kind="plan_state")
        self._nw = len(words)

    def get(self):
        cols = self._cols.get_vals()
        a = self._aux.materialize()
        return cols, [a[f"w{i}"] for i in range(self._nw)], a["live"]
from .base import (
    NUM_OUTPUT_BATCHES,
    TOTAL_TIME,
    TpuExec,
    batch_from_vals,
    batch_signature,
    count_scalar,
    program,
    timed,
    vals_of_batch,
)

_JOIN_TYPES = ("inner", "left", "right", "full", "semi", "anti", "cross")

# ---------------------------------------------------------------------------
# Join strategy chooser (conf sql.join.strategy) — the join twin of
# exec/aggregate.choose_agg_strategy. Reads the SAME conf-declared
# roofline peaks the profiler's roofline report measures against, so a
# calibrated deployment moves the chooser and the report together.
# ---------------------------------------------------------------------------
#: CPU-backend AUTO: below this build capacity the direct-address
#: table's two scatters are cheap and the whole-join fusion into the
#: consumer chain wins; at or above it the CPU scatter dialect's charged
#: byte amplification dominates (BENCH_r10: the join shape's fused
#: direct tables + downstream scatter agg touched 29.8x the layout
#: bound) and the co-sorted RADIX merge takes over
_RADIX_JOIN_CPU_MIN_BUILD = 1 << 16
#: near-serial accelerator random-gather cost per element (the binary
#: search's per-step price; same figure ops/join's docstrings cite)
_GATHER_SEC_PER_ELEM = 15e-9


def _key_word_count(key_dtypes) -> Tuple[int, bool]:
    """(radix key words, fixed-width-only) for the chooser's static cost
    model; strings price at their chunk granularity (~2 words/chunk)."""
    words = 0
    fixed = True
    for dt in key_dtypes:
        if isinstance(dt, (T.StringType, T.BinaryType)):
            fixed = False
            words += 4  # typical 16-byte chunk surface
        else:
            words += 2 if dt.to_numpy().itemsize == 8 else 1
    return words, fixed


def choose_join_strategy(
    conf: RapidsConf,
    build_cap: int,
    key_dtypes,
    join_type: str,
    backend: "Optional[str]" = None,
) -> "Tuple[str, str]":
    """Pick the probe lowering for ONE join plan from its STATIC build
    layout — build capacity bucket, key widths, backend — never from
    data (the choice must be a trace-time constant or it would churn the
    compile cache; the runtime fits/unique check inside the DIRECT tier
    stays a lax.cond). Returns ``(strategy, reason)``; the reason rides
    into describe()/explain_metrics and the 'join_strategy' event.

    AUTO resolves:

      * legacy sql.join.pallasProbe.enabled forces PALLAS (back compat);
      * CPU backend -> DIRECT below _RADIX_JOIN_CPU_MIN_BUILD for
        single fixed-width keys (two cheap scatters + consumer fusion),
        RADIX at or above it (the scatter dialect's charged bytes
        dominate — the r10 join shape's 29.8x amplification);
      * otherwise the cheapest of DIRECT (near-serial scatter build +
        two-gather probe), RADIX (bitonic co-sort passes at the derated
        peak HBM rate) and SEARCH (log2(build) gather passes), with
        DIRECT only priced for single fixed-width keys.
    """
    import math

    from ..conf import JOIN_PALLAS_PROBE, JOIN_STRATEGY

    mode = conf.get(JOIN_STRATEGY)
    if mode != "AUTO":
        return mode, "forced by spark.rapids.tpu.sql.join.strategy"
    if conf.get(JOIN_PALLAS_PROBE):
        return ("PALLAS",
                "AUTO: sql.join.pallasProbe.enabled (legacy toggle) — "
                "VMEM-tiled probe kernel")
    if backend is None:
        backend = jax.default_backend()
    words, fixed = _key_word_count(key_dtypes)
    direct_ok = fixed and 0 < words <= 2
    if backend == "cpu":
        if direct_ok and build_cap < _RADIX_JOIN_CPU_MIN_BUILD:
            return ("DIRECT",
                    "AUTO: CPU backend, single fixed-width key, build "
                    f"cap {build_cap} < 2^16 — direct-address tables "
                    "are two cheap scatters and the probe fuses into "
                    "its consumer chain")
        return ("RADIX",
                "AUTO: CPU backend at build cap "
                f"{build_cap} — the scatter dialect charges the "
                "direct-address tables far past the layout bound "
                "(BENCH_r10 join: 29.8x); the co-sorted merge is sized "
                "to the bound")
    from .aggregate import _HBM_DERATE, _roofline_peaks

    if (direct_ok and build_cap <= (1 << 20)
            and join_type in ("inner", "left", "semi", "anti")):
        # the direct table probes with two gathers AND fuses the whole
        # join into its consumer chain (one dispatch) — for the
        # dense-dim-key case the fusion is worth more than any probe
        # micro-cost; past ~2^20 the 4x-cap tables and their scatter
        # build stop amortizing. Full joins can never fuse (the
        # unmatched-build pass), so they fall to the cost comparison
        # below instead of paying the scatter build for nothing
        return ("DIRECT",
                f"AUTO: single fixed-width key, build cap {build_cap} "
                "<= 2^20 — the direct-address table probes with two "
                "gathers and fuses into its consumer chain")
    hbm_bps, _ = _roofline_peaks(conf, backend)
    hbm_eff = _HBM_DERATE * hbm_bps
    lg = max(1, math.ceil(math.log2(max(2, build_cap))))
    key_bytes = 4 * max(1, words)
    # probe capacity is not known at build time; a probe side at least
    # as large as the build is the hash-join common case, so per-side
    # costs use build_cap for both surfaces. The search's gather chain
    # is priced at the chip's near-serial random-access gather rate —
    # the reason the sequential-bandwidth merge exists at all
    search_s = (2 * lg * build_cap * max(1, words)
                * _GATHER_SEC_PER_ELEM)
    sort_passes = lg * (lg + 1) / 2  # bitonic compare-exchange rounds
    radix_s = (2 * build_cap * (key_bytes + 12) * sort_passes
               + 4 * build_cap * 8) / hbm_eff
    pick = "RADIX" if radix_s < search_s else "SEARCH"
    return (pick,
            f"AUTO: est radix {radix_s * 1e3:.1f}ms "
            f"({sort_passes:.0f} passes) vs search "
            f"{search_s * 1e3:.1f}ms ({2 * lg} gather passes) at build "
            f"cap={build_cap}, {hbm_bps / 1e9:.0f}GB/s peak")


def _concat_all(conf, exec_: TpuExec) -> Optional[ColumnarBatch]:
    """Materialize every partition of an exec into ONE batch (build side)."""
    batches: List[ColumnarBatch] = []
    for p in range(exec_.num_partitions):
        for b in exec_.execute_partition(p):
            if b.num_rows > 0:
                batches.append(b)
    return _concat_batches(exec_.output_schema, batches)


def _concat_partition(exec_: TpuExec, index: int) -> Optional[ColumnarBatch]:
    """Materialize ONE partition of an exec into one batch."""
    batches = [
        b for b in exec_.execute_partition(index) if b.num_rows > 0
    ]
    return _concat_batches(exec_.output_schema, batches)


def _concat_batches(
    schema: StructType, batches: List[ColumnarBatch]
) -> Optional[ColumnarBatch]:
    if not batches:
        return None
    # sort/window/join kernels want the plain Arrow string layout (byte
    # chunk keys, row-repeating gathers): dict columns materialize here
    from .base import materialized_batch

    batches = [materialized_batch(b) for b in batches]
    if len(batches) == 1:
        return batches[0]
    lengths = [b.num_rows for b in batches]
    str_cols = [
        j for j, f in enumerate(schema.fields)
        if isinstance(f.dataType, (T.StringType, T.BinaryType))
    ]
    byte_lengths = [
        [int(b.columns[j].offsets[b.num_rows]) for j in str_cols]
        for b in batches
    ]
    out_cap = choose_capacity(sum(lengths))
    out_char_caps = [
        choose_capacity(max(1, sum(bl[k] for bl in byte_lengths)), 128)
        for k in range(len(str_cols))
    ]
    cols, n = concat_ops.concat_batches_cols(
        [vals_of_batch(b) for b in batches], lengths, byte_lengths,
        out_cap, out_char_caps,
    )
    return batch_from_vals(cols, schema, n)


class TpuShuffledHashJoinExec(TpuExec):
    """Build right side once, stream probe batches from the left.

    Handles inner/left/right/full/semi/anti equi-joins plus an optional
    residual condition on inner joins (reference: GpuShuffledHashJoinBase +
    GpuHashJoin condition handling)."""

    def __init__(
        self,
        conf: RapidsConf,
        left: TpuExec,
        right: TpuExec,
        left_keys: Sequence[E.Expression],
        right_keys: Sequence[E.Expression],
        join_type: str = "inner",
        condition: Optional[E.Expression] = None,
        partitioned: bool = False,
    ):
        super().__init__(conf, [left, right])
        if join_type not in _JOIN_TYPES:
            raise ValueError(f"unknown join type {join_type}")
        self.join_type = join_type
        #: True when both sides are co-partitioned by the join keys (the
        #: planner inserted hash exchanges): build/probe stay per-partition
        self.partitioned = partitioned
        self.condition = condition
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        # right joins: swap sides, permute output columns back at the end
        self._swap = join_type == "right"
        self._probe = right if self._swap else left
        self._build = left if self._swap else right
        self._probe_keys = [
            E.bind_references(k, self._probe.output_schema)
            for k in (right_keys if self._swap else left_keys)
        ]
        self._build_keys = [
            E.bind_references(k, self._build.output_schema)
            for k in (left_keys if self._swap else right_keys)
        ]
        self._jt = "left" if self._swap else join_type

        lf = left.output_schema.fields
        rf = right.output_schema.fields
        if join_type in ("semi", "anti"):
            self._schema = StructType(tuple(lf))
        else:
            nl = join_type in ("right", "full")
            nr = join_type in ("left", "full")
            self._schema = StructType(tuple(
                [StructField(f.name, f.dataType, f.nullable or nl) for f in lf]
                + [StructField(f.name, f.dataType, f.nullable or nr) for f in rf]
            ))
        if condition is not None:
            if join_type != "inner":
                raise ValueError(
                    "residual join conditions only supported for inner joins")
            comb = StructType(tuple(lf) + tuple(rf))
            self._cond = E.bind_references(condition, comb)
        else:
            self._cond = None
        self._built = None  # lazy build-side state
        self._fast_built = None  # lazy direct-address build (None=untried)
        self._build_batch = None  # concatenated build input, shared by both paths
        # join strategy (conf sql.join.strategy): resolved lazily per
        # build capacity bucket — the choice must see the real build
        # shape — and memoized so AUTO never flips mid-plan (same
        # contract as the aggregate's _strategy_by_cap)
        self._strategy_by_cap: dict = {}
        self._join_strategy_choice: Optional[Tuple[str, str]] = None

    @property
    def output_schema(self):
        return self._schema

    @property
    def num_partitions(self):
        # full outer needs a global unmatched-build pass: single partition
        # unless the sides are co-partitioned (unmatched rows stay local)
        if self.join_type == "full" and not self.partitioned:
            return 1
        return self._probe.num_partitions

    def describe(self):
        strat = (f", strategy={self._join_strategy_choice[0]}"
                 if self._join_strategy_choice is not None else "")
        return f"TpuShuffledHashJoinExec({self.join_type}{strat})"

    def resolved_strategy(self, build_cap: int) -> str:
        """Resolve (and memoize per build capacity bucket) the probe
        lowering for this plan. The choice lands in describe() — and
        thus explain_metrics() — and emits ONE 'join_strategy' event per
        (exec, build capacity), so tools/tpu_profile.py can hold the
        chooser accountable against the measured op spans of the same
        log (the agg resolved_strategy contract)."""
        hit = self._strategy_by_cap.get(build_cap)
        if hit is not None:
            return hit
        strategy, reason = choose_join_strategy(
            self.conf, build_cap,
            [k.dtype for k in self._build_keys], self._jt)
        self._strategy_by_cap[build_cap] = strategy
        self._join_strategy_choice = (strategy, reason)
        from .. import events as _events
        from .. import obs as _obs

        if _events.enabled():
            _events.emit("join_strategy", op=self.node_name,
                         strategy=strategy, reason=reason,
                         build_cap=build_cap)
        if _obs.enabled():
            _obs.inc("tpu_join_strategy", 1, strategy=strategy)
        return strategy

    # -- build side --------------------------------------------------------
    def _key_str_lens(self, batch, keys) -> Tuple[int, ...]:
        lens = []
        for k in keys:
            if isinstance(k.dtype, (T.StringType, T.BinaryType)):
                if isinstance(k, E.BoundReference):
                    c = batch.columns[k.ordinal]
                    m = int(max_string_len(StrV(c.offsets, c.chars, c.validity)))
                else:
                    m = 64
                lens.append(max(4, choose_capacity(max(1, m), 4)))
        return tuple(lens)

    def _concat_build(self) -> ColumnarBatch:
        """Concatenate the whole build side ONCE, shared between the fast
        direct-address build and the sorted general build (a runtime
        fast-path rejection must not re-execute the build subtree)."""
        if self._build_batch is None:
            batch = _concat_all(self.conf, self._build)
            if batch is None:
                bschema = self._build.output_schema
                batch = ColumnarBatch.from_pydict(
                    {f.name: [] for f in bschema.fields}, bschema)
            self._build_batch = batch
        return self._build_batch

    def _get_build(self, index: Optional[int] = None):
        """Build-side state; ``index`` keys per-partition builds when the
        sides are co-partitioned."""
        if self._built is None:
            self._built = {}
        if index in self._built:
            return self._built[index]
        if index is not None:
            batch = _concat_partition(self._build, index)
            if batch is None:
                bschema = self._build.output_schema
                batch = ColumnarBatch.from_pydict(
                    {f.name: [] for f in bschema.fields}, bschema)
        else:
            batch = self._concat_build()
        cap = batch.capacity
        n = batch.num_rows
        sml = self._key_str_lens(batch, self._build_keys)
        strategy = self.resolved_strategy(cap)

        def prep(cols, num_rows):
            live = filter_gather.live_of(num_rows, cap)
            keys = [lower(k, cols, cap) for k in self._build_keys]
            words, any_null = join_ops.radix_key_words(
                keys, [k.dtype for k in self._build_keys], sml)
            ok = live & ~any_null
            # sort build rows: joinable rows first, then live null-key rows
            # (they can never match, but full outer must still emit them),
            # dead padding last
            order_rank = jnp.where(ok, 0, jnp.where(live, 1, 2))
            perm, sorted_radix = sort_with_radix_keys(
                keys, [k.dtype for k in self._build_keys],
                [SortOrder(True, True) for _ in keys],
                order_rank == 0, sml)
            live_all = jnp.take(live, perm, mode="clip")
            sorted_cols = filter_gather.gather(cols, perm, live_all)
            sorted_words = [jnp.take(w, perm, mode="clip") for w in words]
            count = jnp.sum(ok.astype(jnp.int32))
            return sorted_cols, sorted_words, count, live_all

        fn = self._jit_cache_get(
            ("build", batch_signature(batch), cap, sml, strategy), prep)
        sorted_cols, sorted_words, count, live_all = fn(
            vals_of_batch(batch), count_scalar(n))
        # the build side is registered with the buffer catalog so memory
        # pressure can spill it between build and probe (reference:
        # SpillableColumnarBatch around the concatenated build table,
        # GpuShuffledHashJoinExec). The registration runs under this
        # exec's op scope: builds happen lazily on first probe — outside
        # any op_timed section — so the HBM ledger would otherwise book
        # the plan-state bytes as unattributed.
        from .. import xla_cost as _xc

        with _xc.op_scope(self.node_name):
            sb = _SpillableBuild(sorted_cols, sorted_words, live_all)
        # the raw concatenated batch must NOT ride in the tuple: the handle
        # is the only reference so a spill actually frees the device copy
        built = (sb, int(count), cap, sml)
        self._built[index] = built
        if index is None:
            self._build_batch = None  # sorted spillable state replaces it
        return built

    # -- fused fast paths (fusable) ----------------------------------------
    # When the probe can run as a pure masked transform — no expansion
    # plan, no output-size sync — the whole join FUSES into the consumer
    # chain (e.g. scan->join->aggregate is ONE XLA dispatch). Two
    # variants, picked by the resolved strategy:
    #
    #   * DIRECT: the build keys form a dense-enough range (TPC-DS
    #     dim-key case) AND are unique (or the join only needs a
    #     membership bit) — one packed (first,count) table lookup + one
    #     packed build-row gather per probe batch;
    #   * RADIX:  the build keys are UNIQUE (any fixed-width key set, no
    #     density requirement; semi/anti need not even that) — the probe
    #     co-sorts against the HBM-resident sorted build words
    #     (ops/join.radix_probe_ranges) INSIDE the fused program, so no
    #     scatter-built table and no cap-sized join output ever
    #     materializes; a matched probe row gathers its single build row
    #     at lo.
    #
    # Each syncs ONE feasibility word per build (fits/unique for DIRECT,
    # unique for RADIX) — the only host round trip the fast paths take.
    # Reference contract: GpuHashJoin.doJoinLeftRight
    # (execution/GpuHashJoin.scala:265) — cudf probes a hash table.

    def _fast_static_ok(self, strategy: str = "DIRECT") -> bool:
        if self.partitioned or self._jt not in ("inner", "left", "semi", "anti"):
            return False
        words = 0
        for k in self._build_keys:
            if isinstance(k.dtype, (T.StringType, T.BinaryType)):
                return False
            words += 2 if k.dtype.to_numpy().itemsize == 8 else 1
        if len(self._build_keys) == 0:
            return False
        if strategy == "DIRECT" and words > 2:
            return False  # the packed table key is one u64
        if self._jt in ("inner", "left"):
            # appended build columns gather as one packed matrix: fixed,
            # packable dtypes only (f64 has no lossless 32-bit split)
            from ..ops.filter_gather import packable_dtype

            for f in self._build.output_schema.fields:
                if isinstance(f.dataType, (T.StringType, T.BinaryType)):
                    return False
                if not packable_dtype(f.dataType.to_numpy()):
                    return False
        return True

    def _try_fast_build(self):
        """Build the fused fast-path state once (see the section comment);
        returns the state dict or False."""
        if self._fast_built is not None:
            return self._fast_built
        if not self._fast_static_ok("ANY"):
            self._fast_built = False
            return False
        batch = self._concat_build()
        strategy = self.resolved_strategy(batch.capacity)
        if strategy == "RADIX":
            # no RADIX-specific static precondition beyond the common
            # "ANY" gate above (any fixed-width key set qualifies)
            self._fast_built = self._radix_fast_build(batch)
            return self._fast_built
        from ..conf import JOIN_PALLAS_PROBE, JOIN_STRATEGY

        legacy_pallas = (strategy == "PALLAS"
                         and self.conf.get(JOIN_STRATEGY) == "AUTO"
                         and self.conf.get(JOIN_PALLAS_PROBE))
        if strategy == "DIRECT" or legacy_pallas:
            # the fused whole-join fast path. The legacy pallasProbe
            # toggle only ever governed the GENERAL probe path — the
            # direct fast path pre-empted it before the strategy conf
            # existed, so under AUTO it still does (the conf's
            # keep-their-behavior contract); a forced
            # sql.join.strategy=PALLAS does disable it
            if not self._fast_static_ok("DIRECT"):
                self._fast_built = False
                return False
        else:
            # SEARCH / forced PALLAS (and infeasible shapes) probe
            # through the general per-batch path
            self._fast_built = False
            return False
        bcap = batch.capacity
        tbl = 4 * bcap
        need_mat = self._jt in ("inner", "left")
        kd = [k.dtype for k in self._build_keys]

        def prep(cols, num_rows):
            from ..ops import filter_gather

            live = filter_gather.live_of(num_rows, bcap)
            keys = [lower(k, cols, bcap) for k in self._build_keys]
            words, any_null = join_ops.radix_key_words(keys, kd, ())
            ok = live & ~any_null
            key64 = join_ops._pack_u64(words)
            has = jnp.any(ok)
            kmin = jnp.min(jnp.where(ok, key64, jnp.uint64(2**64 - 1)))
            kmax = jnp.max(jnp.where(ok, key64, jnp.uint64(0)))
            fits = (~has) | ((kmax - kmin) < jnp.uint64(tbl))
            diffu = key64 - kmin
            off = jnp.where(ok & (diffu < jnp.uint64(tbl)), diffu, jnp.uint64(tbl)
                            ).astype(jnp.int64)
            bidx = jnp.arange(bcap, dtype=jnp.int32)
            first = jnp.full(tbl, bcap, jnp.int32).at[off].min(bidx, mode="drop")
            cnt = jnp.zeros(tbl, jnp.int32).at[off].add(1, mode="drop")
            unique = jnp.max(cnt) <= 1
            packed_tbl = jnp.stack([first, cnt], axis=-1)
            outs = (packed_tbl, kmin, fits, unique)
            if need_mat:
                from ..ops.filter_gather import pack_fixed_cols

                outs = outs + (pack_fixed_cols(list(cols)),)
            return outs

        fn = self._jit_cache_get(
            ("fastbuild", batch_signature(batch), bcap, need_mat,
             "DIRECT"), prep)
        res = fn(vals_of_batch(batch), count_scalar(batch.num_rows_lazy))
        packed_tbl, kmin, fits, unique = res[:4]
        from .base import host_pull

        fits_h, unique_h = (bool(x) for x in host_pull((fits, unique)))
        if not fits_h or (not unique_h and self._jt in ("inner", "left")):
            self._fast_built = False
            return False
        from ..memory import ACTIVE_BATCHING_PRIORITY
        from ..memory.catalog import SpillableHandle

        from .. import xla_cost as _xc

        arrays = {"tbl": packed_tbl, "kmin": kmin}
        if need_mat:
            arrays["mat"] = res[4]
        # fast builds run at fusion-planning time, outside op_timed:
        # scope the registration so the ledger attributes the state
        with _xc.op_scope(self.node_name):
            handle = SpillableHandle(arrays, ACTIVE_BATCHING_PRIORITY,
                                     ledger_kind="plan_state")
        state = {
            "kind": "direct",
            "handle": handle,
            "has_mat": need_mat,
        }
        if need_mat:
            state["dtypes"] = tuple(
                c.data.dtype for c in vals_of_batch(batch)
            )
        self._fast_built = state
        # the raw concatenated batch is no longer needed: only the
        # spill-registered table/matrix state survives (holding both would
        # pin two copies of the build side in HBM)
        self._build_batch = None
        return state

    def _radix_fast_build(self, batch):
        """RADIX fused-probe state: the sorted build key words (+ packed
        build-column matrix for inner/left). Inner/left require UNIQUE
        build keys — a probe row then owns at most one output row and
        the join stays a pure masked transform; semi/anti only need the
        membership bit and take any build. Syncs ONE unique flag."""
        bcap = batch.capacity
        need_mat = self._jt in ("inner", "left")
        kd = [k.dtype for k in self._build_keys]

        def prep(cols, num_rows):
            live = filter_gather.live_of(num_rows, bcap)
            keys = [lower(k, cols, bcap) for k in self._build_keys]
            words, any_null = join_ops.radix_key_words(keys, kd, ())
            ok = live & ~any_null
            perm, _ = sort_with_radix_keys(
                keys, kd, [SortOrder(True, True) for _ in keys], ok, ())
            sorted_words = [jnp.take(w, perm, mode="clip") for w in words]
            count = jnp.sum(ok.astype(jnp.int32))
            # unique = no adjacent equal keys among the joinable prefix
            idx = jnp.arange(bcap, dtype=jnp.int32)
            inner_pos = (idx >= 1) & (idx < count)
            same = inner_pos
            for w in sorted_words:
                same = same & (w == jnp.concatenate([w[:1], w[:-1]]))
            unique = ~jnp.any(same)
            outs = (sorted_words, count, unique)
            if need_mat:
                from ..ops.filter_gather import pack_fixed_cols

                live_all = jnp.take(live, perm, mode="clip")
                sorted_cols = filter_gather.gather(cols, perm, live_all)
                outs = outs + (pack_fixed_cols(list(sorted_cols)),)
            return outs

        fn = self._jit_cache_get(
            ("fastbuild", batch_signature(batch), bcap, need_mat,
             "RADIX"), prep)
        res = fn(vals_of_batch(batch), count_scalar(batch.num_rows_lazy))
        sorted_words, count, unique = res[:3]
        from .base import host_pull

        if need_mat and not bool(host_pull(unique)):
            return False  # duplicate build keys: general RADIX path
        from ..memory import ACTIVE_BATCHING_PRIORITY
        from ..memory.catalog import SpillableHandle

        from .. import xla_cost as _xc

        arrays = {f"w{i}": w for i, w in enumerate(sorted_words)}
        arrays["count"] = count
        if need_mat:
            arrays["mat"] = res[3]
        with _xc.op_scope(self.node_name):
            handle = SpillableHandle(arrays, ACTIVE_BATCHING_PRIORITY,
                                     ledger_kind="plan_state")
        state = {
            "kind": "radix",
            "handle": handle,
            "has_mat": need_mat,
            "nwords": len(sorted_words),
        }
        if need_mat:
            state["dtypes"] = tuple(
                c.data.dtype for c in vals_of_batch(batch))
        self._build_batch = None
        return state

    @property
    def fusable(self):
        return bool(self._try_fast_build())

    @property
    def sparsifies(self):
        return self._jt in ("inner", "semi", "anti")

    def fusion_stream_child(self):
        return self._probe

    def fusion_key(self):
        st = self._fast_built if isinstance(self._fast_built, dict) else {}
        return (
            "join_fast", st.get("kind", "direct"), self._jt, self._swap,
            tuple(repr(k) for k in self._probe_keys), repr(self._cond),
            tuple(str(dt) for dt in st.get("dtypes", ())),
        )

    def side_vals(self) -> tuple:
        st = self._try_fast_build()
        assert isinstance(st, dict)
        a = st["handle"].materialize()
        if st["kind"] == "radix":
            out = tuple(a[f"w{i}"] for i in range(st["nwords"]))
            out = out + (a["count"],)
        else:
            out = (a["tbl"], a["kmin"])
        if st["has_mat"]:
            out = out + (a["mat"],)
        return out

    def lower_batch(self, cols, live, cap, side=()):
        from ..expr.values import DictV as _DictV, as_plain_str

        st = self._fast_built
        keys = [lower(k, cols, cap) for k in self._probe_keys]
        # dict-encoded probe keys expand to bytes for the radix words;
        # non-key dict columns stream through encoded (mask-only path)
        keys = [as_plain_str(v) if isinstance(v, _DictV) else v for v in keys]
        words, any_null = join_ops.radix_key_words(
            keys, [k.dtype for k in self._probe_keys], ())
        ok = live & ~any_null
        if st["kind"] == "radix":
            # co-sorted merge against the HBM-resident sorted build
            # words, INSIDE the fused program: zero scatters, no table
            nw = st["nwords"]
            bwords = list(side[:nw])
            lo, hi, _ = join_ops.radix_probe_ranges(
                bwords, side[nw].astype(jnp.int32), words, ok,
                lo_matched_only=True)
            matched = ok & (hi > lo)
            brow = jnp.where(matched, lo, 0)
            mat_idx = nw + 1
        else:
            packed_tbl, kmin = side[0], side[1]
            tbl = packed_tbl.shape[0]
            key64 = join_ops._pack_u64(words)
            diffu = key64 - kmin
            pin = ok & (key64 >= kmin) & (diffu < jnp.uint64(tbl))
            pc = jnp.where(pin, diffu, jnp.uint64(0)).astype(jnp.int32)
            fc = jnp.take(packed_tbl, pc, axis=0, mode="clip")
            matched = pin & (fc[:, 1] > 0)
            brow = jnp.where(matched, fc[:, 0], 0)
            mat_idx = 2
        jt = self._jt
        if jt == "semi":
            return list(cols), live & matched
        if jt == "anti":
            return list(cols), live & ~matched
        from ..ops.filter_gather import unpack_fixed_cols

        bvals = unpack_fixed_cols(
            jnp.take(side[mat_idx], brow, axis=0, mode="clip"),
            list(st["dtypes"]), matched)
        out = (
            list(bvals) + list(cols) if self._swap
            else list(cols) + list(bvals)
        )
        live_out = (live & matched) if jt == "inner" else live
        if self._cond is not None:
            c = lower(self._cond, out, cap)
            live_out = live_out & c.data & c.validity
        return out, live_out

    # -- probe -------------------------------------------------------------
    def execute_partition(self, index: int) -> Iterator[ColumnarBatch]:
        if self._try_fast_build():
            from .base import run_fused_chain

            yield from run_fused_chain(self, index)
            return
        (sb, build_count, build_cap, bsml) = self._get_build(
            index if self.partitioned else None)
        build_cols, build_words, build_live_all = sb.get()
        build_schema = self._build.output_schema
        matched_any = (
            jnp.zeros(build_cap, jnp.bool_) if self.join_type == "full" else None
        )
        probe_parts = (
            range(self._probe.num_partitions)
            if self.join_type == "full" and not self.partitioned
            else [index]
        )
        from ..memory.retry import with_oom_retry

        def probe_attempt(b):
            from .base import materialized_batch

            # join expansion repeats rows: dict columns materialize
            # up front (their byte bound only covers row subsets)
            return self._probe_batch(
                materialized_batch(b), build_cols, build_words,
                build_count, build_cap)

        for pi in probe_parts:
            for pbatch in self._probe.execute_partition(pi):
                # probe rows are row-local against the intact build side,
                # so split-and-retry streams each half's output as its
                # own batch (combine="list") — half-capacity probe
                # programs, exact results
                with self.op_timed("probe"):
                    outs = with_oom_retry(
                        self.node_name, probe_attempt, pbatch, self.conf,
                        combine="list")
                for out in outs:
                    if out is None:
                        continue
                    batch, matched = out
                    if matched is not None and matched_any is not None:
                        matched_any = matched_any | matched
                    if batch is not None and batch.num_rows > 0:
                        yield self.record_batch(batch)
        if self.join_type == "full":
            yield from self._unmatched_build(
                build_cols, build_live_all, matched_any)

    def _probe_batch(self, pbatch, build_cols, build_words, build_count, build_cap):
        cap = pbatch.capacity if pbatch.columns else 128
        psml = self._key_str_lens(pbatch, self._probe_keys)
        jt = self._jt
        strategy = self.resolved_strategy(build_cap)
        # full outer under RADIX derives the matched-build mask from the
        # SAME co-sorted merge (scatter-free); other tiers keep the
        # eager range-delta mask (one scatter pair)
        radix_matched = self.join_type == "full" and strategy == "RADIX"

        # build words/count enter as jit ARGUMENTS (not closure constants):
        # with per-partition builds the same compiled probe must serve every
        # partition's build data
        def count_phase(cols, num_rows, bwords, bcount):
            live = filter_gather.live_of(num_rows, cap)
            keys = [lower(k, cols, cap) for k in self._probe_keys]
            words, any_null = join_ops.radix_key_words(
                keys, [k.dtype for k in self._probe_keys], psml)
            ok = live & ~any_null
            matched_b = None
            if radix_matched:
                lo, hi, matched_b = join_ops.radix_probe_ranges(
                    bwords, bcount.astype(jnp.int32), words, ok,
                    want_matched=True)
            else:
                lo, hi = join_ops.probe_ranges(
                    bwords, bcount.astype(jnp.int32), words, ok,
                    strategy=strategy)
            counts = hi - lo
            if jt in ("semi", "anti"):
                keep = (counts > 0) if jt == "semi" else (live & (counts == 0))
                if jt == "semi":
                    keep = keep & ok
                return lo, counts, keep, live, matched_b
            if jt in ("left", "full"):
                ex_counts = jnp.where(live & (counts == 0), 1, counts)
                ex_counts = jnp.where(live, ex_counts, 0)
            else:  # inner probe side
                ex_counts = jnp.where(live, counts, 0)
            return lo, counts, ex_counts, live, matched_b

        ckey = ("count", batch_signature(pbatch), cap, psml, build_cap,
                len(build_words), strategy)
        fn = self._jit_cache_get(ckey, count_phase)
        lo, counts, aux, live, matched = fn(
            vals_of_batch(pbatch), count_scalar(pbatch.num_rows_lazy),
            list(build_words), jnp.int32(build_count))

        if self.join_type == "full" and matched is None:
            matched = join_ops.matched_build_mask(lo, lo + counts, live, build_cap)

        if jt in ("semi", "anti"):
            from .base import _donation as _don_semi

            vals, count = filter_gather.filter_cols(
                vals_of_batch(pbatch), aux, pbatch.num_rows_lazy)
            # the compacted output's planes are freshly gathered — no
            # other reference exists, so downstream sites may donate
            return _don_semi().mark_exclusive(
                batch_from_vals(vals, self._schema, count)), matched

        total = int(jnp.sum(aux))
        if total == 0:
            return None, matched
        out_cap = choose_capacity(total, self.conf.shape_bucket_min)

        # the RADIX tier expands scatter-free (prefix-sum searchsorted);
        # other tiers keep the two-repeat plan (scatter+cumsum under the
        # hood, ~20x faster than the search on TPU)
        expand_plan = (join_ops.radix_expansion_plan
                       if strategy == "RADIX" else join_ops.expansion_plan)
        has_strings = any(isinstance(c, StrV) for c in build_cols) or any(
            c.is_string for c in pbatch.columns)
        if has_strings:
            # string outputs need host-synced byte capacities; keep the
            # original eager path for those
            p, build_row, slot_live = expand_plan(aux, lo, out_cap)
            pad_slot = slot_live & (jnp.take(counts, p, mode="clip") == 0)
            build_live = slot_live & ~pad_slot

            def str_caps(cols, rows, live_mask):
                caps = []
                for c in cols:
                    if isinstance(c, StrV):
                        lens = c.offsets[1:] - c.offsets[:-1]
                        need = jnp.sum(jnp.where(
                            live_mask, jnp.take(lens, rows, mode="clip"), 0))
                        caps.append(choose_capacity(max(1, int(need)), 128))
                return caps

            probe_side = filter_gather.gather(
                vals_of_batch(pbatch), p, slot_live,
                str_caps(vals_of_batch(pbatch), p, slot_live))
            build_side = filter_gather.gather(
                build_cols, build_row, build_live,
                str_caps(build_cols, build_row, build_live))
        else:
            # fixed-width: the whole expansion (plan + pad mask + both
            # gathers) is ONE jitted program — eager per-op dispatch over
            # out_cap-sized arrays dominated join wallclock otherwise
            def expand_phase(pvals, bcols, lo_, counts_, aux_):
                p, build_row, slot_live = expand_plan(aux_, lo_, out_cap)
                pad_slot = slot_live & (
                    jnp.take(counts_, p, mode="clip") == 0)
                build_live = slot_live & ~pad_slot
                return (
                    filter_gather.gather(pvals, p, slot_live),
                    filter_gather.gather(bcols, build_row, build_live),
                )

            ekey = ("expand", batch_signature(pbatch), out_cap,
                    len(build_cols),
                    tuple(int(c.data.shape[0]) for c in build_cols),
                    strategy)
            from .base import _donation

            don = _donation()
            # the expand dispatch is the LAST read of the probe planes
            # (count_phase above already ran) — the one join program
            # certified to donate; build_cols (argnum 1) serve every
            # probe batch and never donate
            mask = don.dispatch_mask("join", pbatch, self.conf)
            fne = self._jit_cache_get(ekey, expand_phase, donate=mask)
            if mask:
                # with_oom_retry re-dispatches this probe batch on OOM,
                # so the guard snapshots/restores its planes
                with don.guard("join", pbatch, op=self.node_name,
                               conf=self.conf,
                               metric=self.metric("donatedBytes")):
                    probe_side, build_side = fne(
                        vals_of_batch(pbatch), list(build_cols), lo,
                        counts, aux)
            else:
                probe_side, build_side = fne(
                    vals_of_batch(pbatch), list(build_cols), lo, counts,
                    aux)
        left_side, right_side = (
            (build_side, probe_side) if self._swap else (probe_side, build_side)
        )
        vals = list(left_side) + list(right_side)
        out = batch_from_vals(vals, self._schema, total)
        if self._cond is not None:
            ocap = out.capacity

            def apply_cond(cols, num_rows):
                livec = filter_gather.live_of(num_rows, ocap)
                c = lower(self._cond, cols, ocap)
                mask = livec & c.data & c.validity
                return filter_gather.filter_cols(cols, mask, num_rows)

            fnc = self._jit_cache_get(
                ("cond", batch_signature(out), ocap), apply_cond)
            vals2, cnt = fnc(
                vals_of_batch(out), count_scalar(out.num_rows_lazy))
            out = batch_from_vals(vals2, self._schema, cnt)
        from .base import _donation as _don_out

        # join outputs are freshly gathered planes with exactly one
        # reference (this yield path) — certified downstream sites
        # (agg over a join, a second join's probe) may donate them
        return _don_out().mark_exclusive(out), matched

    def _jit_cache_get(self, key, fn, donate=()):
        cache = getattr(self, "_jits", None)
        if cache is None:
            cache = self._jits = {}
        # the shared pipeline-cache guard: miss accounting + the
        # compiled-program cost plane ride cached_pipeline (xla_cost.py)
        from .base import cached_pipeline

        return cached_pipeline(cache, key, "join",
                               lambda: jax.jit(program("join")(fn),
                                               donate_argnums=donate),
                               donate=donate, per_instance=True)

    def _unmatched_build(self, build_cols, build_live_all, matched_any):
        """full outer: emit build rows no probe row matched (including live
        null-key rows, which can never match), null-padded on the left."""
        unmatched = build_live_all & ~matched_any
        vals, count = filter_gather.filter_cols(build_cols, unmatched, None)
        n = int(count)
        if n == 0:
            return
        lf = self.children[0].output_schema.fields
        cap_out = vals[0].validity.shape[0] if vals else 128
        null_left: List[Val] = []
        for f in lf:
            if isinstance(f.dataType, (T.StringType, T.BinaryType)):
                null_left.append(StrV(
                    jnp.zeros(cap_out + 1, jnp.int32),
                    jnp.zeros(1, jnp.uint8),
                    jnp.zeros(cap_out, jnp.bool_),
                ))
            else:
                null_left.append(ColV(
                    jnp.zeros(cap_out, dtype=f.dataType.to_numpy()),
                    jnp.zeros(cap_out, jnp.bool_),
                ))
        out = batch_from_vals(null_left + list(vals), self._schema, n)
        yield self.record_batch(out)


class TpuBroadcastNestedLoopJoinExec(TpuExec):
    """Cartesian/conditioned nested-loop join (reference:
    GpuBroadcastNestedLoopJoinExec.scala:311, GpuCartesianProductExec).

    Inner-only: every (probe, build) pair is generated with static shapes
    and the condition filters it."""

    def __init__(self, conf: RapidsConf, left: TpuExec, right: TpuExec,
                 condition: Optional[E.Expression] = None):
        super().__init__(conf, [left, right])
        lf, rf = left.output_schema.fields, right.output_schema.fields
        self._schema = StructType(tuple(lf) + tuple(rf))
        self._cond = (
            E.bind_references(condition, self._schema)
            if condition is not None else None
        )
        self._built = None

    @property
    def output_schema(self):
        return self._schema

    @property
    def num_partitions(self):
        return self.children[0].num_partitions

    def describe(self):
        return self.node_name

    def execute_partition(self, index: int) -> Iterator[ColumnarBatch]:
        if self._built is None:
            self._built = _concat_all(self.conf, self.children[1])
        build = self._built
        if build is None:
            return
        nb = build.num_rows
        build_vals = vals_of_batch(build)
        for pbatch in self.children[0].execute_partition(index):
            from .base import materialized_batch

            pbatch = materialized_batch(pbatch)
            np_ = pbatch.num_rows
            if np_ == 0 or nb == 0:
                continue
            out_cap = choose_capacity(np_ * nb, self.conf.shape_bucket_min)
            pcap = pbatch.capacity
            pcaps = [
                choose_capacity(max(1, int(c.offsets[np_]) * nb), 128)
                for c in pbatch.columns if c.is_string
            ]
            bcaps = [
                choose_capacity(max(1, int(c.offsets[nb]) * np_), 128)
                for c in build.columns if c.is_string
            ]

            @program("join")
            def expand(pcols, bcols):
                j = jnp.arange(out_cap, dtype=jnp.int32)
                pi = j // nb
                bi = j % nb
                slot_live = j < (np_ * nb)
                left_side = filter_gather.gather(pcols, pi, slot_live, pcaps)
                right_side = filter_gather.gather(bcols, bi, slot_live, bcaps)
                cols = list(left_side) + list(right_side)
                if self._cond is not None:
                    c = lower(self._cond, cols, out_cap)
                    mask = slot_live & c.data & c.validity
                    cols, count = filter_gather.filter_cols(cols, mask, np_ * nb)
                    return cols, count
                return cols, jnp.int32(np_ * nb)

            cache = getattr(self, "_jits", None)
            if cache is None:
                cache = self._jits = {}
            key = (batch_signature(pbatch), out_cap, np_, nb)
            from .base import _donation, cached_pipeline

            don = _donation()
            # probe planes (argnum 0) are dead after the expansion —
            # the build side (argnum 1) is retained for every probe
            # batch and never donates (the "join" certification)
            mask = don.dispatch_mask("join", pbatch, self.conf)
            fn = cached_pipeline(cache, key, "join",
                                 lambda: jax.jit(expand,
                                                 donate_argnums=mask),
                                 donate=mask, per_instance=True)
            with self.op_timed():
                if mask:
                    # no retry harness wraps this dispatch: skip the
                    # guard's host snapshot leg
                    with don.guard("join", pbatch, op=self.node_name,
                                   snapshot=False,
                                   metric=self.metric("donatedBytes")):
                        vals, count = fn(vals_of_batch(pbatch),
                                         build_vals)
                else:
                    vals, count = fn(vals_of_batch(pbatch), build_vals)
                n = int(count)
            if n:
                yield self.record_batch(batch_from_vals(vals, self._schema, n))


class TpuCartesianProductExec(TpuBroadcastNestedLoopJoinExec):
    """Unconditioned cross join (reference: GpuCartesianProductExec.scala:304
    — the same pair-expansion kernel as the nested-loop join, no residual
    condition)."""

    def __init__(self, conf: RapidsConf, left: TpuExec, right: TpuExec):
        super().__init__(conf, left, right, condition=None)
