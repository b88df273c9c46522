"""Typed configuration system (`spark.rapids.tpu.*`).

Re-creation of the reference's RapidsConf (sql-plugin/.../RapidsConf.scala:120-160
entry builders; ~90 keys at :282-814; markdown generator at :838): every tunable
is a registered, documented, validated entry; `RapidsConf.help()` generates the
user-facing configs doc.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence


_REGISTRY: Dict[str, "ConfEntry"] = {}
_REGISTRY_LOCK = threading.Lock()


@dataclasses.dataclass
class ConfEntry:
    key: str
    default: Any
    doc: str
    conf_type: type
    internal: bool = False
    check: Optional[Callable[[Any], Optional[str]]] = None
    valid_values: Optional[Sequence[Any]] = None

    def convert(self, raw: Any) -> Any:
        if raw is None:
            return self.default
        if self.conf_type is bool:
            if isinstance(raw, bool):
                v: Any = raw
            else:
                s = str(raw).strip().lower()
                if s not in ("true", "false"):
                    raise ValueError(f"{self.key}: expected boolean, got {raw!r}")
                v = s == "true"
        elif self.conf_type in (int, float):
            v = self.conf_type(raw)
        else:
            v = str(raw)
        if self.valid_values is not None and v not in self.valid_values:
            raise ValueError(
                f"{self.key}: {v!r} not in allowed values {list(self.valid_values)}"
            )
        if self.check is not None:
            err = self.check(v)
            if err:
                raise ValueError(f"{self.key}: {err}")
        return v


def _register(entry: ConfEntry) -> ConfEntry:
    with _REGISTRY_LOCK:
        if entry.key in _REGISTRY:
            raise ValueError(f"duplicate conf key {entry.key}")
        _REGISTRY[entry.key] = entry
    return entry


def conf(key, default, doc, conf_type=None, internal=False, check=None, valid_values=None):
    if conf_type is None:
        conf_type = type(default) if default is not None else str
    return _register(ConfEntry(key, default, doc, conf_type, internal, check, valid_values))


def _positive(v):
    return None if v > 0 else "must be positive"


def _fraction(v):
    return None if 0.0 <= v <= 1.0 else "must be in [0, 1]"


# ---------------------------------------------------------------------------
# General (reference: RapidsConf.scala:282-450)
# ---------------------------------------------------------------------------
SQL_ENABLED = conf(
    "spark.rapids.tpu.sql.enabled", True,
    "Enable or disable TPU acceleration of SQL operators entirely.")
EXPLAIN = conf(
    "spark.rapids.tpu.sql.explain", "NONE",
    "Explain why parts of a query were or were not placed on the TPU. "
    "NONE/ALL/NOT_ON_TPU.", valid_values=("NONE", "ALL", "NOT_ON_TPU"))
INCOMPATIBLE_OPS = conf(
    "spark.rapids.tpu.sql.incompatibleOps.enabled", False,
    "Enable operators that produce results slightly different from Spark "
    "(e.g. float aggregation ordering).")
IMPROVED_FLOAT_OPS = conf(
    "spark.rapids.tpu.sql.variableFloatAgg.enabled", False,
    "Allow floating-point aggregations whose result may differ in "
    "last-ulp ordering from CPU Spark.")
HAS_NANS = conf(
    "spark.rapids.tpu.sql.hasNans", True,
    "Assume columns may contain NaNs; disables some fast paths when true.")
ENABLE_FLOAT_ROUND_TRIP = conf(
    "spark.rapids.tpu.sql.castFloatToString.enabled", False,
    "Casting floats to string may differ in tie-breaking digits from Java's "
    "formatting; enable if acceptable.")
ENABLE_CAST_STRING_TO_FLOAT = conf(
    "spark.rapids.tpu.sql.castStringToFloat.enabled", False,
    "String-to-float casts can differ in last-ulp from Spark.")
ENABLE_CAST_STRING_TO_TIMESTAMP = conf(
    "spark.rapids.tpu.sql.castStringToTimestamp.enabled", False,
    "String-to-timestamp casts support a subset of formats.")
ENABLE_CAST_FLOAT_TO_TIMESTAMP = conf(
    "spark.rapids.tpu.sql.castFloatToTimestamp.enabled", False,
    "Float-to-timestamp casts round differently from Spark (reference "
    "gates the same pair, RapidsConf.scala:487-533); additionally the "
    "chip's f32-pair f64 emulation overflows for |x| > ~1e38.")
ENABLE_CAST_STRING_TO_INTEGER = conf(
    "spark.rapids.tpu.sql.castStringToInteger.enabled", False,
    "String-to-integral casts can differ from Spark on malformed-input edge "
    "cases (reference gate: spark.rapids.sql.castStringToInteger.enabled).")
DECIMAL_ENABLED = conf(
    "spark.rapids.tpu.sql.decimalType.enabled", True,
    "Enable DECIMAL(<=18) columns on the TPU (stored as int64 unscaled).")
UDF_COMPILER_ENABLED = conf(
    "spark.rapids.tpu.sql.udfCompiler.enabled", False,
    "Compile Python scalar UDF bytecode into engine expression trees "
    "(analog of the reference's JVM-bytecode udf-compiler).")
AUTO_BROADCAST_JOIN_THRESHOLD = conf(
    "spark.rapids.tpu.sql.autoBroadcastJoinThreshold", 10 * 1024 * 1024,
    "Build sides estimated below this size broadcast instead of paying two "
    "hash exchanges (Spark's spark.sql.autoBroadcastJoinThreshold role). "
    "-1 disables.", conf_type=int)
REPLACE_SORT_MERGE_JOIN = conf(
    "spark.rapids.tpu.sql.replaceSortMergeJoin.enabled", True,
    "Replace sort-merge joins with TPU hash joins (reference: RapidsConf.scala:476).")
JOIN_PALLAS_PROBE = conf(
    "spark.rapids.tpu.sql.join.pallasProbe.enabled", False,
    "Legacy toggle (pre-round-14): lower single-fixed-width-key "
    "hash-join probes to the hand-written Pallas kernel "
    "(ops/pallas_join.py). Superseded by "
    "spark.rapids.tpu.sql.join.strategy=PALLAS; when join.strategy is "
    "AUTO, this flag still selects the PALLAS tier for the GENERAL "
    "probe path while the DIRECT fused fast path keeps pre-empting it "
    "where its table fits — exactly the pre-round-14 behavior. A "
    "forced join.strategy=PALLAS disables the fast path too.")
JOIN_STRATEGY = conf(
    "spark.rapids.tpu.sql.join.strategy", "AUTO",
    "Lowering strategy for equi-join probes (ops/join.py), the join "
    "twin of sql.agg.strategy. SEARCH runs the vectorized lexicographic "
    "binary search over the sorted build words (log2(build) gather "
    "passes — the general fallback every other tier degrades to when "
    "its shape preconditions fail); DIRECT builds scatter-built "
    "direct-address (first,count) tables when the single fixed-width "
    "key's value range fits 4x the build capacity, probing with two "
    "gathers — and the whole join can then FUSE into its consumer "
    "chain; RADIX co-radix-sorts build and probe rows by the shared "
    "order-preserving key words (the sort IS the binning, exactly as "
    "the RADIX aggregation tier) and derives every [lo,hi) match range "
    "from segmented prefix sums over that order — zero scatter "
    "instructions, no cap-sized table, bytes sized to the layout "
    "bound; PALLAS runs the probe as the hand-written VMEM-tiled "
    "jax.experimental.pallas kernel (interpret mode off-TPU). All "
    "tiers produce bit-identical ranges and pair lists. AUTO picks per "
    "plan from the static build layout (capacity, key widths, backend) "
    "against the conf-declared roofline peaks "
    "(spark.rapids.tpu.roofline.peakHbmGBps/.peakTflops) and records "
    "its choice — with the reason — in describe()/explain_metrics() "
    "and the event log ('join_strategy'), so a wrong prediction is "
    "visible in tools/tpu_profile.py instead of only as wall-clock.",
    valid_values=("AUTO", "SEARCH", "DIRECT", "RADIX", "PALLAS"))
ENABLE_HASH_PARTIAL_AGG = conf(
    "spark.rapids.tpu.sql.hashAgg.replaceMode", "all",
    "Which aggregation modes to replace: all/partial/final.",
    valid_values=("all", "partial", "final"))
STABLE_SORT = conf(
    "spark.rapids.tpu.sql.stableSort.enabled", True,
    "Use stable sorts so row order matches CPU Spark for equal keys.")
MAX_READER_BATCH_SIZE_ROWS = conf(
    "spark.rapids.tpu.sql.reader.batchSizeRows", 1 << 20,
    "Soft cap on rows per batch produced by scans.", check=_positive)
MAX_READER_BATCH_SIZE_BYTES = conf(
    "spark.rapids.tpu.sql.reader.batchSizeBytes", 2147483647,
    "Soft cap on bytes per batch produced by scans.", check=_positive)
TPU_BATCH_SIZE_BYTES = conf(
    "spark.rapids.tpu.sql.batchSizeBytes", 1 << 31,
    "Target batch size for coalescing (reference: RapidsConf.scala:372).",
    check=_positive)
SHAPE_BUCKET_MIN = conf(
    "spark.rapids.tpu.sql.shapeBucket.minRows", 128,
    "Row counts are padded up to power-of-two buckets >= this to bound XLA "
    "recompilation (TPU-specific; no reference analog).", check=_positive)
CONCURRENT_TPU_TASKS = conf(
    "spark.rapids.tpu.sql.concurrentTpuTasks", 1,
    "Number of tasks that may hold the TPU concurrently "
    "(reference GpuSemaphore: GpuSemaphore.scala:27-66).", check=_positive)
SEMAPHORE_ACQUIRE_TIMEOUT_MS = conf(
    "spark.rapids.tpu.sql.semaphore.acquireTimeoutMs", 0,
    "Give up acquiring the TPU concurrency semaphore after this many "
    "milliseconds and raise TpuSemaphoreTimeout naming the current "
    "holder threads and the wait duration, instead of blocking forever "
    "(the escape hatch for the watchdog's 'deadlocked semaphore' "
    "scenario). 0 (the default) waits indefinitely, matching the "
    "reference GpuSemaphore.", conf_type=int,
    check=lambda v: None if v >= 0 else "must be >= 0")
ENABLE_TRACE = conf(
    "spark.rapids.tpu.sql.trace.enabled", False,
    "Wrap operator hot sections and the host phases inside them (file "
    "read, page planning, host decode, upload, merge parts, d2h) in "
    "jax.profiler TraceAnnotations named <Exec>.<section>, each carrying "
    "query=<id> and its counts (bytes, columns, cache hits); one "
    "TpuSession.query span a query (reference: NvtxWithMetrics.scala). "
    "Off, no annotation is built; program and scope names are always on. "
    "docs/tuning.md lists the names.")
METRICS_DEVICE_SYNC = conf(
    "spark.rapids.tpu.metrics.deviceSync.enabled", False,
    "Device-accurate operator timing: every operator blocks until its "
    "output batch's device buffers are ready and records the wait in its "
    "opTimeDevice metric (reference: the GpuMetric op-time/CUDA-event "
    "pairs in NvtxWithMetrics.scala). With the conf on for the whole "
    "plan, upstream outputs are already fenced when an operator "
    "dispatches, so each wait isolates that operator's own device work. "
    "Costs one host sync per batch per operator — profiling runs only; "
    "read the result with TpuSession.explain_metrics().")
AGG_FUSED_PLAN = conf(
    "spark.rapids.tpu.sql.agg.fusedPlan", "AUTO",
    "Compile the aggregate's whole update+merge(+result projection) over "
    "all same-shaped input batches into ONE XLA program per plan. ON "
    "always fuses (fixed-width buffer schemas only), OFF runs one update "
    "program per batch plus a separate merge program, AUTO fuses except "
    "multi-batch runs on the host/CPU backend (the fused merge stacks "
    "partials at capacity to stay sync-free, the right trade only over a "
    "high-latency device link; the CPU backend merges at real row counts "
    "instead).", valid_values=("AUTO", "ON", "OFF"))
AGG_STRATEGY = conf(
    "spark.rapids.tpu.sql.agg.strategy", "AUTO",
    "Lowering strategy for grouped-aggregation reductions "
    "(ops/bucket_reduce.py, ops/groupby.py, ops/radix_bin.py). MATMUL "
    "prices sums/counts as one-hot limb matmuls on the MXU over the "
    "hash-bucket tiers; SCATTER uses native segment scatters over the "
    "same tiers; RADIX reduces EVERY aggregate "
    "family over the radix-binned order in HBM-resident tiles — zero "
    "scatter instructions and no one-hot, so bytes-accessed approaches "
    "the layout bound; PALLAS runs the hash-groupby update as "
    "hand-written jax.experimental.pallas TPU kernels over the "
    "hash-bucket tiers (interpret mode executes the same kernels "
    "off-TPU). AUTO picks per plan from what it can observe, the backend "
    "and the capacity: MATMUL on an accelerator (the lowering the v5e "
    "compiler takes at every capacity; it refuses RADIX at small ones), "
    "SCATTER on the CPU backend below capacity 2^21 and RADIX from there "
    "on. It records its choice — with the reason — in explain_metrics() "
    "and the event log ('agg_strategy'), so a wrong prediction is "
    "visible in tools/tpu_profile.py instead of only as wall-clock.",
    valid_values=("AUTO", "MATMUL", "SCATTER", "RADIX", "PALLAS"))

# ---------------------------------------------------------------------------
# Memory (reference: RapidsConf.scala:200-340, GpuDeviceManager.scala:160-271)
# ---------------------------------------------------------------------------
HBM_POOL_FRACTION = conf(
    "spark.rapids.tpu.memory.hbm.allocFraction", 0.9,
    "Fraction of HBM to consider available to the pool.", check=_fraction)
HBM_RESERVE = conf(
    "spark.rapids.tpu.memory.hbm.reserve", 1 << 28,
    "Bytes of HBM to hold back from the pool for XLA scratch.", check=_positive)
HOST_SPILL_STORAGE_SIZE = conf(
    "spark.rapids.tpu.memory.host.spillStorageSize", 1 << 30,
    "Bytes of host memory for spilled buffers before going to disk.",
    check=_positive)
SPILL_ENABLED = conf(
    "spark.rapids.tpu.memory.spill.enabled", True,
    "Enable tiered DEVICE->HOST->DISK spill of cached batches.")
MEMORY_DEBUG = conf(
    "spark.rapids.tpu.memory.debug", False,
    "Log allocation/spill events (reference: spark.rapids.memory.gpu.debug).")

# ---------------------------------------------------------------------------
# Shuffle (reference: RapidsConf.scala:687-786)
# ---------------------------------------------------------------------------
SHUFFLE_MESH_SIZE = conf(
    "spark.rapids.tpu.shuffle.meshSize", 0,
    "Number of devices in the exchange mesh (0 = all local devices). "
    "Superseded by spark.rapids.tpu.mesh.devices when both are set.")
MESH_DEVICES = conf(
    "spark.rapids.tpu.mesh.devices", 0,
    "Shard count for SPMD mesh execution (parallel/mesh.get_mesh): caps "
    "or forces how many local devices the mesh spans (0 = all). A value "
    "above the visible device count raises at mesh construction instead "
    "of silently truncating; meshes are memoized per count so every "
    "stage at one width shares a single jax.sharding.Mesh.",
    check=lambda v: None if v >= 0 else "must be >= 0")
MESH_WHOLE_PLAN = conf(
    "spark.rapids.tpu.shuffle.mesh.wholePlan.enabled", True,
    "Absorb fixed-width filter/project chains between a mesh stage and "
    "its source INTO the stage's SPMD program (the execs' lower_batch "
    "hooks run per shard), and feed the program from a sharded scan "
    "(io/mesh_stage.py) when the source supports it — the whole "
    "scan->partial->all_to_all->final plan compiles to ONE jitted "
    "program. Off restores the round-5 behavior: children execute on "
    "the default device and staging gathers through the host.")
MESH_EXCHANGE_BUCKET_FACTOR = conf(
    "spark.rapids.tpu.shuffle.mesh.exchangeBucketFactor", 2.0,
    "Mesh SORT exchange granule as a multiple of the fair per-target "
    "share (cap / n_shards): sampled range bounds spread rows roughly "
    "evenly, so a ~2x granule keeps the all_to_all receive surface "
    "O(cap) instead of O(n_shards x cap); a skewed distribution "
    "overflows the block and the stage retries with the granule "
    "doubled. 0 disables (always-fits full-capacity granule).",
    check=lambda v: None if v >= 0 else "must be >= 0")
MESH_AGG_EXCHANGE_CAP = conf(
    "spark.rapids.tpu.shuffle.mesh.aggExchangeCapacity", 4096,
    "Starting per-shard row capacity for the mesh aggregate's post-PARTIAL "
    "all_to_all: partial aggregates are compacted and sliced to this many "
    "groups per shard before crossing ICI, so the exchange surface is "
    "sized to the GROUP cardinality, not the input row capacity (which "
    "made the naive exchange O(shards x rows)). A shard with more groups "
    "than the cap reports overflow and the stage retries with the cap "
    "doubled (recompiling once per doubling).", check=_positive)
AQE_ENABLED = conf(
    "spark.rapids.tpu.sql.adaptive.enabled", True,
    "Re-plan exchange reads from materialized per-partition stats: "
    "coalesce small partitions, split skewed join probes (reference: "
    "GpuCustomShuffleReaderExec + ShuffledBatchRDD partition specs).")
AQE_TARGET_ROWS = conf(
    "spark.rapids.tpu.sql.adaptive.targetPartitionRows", 1 << 20,
    "Advisory rows per post-AQE partition (coalesce/split target).",
    check=_positive)
AQE_SKEW_FACTOR = conf(
    "spark.rapids.tpu.sql.adaptive.skewedPartitionFactor", 4.0,
    "A join probe partition is skewed when its rows exceed this multiple "
    "of the median (and the target rows).")
SHUFFLE_MODE = conf(
    "spark.rapids.tpu.shuffle.mode", "auto",
    "Exchange lowering: 'ici' lowers shuffle-bounded stages to one SPMD "
    "shard_map program over the device mesh (collectives over ICI), 'host' "
    "uses the single-host exchange, 'auto' picks ici when >1 device is "
    "visible. Reference analog: spark.rapids.shuffle.transport.enabled.",
    valid_values=("auto", "host", "ici"))
SHUFFLE_TRANSPORT_CLASS = conf(
    "spark.rapids.tpu.shuffle.transport.class", "device",
    "Transport for exchange pieces: 'device' (pieces stay TPU-resident in "
    "the shuffle catalog, the UCX device-cache analog), 'host' "
    "(serialized host bytes, the fallback-serializer analog), or "
    "'network' (TCP block server/client across worker processes, the "
    "RapidsShuffleServer/Client analog — selection by conf mirrors "
    "RapidsShuffleTransport.scala:328-411 + RapidsConf.scala:696).",
    valid_values=("device", "host", "network"))
SHUFFLE_NETWORK_PEERS = conf(
    "spark.rapids.tpu.shuffle.network.peers", "",
    "Comma-separated host:port list of the OTHER workers' shuffle "
    "servers; fetches merge local pieces with every peer's (reference: "
    "RapidsCachingReader splits local catalog hits from transport "
    "fetches, RapidsCachingReader.scala:60-155).")
SHUFFLE_NETWORK_LISTEN_PORT = conf(
    "spark.rapids.tpu.shuffle.network.listenPort", 0,
    "TCP port for this process's shuffle block server; 0 picks an "
    "ephemeral port (the chosen address is in the transport's "
    "server.address).")
SHUFFLE_COMPRESSION_CODEC = conf(
    "spark.rapids.tpu.shuffle.compression.codec", "none",
    "Codec for host-path shuffle payloads: none/zstd/lz4. lz4 is the "
    "native C++ block codec (native/src/lz4.cpp, the nvcomp-LZ4 analog) "
    "and requires the g++-built library.",
    valid_values=("none", "zstd", "lz4"))
SHUFFLE_PARTITIONS = conf(
    "spark.rapids.tpu.sql.shuffle.partitions", 0,
    "Number of reduce partitions for exchanges; 0 keeps the child's "
    "partition count (reference: spark.sql.shuffle.partitions).")
SHUFFLE_PARTITIONING_MAX_PARTITIONS = conf(
    "spark.rapids.tpu.shuffle.maxPartitions", 1 << 16,
    "Upper bound on shuffle partitions.", check=_positive)
SHUFFLE_BOUNCE_BUFFER_SIZE = conf(
    "spark.rapids.tpu.shuffle.bounceBuffers.size", 4 << 20,
    "Host staging-buffer size for the host transport path.", check=_positive)

# ---------------------------------------------------------------------------
# IO (reference: RapidsConf.scala:546-665)
# ---------------------------------------------------------------------------
PARQUET_ENABLED = conf(
    "spark.rapids.tpu.sql.format.parquet.enabled", True,
    "Enable TPU parquet scan/write.")
PARQUET_READER_TYPE = conf(
    "spark.rapids.tpu.sql.format.parquet.reader.type", "AUTO",
    "PERFILE, COALESCING, MULTITHREADED or AUTO (reference: RapidsConf.scala:546).",
    valid_values=("AUTO", "PERFILE", "COALESCING", "MULTITHREADED"))
PARQUET_MULTITHREAD_READ_NUM_THREADS = conf(
    "spark.rapids.tpu.sql.format.parquet.multiThreadedRead.numThreads", 4,
    "Threads for the cloud multithreaded reader.", check=_positive)
PARQUET_DEVICE_DECODE = conf(
    "spark.rapids.tpu.sql.format.parquet.deviceDecode.enabled", True,
    "Decode parquet pages ON the TPU (dictionary/RLE expansion as XLA "
    "kernels) so the host uploads encoded bytes instead of raw columns — "
    "the TPU analog of cudf's GPU decoder (GpuParquetScan.scala:1157 "
    "Table.readParquet). Columns with unsupported encodings fall back to "
    "the host arrow decoder per-column.")
STAGE_FUSION = conf(
    "spark.rapids.tpu.sql.stageFusion", "AUTO",
    "Fuse parquet scan->aggregate stages into ONE XLA program. ON always "
    "fuses, OFF never does, AUTO fuses except on the host/CPU backend: "
    "the fusion exists to amortize the per-program dispatch round trip, "
    "but it re-decodes the pages inside the program on EVERY execution. "
    "Where dispatch is free (CPU backend) the separate decode program + "
    "HBM scan cache decode once and reuse, so AUTO prefers that.",
    valid_values=("AUTO", "ON", "OFF"))
PARQUET_PIPELINE_MAX_IN_FLIGHT = conf(
    "spark.rapids.tpu.sql.format.parquet.pipeline.maxInFlight", 8,
    "Row groups the pipelined device-decode reader keeps in flight "
    "(io/parquet_device.py): while row group N's staged transfer and "
    "device unpack run, up to this many row groups (N included) are "
    "host-decoding on the shared srtpu-pqdec pool, and within a row "
    "group the first half of the column chunks to finish decoding "
    "stages+uploads while the rest still decompress (double-buffered "
    "staging). Bounds host memory at ~maxInFlight decoded row-group "
    "payloads (ENCODED pages, typically 1-2 B/value); the default "
    "matches the srtpu-pqdec pool width — measured 2.4x on a cold "
    "16-row-group read vs 1 (the serial round-6 behavior, which this "
    "setting restores). Reference analog: the coalescing multithreaded "
    "reader's copy pipeline (GpuParquetScan.scala:880-900).",
    check=_positive)
PARQUET_DICT_STRINGS = conf(
    "spark.rapids.tpu.sql.format.parquet.dictStrings.enabled", True,
    "Keep dictionary-encoded BYTE_ARRAY columns ENCODED on the TPU "
    "(int32 codes + the file's own dictionary page as a small string "
    "pool) instead of expanding to full offsets+chars at decode — late "
    "materialization, the TPU analog of cudf handing dictionary32 "
    "columns to the plugin. String kernels then run once over the "
    "dictionary (O(cardinality)) and per-row work collapses to integer "
    "gathers; operators without a dictionary path materialize on entry, "
    "so results are identical either way (see docs/compatibility.md).")
SCAN_DEVICE_CACHE = conf(
    "spark.rapids.tpu.scan.deviceCache.enabled", True,
    "Keep decoded scan columns resident in HBM keyed by "
    "(file, mtime, size, row group) so hot files upload once — the TPU "
    "engine's buffer pool. The CPU engine's scans enjoy the OS page "
    "cache; on TPU the host link is the scarce resource, so the pool "
    "caches the post-link artifact (reference analog: the columnar "
    "cache serializer, ParquetCachedBatchSerializer.scala, plus every "
    "database's buffer pool). Invalidated by file mtime/size changes; "
    "evicted LRU under scan.deviceCache.maxBytes.")
SCAN_DEVICE_CACHE_MAX_BYTES = conf(
    "spark.rapids.tpu.scan.deviceCache.maxBytes", 2 << 30,
    "LRU byte budget for the device scan cache.", check=_positive)
CLOUD_SCHEMES = conf(
    "spark.rapids.tpu.cloudSchemes", "abfs,abfss,dbfs,gs,s3,s3a,s3n,wasbs",
    "URI schemes treated as high-latency cloud stores.")
CSV_ENABLED = conf(
    "spark.rapids.tpu.sql.format.csv.enabled", True, "Enable TPU CSV scan.")
ORC_ENABLED = conf(
    "spark.rapids.tpu.sql.format.orc.enabled", True,
    "Enable TPU ORC scan (per-stripe splits via the host arrow reader).")

MATRIX_PROBE_CROSS_CHECK = conf(
    "spark.rapids.tpu.sql.matrix.probeCrossCheck.enabled", False,
    "Debug: run the legacy abstract-trace lowering probe alongside the "
    "static type-support matrix (plugin/typechecks.py) during plan "
    "tagging and record every verdict disagreement. The matrix is the "
    "primary tagging mechanism; when this is on, a probe-only failure is "
    "conservatively added to the fallback reasons and the disagreement "
    "is kept in typechecks.cross_check_log() for inspection.")
ANALYSIS_ENABLED = conf(
    "spark.rapids.tpu.sql.analysis.enabled", True,
    "Run the static plan analyzer (plugin/plananalysis.py) and render its "
    "report — per-operator batch layouts, nullability, predicted peak HBM "
    "footprint, and the forecast of distinct XLA compile signatures per "
    "pipeline cache site — in explain(). The analysis walks the bound "
    "plan without lowering or executing anything; see docs/tuning.md.")
ANALYSIS_CROSS_CHECK = conf(
    "spark.rapids.tpu.sql.analysis.crossCheck.enabled", False,
    "Debug: the test harness runs the static plan analyzer for every "
    "query and asserts its forecasts against reality — actual compile "
    "cache misses per site never exceed the forecast, measured "
    "bytesTouched never exceeds the analyzer's byte bound, and "
    "nullability-elided execution matches the mask-carrying path "
    "exactly (same pattern as sql.matrix.probeCrossCheck.enabled).")
ANALYSIS_NULL_ELISION = conf(
    "spark.rapids.tpu.sql.analysis.nullElision.enabled", True,
    "Elide validity-plane HBM reads for statically NON_NULL columns at "
    "fused-pipeline entries: a declared non-null column's validity is "
    "exactly the liveness mask (padding rows invalid, live rows valid), "
    "so the iota-derived mask replaces the stored plane bit-for-bit and "
    "null-park arithmetic folds away. Disable to force the "
    "mask-carrying path (the analysis cross-check diffs the two).")
ANALYSIS_STORM_THRESHOLD = conf(
    "spark.rapids.tpu.sql.analysis.recompileStorm.threshold", 8,
    "Warn in explain() when the analyzer forecasts at least this many "
    "distinct compile signatures for ONE pipeline cache site — the "
    "static recompile-storm detector (the profiler's cache-miss footer "
    "reports the same storms after the fact).", check=_positive)
LINT_ALLOWLIST_PATH = conf(
    "spark.rapids.tpu.tools.lint.allowlistPath", "tools/tpu_lint_allow.txt",
    "Path (relative to the repo root) of the tracing-hazard lint's "
    "allowlist file — the documented legitimate host-sync sites "
    "tools/tpu_lint.py accepts (one 'path::qualname::RULE  # why' per "
    "line). Read by the lint TOOL at startup (override per run with "
    "--allowlist=); not a per-session runtime setting.")
RACECHECK_ALLOWLIST_PATH = conf(
    "spark.rapids.tpu.tools.racecheck.allowlistPath",
    "tools/tpu_racecheck_allow.txt",
    "Path (relative to the repo root) of the concurrency race analyzer's "
    "allowlist file — the documented deliberate exceptions "
    "tools/tpu_racecheck.py accepts (one 'path::qualname::RULE  # why' "
    "per line). Read by the racecheck TOOL at startup (override per run "
    "with --allowlist=); not a per-session runtime setting.")
RACECHECK_WITNESS_ENABLED = conf(
    "spark.rapids.tpu.tools.racecheck.witness.enabled", False,
    "Install the runtime lock-order witness: every ordered_lock acquire "
    "is validated against the declared LOCK_ORDER hierarchy "
    "(spark_rapids_tpu/utils/locks.py) and observed (outer, inner) "
    "acquisition pairs are recorded for the chaos suite's cross-check "
    "against tools/tpu_racecheck.py's static acquire graph. An "
    "out-of-order acquire raises LockOrderInversion naming the "
    "colliding pair BEFORE blocking, so a would-be deadlock is a typed "
    "error instead of a hang. Off by default — an acquire then costs "
    "one module-global read (the event-log zero-overhead contract). "
    "The SRTPU_RACECHECK_WITNESS=1 environment variable turns it on at "
    "import for subprocess/CI runs.")
DONATION_ENABLED = conf(
    "spark.rapids.tpu.sql.donation.enabled", True,
    "Donate dead-after-dispatch input planes to XLA (donate_argnums) at "
    "the compile sites the donation-safety analyzer certifies "
    "(tools/tpu_donate.py; plugin/donation.py holds the per-site "
    "certification table). A donated plane's HBM is reused for the "
    "program's outputs/temps, cutting peak temp bytes; soundness comes "
    "from the batch-exclusivity protocol — only batches explicitly "
    "marked exclusive by their producer ever donate, so scan-cache / "
    "catalog / spill-held planes are never aliased away. Disable to "
    "force copy-semantics dispatch everywhere (the donation "
    "differential tests diff the two bit-for-bit).")
DONATION_RETRY_SNAPSHOT = conf(
    "spark.rapids.tpu.sql.donation.retrySnapshot.enabled", True,
    "At donating sites under with_oom_retry, snapshot donated planes to "
    "host before dispatch and restore them on failure, so split-and-"
    "retry can re-read the input batch it re-dispatches (memory/"
    "retry.py's contract). Disabling switches those sites to exclusion "
    "mode — retry-covered args are simply not donated — trading the "
    "snapshot's host round-trip for the lost donation win.")
DONATION_WITNESS_ENABLED = conf(
    "spark.rapids.tpu.tools.donation.witness.enabled", False,
    "Install the runtime donation witness: after every donating "
    "dispatch, assert at least one donated buffer was actually deleted "
    "by JAX (the backend may decline INDIVIDUAL aliases — a validity "
    "plane matching no output — but a mask with NO effect means the "
    "certification named an argnum the program never aliased) and "
    "convert any "
    "use-after-donation 'Array has been deleted' error into a typed, "
    "op-attributed TpuDonationViolation naming the site and plane. Off "
    "by default — a dispatch then costs one module-global read (the "
    "event-log zero-overhead contract). The SRTPU_DONATION_WITNESS=1 "
    "environment variable turns it on at import for subprocess/CI runs.")
DONATE_ALLOWLIST_PATH = conf(
    "spark.rapids.tpu.tools.donate.allowlistPath",
    "tools/tpu_donate_allow.txt",
    "Path (relative to the repo root) of the donation-safety analyzer's "
    "allowlist file — the documented deliberate exceptions "
    "tools/tpu_donate.py accepts (one 'path::qualname::RULE  # why' per "
    "line). Read by the donation TOOL at startup (override per run with "
    "--allowlist=); not a per-session runtime setting.")
SCAN_HOST_RESIDENT = conf(
    "spark.rapids.tpu.sql.inMemoryScan.hostResident", False,
    "Keep InMemoryScanExec partitions host-resident and upload fresh "
    "device planes on every execute (the faithful Spark .cache() "
    "semantics: the cached representation survives the query). Fresh "
    "uploads are exclusive to the executing query, so downstream "
    "certified sites can donate them; the default device-resident mode "
    "retains device batches across executes (zero re-upload cost) and "
    "therefore never donates scan planes.")

# ---------------------------------------------------------------------------
# Live observability plane (obs/): metrics registry, /metrics + /status
# HTTP exporter, stall/pressure/storm watchdog. Reference analog: the
# SQLMetrics stream into the live Spark UI (the event log covers offline).
# ---------------------------------------------------------------------------
LIVE_METRICS_ENABLED = conf(
    "spark.rapids.tpu.metrics.live.enabled", False,
    "Install the process-global live metrics registry (obs/): per-op "
    "host/device time and bytes, compile misses by site, the "
    "BufferCatalog HBM watermark, shuffle transport traffic, scan-cache "
    "hit rate, per-query progress. Implied by metrics.http.enabled and "
    "watchdog.enabled. Off by default — the engine's emit fast path is "
    "a single boolean check (the event-log zero-overhead contract).")
METRICS_HTTP_ENABLED = conf(
    "spark.rapids.tpu.metrics.http.enabled", False,
    "Start the stdlib-HTTP exporter daemon thread serving /metrics "
    "(Prometheus text exposition 0.0.4 of the whole metric catalog) and "
    "/status (JSON: live queries with forecast-derived per-op progress, "
    "HBM watermark vs budget, watchdog alerts — the payload "
    "tools/tpu_top.py renders). Implies metrics.live.enabled.")
METRICS_HTTP_PORT = conf(
    "spark.rapids.tpu.metrics.http.port", 0,
    "TCP port for the metrics exporter; 0 picks an ephemeral port "
    "(read the chosen address from TpuSession.obs_address).")
METRICS_HTTP_HOST = conf(
    "spark.rapids.tpu.metrics.http.host", "127.0.0.1",
    "Bind address for the metrics exporter (localhost by default; bind "
    "0.0.0.0 only behind your own auth/network policy).")
WATCHDOG_ENABLED = conf(
    "spark.rapids.tpu.watchdog.enabled", False,
    "Start the watchdog sampler thread: raises typed alerts — operator "
    "span open past watchdog.stallThresholdMs (stall), HBM watermark "
    "above watchdog.hbmPressureFraction of the derived budget "
    "(hbm_pressure), at least sql.analysis.recompileStorm.threshold "
    "compile misses on one site inside watchdog.recompileStorm.windowMs "
    "(recompile_storm) — surfaced as log warnings, 'alert' events in "
    "the event log, and the /status alerts list. Implies "
    "metrics.live.enabled. Tune thresholds offline with "
    "tools/tpu_profile.py --alerts over a recorded event log.")
WATCHDOG_INTERVAL_MS = conf(
    "spark.rapids.tpu.watchdog.intervalMs", 1000,
    "Watchdog sample interval.", check=_positive)
WATCHDOG_STALL_MS = conf(
    "spark.rapids.tpu.watchdog.stallThresholdMs", 30000,
    "An operator span still open after this long raises a stall alert "
    "(a hung device dispatch, a wedged host decode).", check=_positive)
WATCHDOG_PRESSURE_FRACTION = conf(
    "spark.rapids.tpu.watchdog.hbmPressureFraction", 0.85,
    "Raise an hbm_pressure alert when the BufferCatalog device-byte "
    "watermark reaches this fraction of the derived HBM budget (the "
    "SAME derive_hbm_budget the spiller and plan analyzer use).",
    check=_fraction)
WATCHDOG_STORM_WINDOW_MS = conf(
    "spark.rapids.tpu.watchdog.recompileStorm.windowMs", 10000,
    "Sliding window for the LIVE recompile-storm alert; the per-site "
    "miss-count threshold is sql.analysis.recompileStorm.threshold (one "
    "storm definition engine-wide: static forecast, offline profiler "
    "footer, and live watchdog all agree).", check=_positive)
WATCHDOG_RETRY_STORM_THRESHOLD = conf(
    "spark.rapids.tpu.watchdog.retryStorm.threshold", 8,
    "Raise a retry_storm alert when one operator logs at least this "
    "many OOM recovery actions (memory/retry.py oom_retry events) "
    "inside watchdog.recompileStorm.windowMs: the queries still "
    "complete, but every batch is paying spill + backoff (+ the "
    "half-capacity recompiles of split-and-retry) — the admission "
    "forecasts or memory.hbm.budgetBytes need attention.",
    check=_positive)

# ---------------------------------------------------------------------------
# Test hooks (reference: RapidsConf 'test' keys)
# ---------------------------------------------------------------------------
TEST_CONF = conf(
    "spark.rapids.tpu.sql.test.enabled", False,
    "Fail instead of falling back to CPU when an operator is unsupported.",
    internal=True)
TEST_ALLOWED_NONTPU = conf(
    "spark.rapids.tpu.sql.test.allowedNonTpu", "",
    "Comma-separated operator class names allowed to stay on CPU when "
    "test.enabled is set.", internal=True)


class RapidsConf:
    """Immutable snapshot of settings; unknown keys rejected, typed access."""

    def __init__(self, settings: Optional[Dict[str, Any]] = None):
        settings = dict(settings or {})
        self._values: Dict[str, Any] = {}
        for key, raw in settings.items():
            entry = _REGISTRY.get(key)
            if entry is None:
                if key.startswith("spark.rapids.tpu."):
                    raise ValueError(f"unknown config key {key}")
                continue  # ignore non-rapids keys, like the reference does
            self._values[key] = entry.convert(raw)

    def get(self, entry: ConfEntry):
        return self._values.get(entry.key, entry.default)

    def __getitem__(self, entry: ConfEntry):
        return self.get(entry)

    # Convenience accessors mirroring RapidsConf's vals
    @property
    def is_sql_enabled(self) -> bool:
        return self.get(SQL_ENABLED)

    @property
    def explain(self) -> str:
        return self.get(EXPLAIN)

    @property
    def batch_size_bytes(self) -> int:
        return self.get(TPU_BATCH_SIZE_BYTES)

    @property
    def concurrent_tpu_tasks(self) -> int:
        return self.get(CONCURRENT_TPU_TASKS)

    @property
    def is_test_enabled(self) -> bool:
        return self.get(TEST_CONF)

    @property
    def shape_bucket_min(self) -> int:
        return self.get(SHAPE_BUCKET_MIN)

    @staticmethod
    def entries() -> List[ConfEntry]:
        return sorted(_REGISTRY.values(), key=lambda e: e.key)

    @staticmethod
    def help(include_internal: bool = False) -> str:
        """Generate the configs markdown doc (reference: RapidsConf.scala:838)."""
        lines = [
            "# TPU RAPIDS Configuration",
            "",
            "| Name | Description | Default |",
            "|------|-------------|---------|",
        ]
        for e in RapidsConf.entries():
            if e.internal and not include_internal:
                continue
            lines.append(f"| {e.key} | {e.doc} | {e.default} |")
        return "\n".join(lines) + "\n"
