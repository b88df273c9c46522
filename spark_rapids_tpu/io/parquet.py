"""Parquet scan: footer parse, row-group pruning, three reader strategies.

Reference analog: GpuParquetScan.scala —
  * CPU-side footer parse + row-group/column prune:
    GpuParquetFileFilterHandler.filterBlocks (:289-352);
  * PERFILE / COALESCING (MultiFileParquetPartitionReader :880) /
    MULTITHREADED cloud reader (MultiFileCloudParquetPartitionReader :1299)
    selected by reader-type conf + cloudSchemes (RapidsConf.scala:546-577);
  * partition values attached as constant columns
    (ColumnarPartitionReaderWithPartitionValues.scala).

Here pyarrow does the host half (exactly the role the CPU plays in the
reference) and the device half is the buffer-level upload in
arrow_convert.py. A "split" is the unit of data parallelism: one or more
(file, row-group) runs that execute as one partition.
"""
from __future__ import annotations

import dataclasses
import glob as _glob
import os
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

from .. import types as T
from ..conf import (
    CLOUD_SCHEMES,
    MAX_READER_BATCH_SIZE_BYTES,
    PARQUET_READER_TYPE,
    RapidsConf,
)
from .arrow_convert import arrow_schema_to_tpu


@dataclasses.dataclass(frozen=True)
class PushedFilter:
    """A col-vs-literal conjunct usable for row-group stat pruning
    (reference: the parquet filter pushdown in filterBlocks)."""

    column: str
    op: str  # one of < <= > >= = != isnull notnull
    value: Any = None


@dataclasses.dataclass
class FileSplit:
    """One scan partition: runs of row groups, plus partition values."""

    path: str
    row_groups: Tuple[int, ...]
    partition_values: Tuple[Tuple[str, Any], ...] = ()


def _is_cloud_path(path: str, conf: RapidsConf) -> bool:
    scheme = path.split("://", 1)[0] if "://" in path else ""
    return scheme in conf.get(CLOUD_SCHEMES).split(",")


def discover_files(path: str) -> List[Tuple[str, Tuple[Tuple[str, Any], ...]]]:
    """Expand a file/directory/glob into (file, hive partition values).

    Directory layouts with key=value components attach partition values
    (reference: partition-value columns in the V1 read bridges).
    """
    paths: List[str]
    if os.path.isdir(path):
        paths = sorted(
            p for p in _glob.glob(os.path.join(path, "**", "*"),
                                  recursive=True)
            if os.path.isfile(p) and not os.path.basename(p).startswith(
                ("_", "."))
        )
    elif any(c in path for c in "*?["):
        paths = sorted(p for p in _glob.glob(path) if os.path.isfile(p))
    else:
        paths = [path]
    out = []
    base = path.rstrip("/")
    for p in paths:
        pvals: List[Tuple[str, Any]] = []
        rel = os.path.relpath(p, base) if os.path.isdir(base) else ""
        for comp in rel.split(os.sep)[:-1]:
            if "=" in comp:
                k, v = comp.split("=", 1)
                pvals.append((k, None if v == "__HIVE_DEFAULT_PARTITION__"
                              else v))
        out.append((p, tuple(pvals)))
    return out


def _stats_allow(stats, f: PushedFilter) -> bool:
    """Can this row group contain rows passing the filter? Conservative:
    True when unknown (reference: filterBlocks keeps unprunable blocks)."""
    if stats is None or not stats.has_min_max:
        return f.op not in ("isnull",) or stats is None or (
            stats.null_count is None or stats.null_count > 0)
    mn, mx = stats.min, stats.max
    v = f.value
    try:
        if f.op == "=":
            return mn <= v <= mx
        if f.op == "<":
            return mn < v
        if f.op == "<=":
            return mn <= v
        if f.op == ">":
            return mx > v
        if f.op == ">=":
            return mx >= v
        if f.op == "isnull":
            return stats.null_count is None or stats.null_count > 0
        if f.op == "notnull":
            return stats.num_values is None or stats.num_values > 0
    except TypeError:
        return True
    return True


def prune_row_groups(pf, filters: Sequence[PushedFilter]) -> List[int]:
    """Row groups that may contain matching rows (min/max/null stats)."""
    md = pf.metadata
    name_to_idx = {md.schema.column(i).path: i
                   for i in range(md.num_columns)}
    keep = []
    for rg in range(md.num_row_groups):
        rgmd = md.row_group(rg)
        ok = True
        for f in filters:
            ci = name_to_idx.get(f.column)
            if ci is None:
                continue
            stats = rgmd.column(ci).statistics
            if not _stats_allow(stats, f):
                ok = False
                break
        if ok:
            keep.append(rg)
    return keep


def prune_columns(columns: List[str], required: frozenset) -> List[str]:
    """The columns a plan reads (``required``, names) of those a file
    scan would read; a plan that reads none (``count(*)``) keeps the
    first, for its row counts."""
    return [c for c in columns if c in required] or columns[:1]


class ParquetScanner:
    """Plans splits and reads them as pyarrow tables."""

    def __init__(self, path: str, conf: RapidsConf,
                 columns: Optional[Sequence[str]] = None,
                 filters: Sequence[PushedFilter] = (),
                 required: Optional[frozenset] = None):
        import pyarrow.parquet as pq

        self.path = path
        self.conf = conf
        self.filters = list(filters)
        self.files = discover_files(path)
        if not self.files:
            raise FileNotFoundError(path)
        first = pq.ParquetFile(self.files[0][0])
        self.file_schema = first.schema_arrow
        self.columns = list(columns) if columns is not None else [
            f.name for f in self.file_schema
        ]
        if required is not None:
            self.columns = prune_columns(self.columns, required)
        # partition columns come from directory names (string-typed);
        # only keys present on EVERY file become schema columns (ragged
        # layouts keep the common prefix)
        if self.files[0][1]:
            common = [k for k, _ in self.files[0][1]]
            for _, pv in self.files[1:]:
                keys = {k for k, _ in pv}
                common = [k for k in common if k in keys]
            self.partition_cols = common
        else:
            self.partition_cols = []
        base = arrow_schema_to_tpu(
            self.file_schema.empty_table().select(self.columns).schema)
        fields = list(base.fields)
        for k in self.partition_cols:
            fields.append(T.StructField(k, T.STRING, True))
        self.schema = T.StructType(tuple(fields))
        self._splits: Optional[List[FileSplit]] = None

    # -- planning ----------------------------------------------------------
    def reader_type(self) -> str:
        rt = self.conf.get(PARQUET_READER_TYPE)
        if rt != "AUTO":
            return rt
        return (
            "MULTITHREADED"
            if _is_cloud_path(self.path, self.conf) else "COALESCING"
        )

    def splits(self) -> List[FileSplit]:
        """Partition the scan: row-group pruning + file coalescing.

        PERFILE: one split per file. COALESCING: files/row-groups packed
        into splits up to the reader batch byte target. MULTITHREADED:
        per-file splits read with a thread pool at execute time.
        """
        if self._splits is not None:
            return self._splits
        import pyarrow.parquet as pq

        target = self.conf.get(MAX_READER_BATCH_SIZE_BYTES)
        rt = self.reader_type()
        splits: List[FileSplit] = []
        pending: List[FileSplit] = []
        pending_bytes = 0
        for fpath, pvals in self.files:
            pf = pq.ParquetFile(fpath)
            keep = prune_row_groups(pf, self.filters)
            if not keep:
                continue
            if rt in ("PERFILE", "MULTITHREADED"):
                splits.append(FileSplit(fpath, tuple(keep), pvals))
                continue
            # COALESCING: pack row-group runs up to the byte target
            md = pf.metadata
            for rg in keep:
                sz = md.row_group(rg).total_byte_size
                if pending and pending_bytes + sz > target:
                    splits.extend(_merge_pending(pending))
                    pending, pending_bytes = [], 0
                pending.append(FileSplit(fpath, (rg,), pvals))
                pending_bytes += sz
        if pending:
            splits.extend(_merge_pending(pending))
        if not splits:
            # fully pruned: one empty split keeps the schema flowing
            splits = [FileSplit(self.files[0][0], (), self.files[0][1])]
        self._splits = splits
        return splits

    # -- reading -----------------------------------------------------------
    def read_split(self, split: FileSplit):
        """One split -> pyarrow Table (file columns only)."""
        import pyarrow.parquet as pq

        pf = pq.ParquetFile(split.path)
        file_cols = [c for c in self.columns if c not in split_pcols(split)]
        if not split.row_groups:
            return pf.schema_arrow.empty_table().select(file_cols)
        t = pf.read_row_groups(list(split.row_groups), columns=file_cols)
        return t

    # unified scanner protocol (shared with CsvScanner/OrcScanner)
    def num_splits(self) -> int:
        return len(self.splits())

    def read_split_i(self, i: int):
        """(pyarrow table, partition values) for split i."""
        s = self.splits()[i]
        return self.read_split(s), s.partition_values

    def read_split_device(self, i: int):
        """Device-decode split i: (list of ColumnarBatch — one per row
        group — or None when no column takes the device path, partition
        values). Cache-missing row groups go through the PIPELINED
        decode→upload reader (io/parquet_device.read_row_groups_pipelined):
        row group N+1 host-decodes on the srtpu-pqdec pool while N's
        staged transfer and device unpack run, bounded by
        ...format.parquet.pipeline.maxInFlight. Reference analog: the GPU
        decode half of GpuParquetScan.scala:1157 plus the coalescing
        reader's copy pipeline (:880-900)."""
        from ..conf import (
            PARQUET_DEVICE_DECODE,
            PARQUET_DICT_STRINGS,
            PARQUET_PIPELINE_MAX_IN_FLIGHT,
        )
        from .parquet_device import read_row_groups_pipelined

        if not self.conf.get(PARQUET_DEVICE_DECODE):
            return None, ()
        dict_strings = bool(self.conf.get(PARQUET_DICT_STRINGS))
        s = self.splits()[i]
        if not s.row_groups:
            return None, s.partition_values
        file_cols = [c for c in self.columns if c not in split_pcols(s)]
        nfields = [
            f for f in self.schema.fields if f.name in file_cols
        ]
        # probe the cache BEFORE opening the file: a fully-hot file must
        # not re-pay the footer parse / mmap it is cached to avoid
        # (the dict-strings flag is part of the key: the two layouts must
        # never serve each other's cached batches)
        cache, keys, batches, asker = _probe_scan_cache(
            self.conf, s, file_cols, "batch-dict" if dict_strings else "batch")
        if all(b is not None for b in batches):
            return batches, s.partition_values
        pf, file_bytes = _open_mapped(s.path)
        missing = [j for j, b in enumerate(batches) if b is None]
        gen = read_row_groups_pipelined(
            s.path, pf, [s.row_groups[j] for j in missing], file_cols,
            nfields, file_bytes, dict_strings=dict_strings,
            max_in_flight=self.conf.get(PARQUET_PIPELINE_MAX_IN_FLIGHT))
        for j, (rg, b) in zip(missing, gen):
            if b is None:
                # no device-decodable column in this row group: the whole
                # split uses the plain reader (generator abandonment is
                # safe — outstanding decode tasks drop their results)
                return None, s.partition_values
            if cache is not None:
                cache.put(keys[j], b, b.device_memory_size(), by=asker)
            batches[j] = b
        return batches, s.partition_values

    def device_stage_plans(self, i: int):
        """Stage-fusion entry: per-row-group decode plans for split i
        WITHOUT dispatching device work, so a consumer exec can splice the
        decode into its own jitted program (one executable per scan→agg
        stage; reference contrast: the GPU decode is one cudf call but
        still a separate kernel launch from the query stage,
        GpuParquetScan.scala:1157). Returns a list per row group of
        ``(num_rows, cap, entries)`` with ``entries`` =
        ``[(args, key, run, field), ...]`` per column, or None when any
        column needs the host decoder (caller uses execute_partition)."""
        from ..conf import PARQUET_DEVICE_DECODE, PARQUET_DICT_STRINGS
        from .parquet_device import row_group_device_plans

        if not self.conf.get(PARQUET_DEVICE_DECODE):
            return None
        dict_strings = bool(self.conf.get(PARQUET_DICT_STRINGS))
        s = self.splits()[i]
        if not s.row_groups or self.partition_cols:
            return None
        file_cols = [c for c in self.columns if c not in split_pcols(s)]
        nfields = [f for f in self.schema.fields if f.name in file_cols]
        # probe the cache BEFORE opening the file (see read_split_device)
        cache, keys, out, asker = _probe_scan_cache(
            self.conf, s, file_cols, "stage-dict" if dict_strings else "stage")
        if all(x is not None for x in out):
            return out
        pf, file_bytes = _open_mapped(s.path)
        for i, rg in enumerate(s.row_groups):
            if out[i] is not None:
                continue
            stage = row_group_device_plans(
                s.path, pf, rg, file_cols, nfields, file_bytes,
                dict_strings=dict_strings)
            if stage is None:
                return None
            if cache is not None:
                nbytes = sum(
                    int(a.size) * a.dtype.itemsize
                    for (args, _, _, _) in stage[2] for a in args)
                cache.put(keys[i], stage, nbytes, by=asker)
            out[i] = stage
        return out



def _probe_scan_cache(conf, split: FileSplit, file_cols, layout: str):
    """(cache or None, keys, one cached value or None per row group, who
    asks). The lookup is a span of the scan exec above, with what it
    found: a later reader tells a warm scan from a cold one by
    ``cache=hit|miss``. Who asks is the query and this split of it: the
    puts of the query's other splits will not evict what this one is
    handed (``scan_cache``'s docstring)."""
    from ..exec.base import current_query, phase
    from .scan_cache import DeviceScanCache, file_key

    cache = DeviceScanCache.get_instance(conf)
    if cache is None:
        return None, None, [None] * len(split.row_groups), None
    qid = current_query()
    asker = None if qid is None else (qid, (split.path, split.row_groups))
    with phase("cache_lookup") as span:
        keys = [file_key(split.path, rg, file_cols, layout)
                for rg in split.row_groups]
        found = [cache.get(k, by=asker) for k in keys]
        hits = sum(x is not None for x in found)
        span.set(cache="hit" if hits == len(found) else "miss",
                 hits=hits, lookups=len(found))
    return cache, keys, found, asker


def _open_mapped(path: str):
    """(ParquetFile, the file's bytes). mmap: plan_chunk touches only the
    selected chunks' byte ranges, so the OS pages in just those — no
    O(splits x file) reads (the page faults land in ``page_plan``)."""
    import mmap

    import pyarrow.parquet as pq

    from ..exec.base import phase

    with phase("read_file") as span:
        pf = pq.ParquetFile(path)
        # the footer is what opening reads; the chunks' bytes are counted
        # where they are paged in (``page_plan``)
        span.set(file_bytes=pf.metadata.serialized_size)
        f = open(path, "rb")
        try:
            file_bytes = mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
        except ValueError:  # empty file
            file_bytes = b""
        finally:
            f.close()
    return pf, file_bytes


def split_pcols(split: FileSplit) -> List[str]:
    return [k for k, _ in split.partition_values]


def _merge_pending(pending: List[FileSplit]) -> List[FileSplit]:
    """Merge same-file consecutive row-group splits; distinct files stay
    separate splits but the exec treats a pending group as one partition.
    """
    out: List[FileSplit] = []
    for s in pending:
        if (out and out[-1].path == s.path
                and out[-1].partition_values == s.partition_values):
            out[-1] = FileSplit(
                s.path, out[-1].row_groups + s.row_groups,
                s.partition_values)
        else:
            out.append(s)
    return out


# ---------------------------------------------------------------------------
# writer (reference: GpuParquetFileFormat.scala + GpuFileFormatWriter)
# ---------------------------------------------------------------------------
def write_parquet(
    batches, path: str, schema: T.StructType,
    compression: str = "snappy",
) -> Dict[str, int]:
    """Chunked parquet write with a temp-file commit protocol.

    Reference analog: cudf chunked writer + GpuFileFormatWriter.scala:339's
    commit semantics (write temp, rename on success). Returns write stats
    (BasicColumnarWriteStatsTracker analog).
    """
    import pyarrow.parquet as pq

    from .arrow_convert import batch_to_arrow
    from .commit import committed_file

    writer = None
    rows = 0
    nbatches = 0
    try:
        with committed_file(path) as tmp:
            for b in batches:
                t = batch_to_arrow(b)
                if writer is None:
                    writer = pq.ParquetWriter(
                        tmp, t.schema, compression=compression)
                writer.write_table(t)
                rows += t.num_rows
                nbatches += 1
            if writer is None:
                from ..columnar.batch import ColumnarBatch

                empty = ColumnarBatch.from_pydict(
                    {f.name: [] for f in schema.fields}, schema)
                t = batch_to_arrow(empty)
                writer = pq.ParquetWriter(
                    tmp, t.schema, compression=compression)
                writer.write_table(t)
            writer.close()
            writer = None
    finally:
        if writer is not None:
            writer.close()
    return {"numRows": rows, "numBatches": nbatches,
            "bytes": os.path.getsize(path)}
