"""ORC scan (reference: GpuOrcScan.scala:924 — same CPU-prune/device-decode
pattern as parquet, single-file reader) + chunked ORC writer. pyarrow.orc
reads stripes on the host; upload is the shared buffer-level path. Pushed
filters apply at the reader (reference: OrcFilters.scala SearchArguments) —
pyarrow exposes no stripe statistics, so the pushdown evaluates host-side
right after decode, before rows cross the (slow) host->device link.
"""
from __future__ import annotations

import os
from typing import Any, Dict, List, Optional, Sequence

from .. import types as T
from ..conf import RapidsConf
from .arrow_convert import arrow_schema_to_tpu
from .parquet import PushedFilter, discover_files, prune_columns


def apply_filters_host(table, filters: Sequence[PushedFilter]):
    """Evaluate pushed col-vs-literal conjuncts on a host arrow table.

    Advisory like all pushdown — the filter exec still re-applies the full
    predicate; this just keeps filtered rows off the host->device link."""
    import pyarrow.compute as pc

    for f in filters:
        if f.column not in table.column_names:
            continue
        c = table[f.column]
        try:
            if f.op == "isnull":
                mask = pc.is_null(c)
            elif f.op == "notnull":
                mask = pc.is_valid(c)
            else:
                op = {"<": pc.less, "<=": pc.less_equal, ">": pc.greater,
                      ">=": pc.greater_equal, "=": pc.equal,
                      "!=": pc.not_equal}.get(f.op)
                if op is None:
                    continue
                mask = op(c, f.value)
        except Exception:
            continue  # unpushable comparison: leave rows for the exec
        table = table.filter(mask.combine_chunks())
    return table


class OrcScanner:
    """One split per (file, stripe)."""

    def __init__(self, path: str, conf: RapidsConf,
                 columns: Optional[Sequence[str]] = None,
                 filters: Optional[Sequence[PushedFilter]] = None,
                 required: Optional[frozenset] = None):
        from pyarrow import orc

        self.conf = conf
        self.files = discover_files(path)
        self.filters = list(filters or ())
        if not self.files:
            raise FileNotFoundError(path)
        f0 = orc.ORCFile(self.files[0][0])
        self.file_schema = f0.schema
        self.columns = list(columns) if columns is not None else [
            self.file_schema.field(i).name
            for i in range(len(self.file_schema.names))
        ]
        if required is not None:
            self.columns = prune_columns(self.columns, required)
        self.schema = arrow_schema_to_tpu(
            self.file_schema.empty_table().select(self.columns).schema)
        self._splits = [
            (fp, s)
            for fp, _ in self.files
            for s in range(orc.ORCFile(fp).nstripes)
        ] or [(self.files[0][0], None)]

    def num_splits(self) -> int:
        return len(self._splits)

    def read_split(self, i: int):
        from pyarrow import orc

        fp, stripe = self._splits[i]
        f = orc.ORCFile(fp)
        if stripe is None:
            return f.schema.empty_table().select(self.columns)
        t = f.read_stripe(stripe, columns=self.columns)
        if self.filters:
            import pyarrow as pa

            t = apply_filters_host(pa.table(t), self.filters)
        return t

    def read_split_i(self, i: int):
        """(pyarrow table, partition values): unified scanner protocol."""
        return self.read_split(i), ()


def write_orc(batches, path: str, schema: T.StructType,
              compression: str = "zstd") -> Dict[str, int]:
    """Chunked ORC write with the temp-file commit protocol (reference:
    GpuOrcFileFormat via the cudf chunked ORC writer +
    GpuFileFormatWriter.scala:339 commit semantics)."""
    from pyarrow import orc

    from ..columnar.batch import ColumnarBatch
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
                    writer = orc.ORCWriter(tmp, compression=compression)
                writer.write(t)
                rows += t.num_rows
                nbatches += 1
            if writer is None:
                empty = ColumnarBatch.from_pydict(
                    {f.name: [] for f in schema.fields}, schema)
                writer = orc.ORCWriter(tmp, compression=compression)
                writer.write(batch_to_arrow(empty))
            writer.close()
            writer = None
    finally:
        if writer is not None:
            writer.close()
    return {"rows": rows, "batches": max(nbatches, 1), "files": 1}
