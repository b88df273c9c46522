"""ColumnarBatch: an ordered set of device columns sharing a row count.

Reference analog: cudf ``Table`` + Spark ``ColumnarBatch`` as bridged by
GpuColumnVector.from(Table) (GpuColumnVector.java:330-420). Here the batch IS
the table; schema travels with it so operators can type-check lazily.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional, Sequence

from ..types import DataType, StructField, StructType
from .column import DeviceColumn, column_from_pylist


class ColumnarBatch:
    """``num_rows`` may be a DEVICE scalar (lazy length): operators thread it
    through fused XLA programs without forcing a host sync — the TPU answer
    to cudf's synchronous row counts. ``num_rows`` (property) syncs and
    caches; ``num_rows_lazy`` never syncs.
    """

    __slots__ = ("columns", "schema", "_num_rows", "_capacity",
                 "exclusive")

    def __init__(self, columns: Sequence[DeviceColumn], schema: StructType,
                 num_rows=None, capacity: Optional[int] = None):
        self.columns: List[DeviceColumn] = list(columns)
        self.schema = schema
        # exclusivity mark (plugin/donation.py): True only when the
        # producer guarantees no other reference to these planes exists,
        # so a certified downstream dispatch may donate them to XLA.
        # select() deliberately builds non-exclusive batches — it SHARES
        # columns with this one.
        self.exclusive = False
        if num_rows is None:
            num_rows = int(columns[0].length) if columns else 0
        self._num_rows = num_rows
        # capacity travels on the batch itself so a zero-column batch (a
        # column-pruning projection feeding count(*)) still knows its row
        # bucket — reading columns[0] would report 0 and silently truncate
        # the live mask downstream
        if self.columns:
            self._capacity = self.columns[0].capacity
        elif capacity is not None:
            self._capacity = capacity
        else:
            from .column import choose_capacity

            self._capacity = choose_capacity(
                num_rows if isinstance(num_rows, int) else 0)
        if isinstance(num_rows, int):
            for c in self.columns:
                if isinstance(c.length, int) and c.length != num_rows:
                    raise ValueError(
                        f"column row count {c.length} != batch rows {num_rows}"
                    )

    @property
    def num_rows(self) -> int:
        if not isinstance(self._num_rows, int):
            self._num_rows = int(self._num_rows)  # device sync, cached
            for c in self.columns:
                c.length = self._num_rows
        return self._num_rows

    @property
    def num_rows_lazy(self):
        """Row count as-is: host int or device scalar, never syncs."""
        return self._num_rows

    @property
    def capacity(self) -> int:
        return self._capacity

    @property
    def num_columns(self) -> int:
        return len(self.columns)

    def column(self, i: int) -> DeviceColumn:
        return self.columns[i]

    def column_by_name(self, name: str) -> DeviceColumn:
        return self.columns[self.schema.field_index(name)]

    def device_memory_size(self) -> int:
        return sum(c.device_memory_size() for c in self.columns)

    def select(self, indices: Iterable[int]) -> "ColumnarBatch":
        idx = list(indices)
        return ColumnarBatch(
            [self.columns[i] for i in idx],
            StructType(tuple(self.schema.fields[i] for i in idx)),
            self.num_rows,
        )

    # -- host interchange -------------------------------------------------
    @staticmethod
    def from_pydict(data: Dict[str, Sequence[Any]], schema: StructType,
                    num_rows: Optional[int] = None) -> "ColumnarBatch":
        cols = []
        n = num_rows
        for f in schema.fields:
            values = data[f.name]
            if n is None:
                n = len(values)
            cols.append(column_from_pylist(values, f.dataType, name=f.name))
        return ColumnarBatch(cols, schema, n if n is not None else 0)

    @staticmethod
    def _parallel_get(leaves: List[Any]) -> List[Any]:
        """Concurrent device→host pulls: jax.device_get fetches tree
        leaves serially, and EACH leaf pays a host round trip — a 7-column
        readback costs 7 of them. Pulling leaves from a thread pool makes the
        wall cost one round trip (reference contrast: cudf's bounce-buffer D2H
        copy is one contiguous DMA, GpuColumnarToRowExec.scala:38)."""
        import jax

        from ..exec.base import phase

        leaves = list(leaves)
        # the d2h boundary of whichever exec pulls (the collect boundary,
        # mostly): it ends when the data is on the host
        with phase("d2h") as span:
            if span.on:
                span.set(bytes=sum(
                    int(getattr(x, "nbytes", 0)) for x in leaves))
            if len(leaves) <= 1:
                return [jax.device_get(x) for x in leaves]
            from concurrent.futures import ThreadPoolExecutor

            with ThreadPoolExecutor(
                    max_workers=min(16, len(leaves))) as pool:
                return list(pool.map(jax.device_get, leaves))

    def host_columns(self) -> List[Any]:
        """Fetch every column (and a lazy row count) in ONE round trip —
        leaves pulled concurrently instead of one link RTT per column.

        When the live row count is far below capacity (post-filter /
        post-aggregate batches), columns are sliced ON DEVICE to the row
        bucket first so the transfer moves only live data — host links
        (PCIe/DCN) are orders slower than HBM."""
        import jax
        import numpy as np

        from ..utils.bucketing import bucket_rows

        from .column import HostColumn

        if not any(c.is_string for c in self.columns):
            return self._host_columns_fixed()

        # round trip 1 (tiny): row count + string byte counts (dict
        # columns need none — the dictionary pool is fetched whole)
        head: List[Any] = [self._num_rows]
        for c in self.columns:
            if c.is_string and not c.is_dict:
                head.append(c.offsets[self._num_rows if not isinstance(self._num_rows, int) else min(self._num_rows, c.offsets.shape[0] - 1)])
        hvals = self._parallel_get(head)
        n = int(hvals[0])
        if not isinstance(self._num_rows, int):
            self._num_rows = n
            for c in self.columns:
                c.length = n
        str_bytes = [int(v) for v in hvals[1:]]

        tree: List[Any] = []
        si = 0
        for c in self.columns:
            if c.is_dict:
                d = c.dictv
                fetch_rows = min(int(d.codes.shape[0]), bucket_rows(n, 1))
                tree.append((d.codes[:fetch_rows], c.validity[:fetch_rows],
                             d.dictionary.offsets, d.dictionary.chars))
            elif c.is_string:
                fetch_rows = min(int(c.offsets.shape[0]) - 1, bucket_rows(n, 1))
                nb = min(int(c.chars.shape[0]), bucket_rows(max(1, str_bytes[si]), 1))
                si += 1
                tree.append(
                    (c.offsets[: fetch_rows + 1], c.chars[:nb], c.validity[:fetch_rows])
                )
            else:
                fetch_rows = min(int(c.data.shape[0]), bucket_rows(n, 1))
                tree.append((c.data[:fetch_rows], c.validity[:fetch_rows]))
        flat: List[Any] = [x for parts in tree for x in parts]
        got = self._parallel_get(flat)
        fetched = []
        pos = 0
        for parts in tree:
            fetched.append(tuple(got[pos: pos + len(parts)]))
            pos += len(parts)
        out: List[HostColumn] = []
        from ..types import BinaryType

        for c, parts in zip(self.columns, fetched):
            if c.is_dict:
                from .column import decode_dict_rows

                codes, validity, doff, dch = parts
                validity = np.asarray(validity)[:n]
                data = decode_dict_rows(
                    np.asarray(dch), np.asarray(doff),
                    np.asarray(codes)[:n], validity,
                    binary=isinstance(c.dtype, BinaryType))
                out.append(HostColumn(c.dtype, data, validity))
            elif c.is_string:
                offsets, chars, validity = parts
                offsets = np.asarray(offsets)
                validity = np.asarray(validity)[:n]
                data = decode_string_rows(
                    np.asarray(chars), offsets, validity, n,
                    binary=isinstance(c.dtype, BinaryType))
                out.append(HostColumn(c.dtype, data, validity))
            else:
                data, validity = parts
                out.append(
                    HostColumn(c.dtype, np.asarray(data)[:n].copy(),
                               np.asarray(validity)[:n])
                )
        return out

    def _host_columns_fixed(self) -> List[Any]:
        """Fixed-width-only readback: ONE speculative round trip.

        Fetches the row count plus a 4K-row slice of every column together;
        only when more rows are live does a second fetch happen. Post-
        aggregate/filter outputs almost always fit the first fetch, so a
        collect costs a single host<->device round trip.
        """
        import jax
        import numpy as np

        from ..utils.bucketing import bucket_rows
        from .column import HostColumn

        cap = self.capacity
        nr = self._num_rows
        guess = min(cap, bucket_rows(nr, 1) if isinstance(nr, int) else 4096)
        tree: List[Any] = [nr]
        for c in self.columns:
            tree.append((c.data[:guess], c.validity[:guess]))
        flat: List[Any] = [tree[0]] + [
            x for parts in tree[1:] for x in parts
        ]
        got = self._parallel_get(flat)
        fetched: List[Any] = [got[0]]
        pos = 1
        for parts in tree[1:]:
            fetched.append(tuple(got[pos: pos + len(parts)]))
            pos += len(parts)
        n = int(fetched[0])
        if not isinstance(self._num_rows, int):
            self._num_rows = n
            for c in self.columns:
                c.length = n
        parts = list(fetched[1:])
        if n > guess:  # rare: second fetch for the tail
            tail = [
                (c.data[guess: bucket_rows(n, 1)], c.validity[guess: bucket_rows(n, 1)])
                for c in self.columns
            ]
            got2 = self._parallel_get([x for parts in tail for x in parts])
            more = [
                (got2[2 * i], got2[2 * i + 1])
                for i in range(len(self.columns))
            ]
            parts = [
                (np.concatenate([d1, d2]), np.concatenate([v1, v2]))
                for (d1, v1), (d2, v2) in zip(parts, more)
            ]
        return [
            HostColumn(c.dtype, np.asarray(d)[:n].copy(), np.asarray(v)[:n])
            for c, (d, v) in zip(self.columns, parts)
        ]

    def to_pydict(self) -> Dict[str, List[Any]]:
        hosts = self.host_columns()
        return {
            f.name: h.to_pylist() for f, h in zip(self.schema.fields, hosts)
        }

    def to_rows(self) -> List[tuple]:
        """Columnar-to-row boundary (reference: GpuColumnarToRowExec.scala:38)."""
        cols = [h.to_pylist() for h in self.host_columns()]
        return list(zip(*cols)) if cols else [() for _ in range(self.num_rows)]

    def __repr__(self):
        names = ",".join(f.name for f in self.schema.fields)
        return f"ColumnarBatch(rows={self.num_rows}, cols=[{names}])"


def decode_string_rows(chars, offsets, validity, n: int, binary: bool = False):
    """Vectorized string-column readback (reference role:
    GpuColumnarToRowExec's accelerated copy, GpuColumnarToRowExec.scala:38).

    ONE utf-8 decode of the whole byte pool, then C-level str slicing at
    per-row CHARACTER offsets (a cumsum over non-continuation bytes maps
    byte offsets to char offsets) — no per-row python decode loop."""
    import numpy as np

    data = np.empty(n, dtype=object)
    if n == 0:
        return data
    total = int(offsets[n])
    raw = chars[:total].tobytes()
    if binary:
        lst = [
            raw[o0:o1] if v else None
            for o0, o1, v in zip(offsets[:n], offsets[1:n + 1], validity)
        ]
        data[:] = lst
        return data
    try:
        big = raw.decode("utf-8")
    except UnicodeDecodeError:
        # external Arrow data may carry garbage bytes under NULL slots
        # (offsets only need to be monotonic); decode row-by-row, skipping
        # invalid rows like the slow path always did
        lst = [
            raw[o0:o1].decode("utf-8") if v else None
            for o0, o1, v in zip(offsets[:n], offsets[1:n + 1], validity)
        ]
        data[:] = lst
        return data
    starts = (chars[:total] & 0xC0) != 0x80
    co = np.zeros(total + 1, np.int64)
    np.cumsum(starts, out=co[1:])
    ro = co[offsets[: n + 1]]
    lst = [
        big[o0:o1] if v else None
        for o0, o1, v in zip(ro[:n], ro[1:], validity)
    ]
    data[:] = lst
    return data


def schema_of(**kwargs: DataType) -> StructType:
    return StructType(tuple(StructField(k, v) for k, v in kwargs.items()))


def batch_from_rows(rows: Sequence[Sequence[Any]], schema: StructType) -> ColumnarBatch:
    """Row-to-columnar transition (reference: GpuRowToColumnarExec.scala:37).

    The row count is passed explicitly: a fully-pruned (zero-column)
    schema has no column to recover it from."""
    data: Dict[str, List[Any]] = {f.name: [] for f in schema.fields}
    width = len(schema.fields)
    for i, row in enumerate(rows):
        if len(row) != width:
            raise ValueError(f"row {i} has {len(row)} values, schema has {width}")
        for f, v in zip(schema.fields, row):
            data[f.name].append(v)
    return ColumnarBatch.from_pydict(data, schema, num_rows=len(rows))
