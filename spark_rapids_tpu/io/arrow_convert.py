"""Buffer-level Arrow <-> device column conversion.

Reference analog: HostColumnarToGpu.scala (arrow-backed host columnar ->
device upload) and GpuColumnVector.from(Table). The device layout IS
Arrow (data + validity, offsets + chars for strings), so conversion is
numpy buffer reshaping + one upload per column — never a per-row loop.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from .. import types as T
from ..columnar.batch import ColumnarBatch
from ..columnar.column import DeviceColumn
from ..columnar.column import choose_capacity


def arrow_type_to_tpu(at) -> T.DataType:
    import pyarrow as pa

    if pa.types.is_boolean(at):
        return T.BOOLEAN
    if pa.types.is_int8(at):
        return T.BYTE
    if pa.types.is_int16(at):
        return T.SHORT
    if pa.types.is_int32(at):
        return T.INT
    if pa.types.is_int64(at):
        return T.LONG
    if pa.types.is_float32(at):
        return T.FLOAT
    if pa.types.is_float64(at):
        return T.DOUBLE
    if pa.types.is_string(at) or pa.types.is_large_string(at):
        return T.STRING
    if pa.types.is_binary(at) or pa.types.is_large_binary(at):
        return T.BINARY
    if pa.types.is_date32(at):
        return T.DATE
    if pa.types.is_timestamp(at):
        return T.TIMESTAMP
    if pa.types.is_decimal(at):
        if at.precision > T.DecimalType.MAX_PRECISION:
            raise TypeError(
                f"decimal precision {at.precision} > 18 not supported")
        return T.DecimalType(at.precision, at.scale)
    raise TypeError(f"unsupported arrow type {at}")


def arrow_schema_to_tpu(schema) -> T.StructType:
    return T.StructType(tuple(
        T.StructField(f.name, arrow_type_to_tpu(f.type), f.nullable)
        for f in schema
    ))


def _np_from_arrow_array(arr, dt: T.DataType) -> Tuple[np.ndarray, ...]:
    """(data, validity) or (offsets, chars, validity) numpy views."""
    import pyarrow as pa

    n = len(arr)
    validity = np.ones(n, bool) if arr.null_count == 0 else ~np.asarray(
        arr.is_null())
    if isinstance(dt, (T.StringType, T.BinaryType)):
        if pa.types.is_large_string(arr.type) or pa.types.is_large_binary(arr.type):
            arr = arr.cast(
                pa.string() if isinstance(dt, T.StringType) else pa.binary())
        # slice-safe: combine offsets relative to the slice start
        off_buf = arr.buffers()[1]
        data_buf = arr.buffers()[2]
        offsets = np.frombuffer(off_buf, np.int32,
                                n + 1 + arr.offset)[arr.offset:]
        chars_all = (
            np.frombuffer(data_buf, np.uint8) if data_buf is not None
            else np.zeros(0, np.uint8)
        )
        start = int(offsets[0])
        end = int(offsets[n])
        return (offsets - start, chars_all[start:end], validity)
    if isinstance(dt, T.TimestampType):
        import pyarrow as pa

        arr = arr.cast(pa.timestamp("us"))
        data = np.asarray(arr.view(pa.int64()))
        return (np.where(validity, data, 0).astype(np.int64), validity)
    if isinstance(dt, T.DateType):
        import pyarrow as pa

        data = np.asarray(arr.view(pa.int32()))
        return (np.where(validity, data, 0).astype(np.int32), validity)
    if isinstance(dt, T.DecimalType):
        data = _decimal_to_int64(arr, dt)
        return (np.where(validity, data, 0), validity)
    if isinstance(dt, T.BooleanType):
        data = np.asarray(arr.cast("bool").fill_null(False))
        return (data.astype(bool), validity)
    np_dt = np.dtype(dt.to_numpy())
    # fill_null avoids NaN poison in padding; cheap on host
    try:
        filled = arr.fill_null(0)
    except Exception:
        filled = arr
    data = np.asarray(filled).astype(np_dt, copy=False)
    return (data, validity)


def _decimal_to_int64(arr, dt: T.DecimalType) -> np.ndarray:
    """decimal128 -> unscaled int64 (precision <= 18 fits)."""
    import pyarrow as pa

    i128 = np.frombuffer(arr.buffers()[1], np.int64)
    lo = i128[0::2][arr.offset: arr.offset + len(arr)]
    return lo.copy()


_UNPACK_CACHE: dict = {}


def packed_upload(host_arrays: List[np.ndarray]):
    """Stage the buffers into ONE host buffer PER DTYPE, upload each in
    one transfer, and split device-side in ONE jitted program.

    Reference analog: the single HostMemoryBuffer the multi-file parquet
    reader stitches before one cudf upload (GpuParquetScan.scala:880-900) —
    per-buffer transfers pay the host link's per-dispatch latency once per
    dtype (a handful) instead of once per column chunk.

    Why per dtype and not one byte buffer: re-typing bytes on the device
    is a width-changing bitcast over a (rows, itemsize) view, and the
    TPU's tiled layouts pad that narrow minor dimension to a full lane
    tile — the v5e compiler sized a 14 MiB row-group unpack at 904 MiB
    of temporaries and took ~20 min over its u8->u16 leg. Buffers that
    already carry their dtype need only 1-D slices, on every backend."""
    import jax
    import jax.numpy as jnp

    from ..exec.base import cached_pipeline, phase, program

    # staging dtype -> elements staged so far; segments stay 128-byte
    # aligned inside their buffer. Bools ride the u8 buffer.
    sizes: dict = {}
    layout = []  # (staging dtype, element offset, length, dtype)
    for a in host_arrays:
        dt = np.dtype(np.uint8) if a.dtype == np.bool_ else a.dtype
        align = max(1, 128 // dt.itemsize)
        off = (sizes.get(dt.str, 0) + align - 1) // align * align
        layout.append((dt.str, off, a.shape[0], a.dtype.str))
        sizes[dt.str] = off + a.shape[0]
    order = sorted(sizes)
    from .. import faults as _faults
    from ..memory.retry import named_oom

    # the h2d boundary of whichever exec is open above (a scan, mostly):
    # staging + transfer, sized by the bytes that cross the link
    with phase("upload") as span:
        bufs = {ds: np.zeros(sizes[ds], np.dtype(ds)) for ds in order}
        for a, (ds, off, ln, _) in zip(host_arrays, layout):
            bufs[ds][off: off + ln] = a.reshape(-1).view(np.dtype(ds))
        nbytes = sum(b.nbytes for b in bufs.values())
        span.set(bytes=int(nbytes))
        if _faults.enabled():
            # injected host-link transfer failure (chaos testing)
            _faults.check("transfer", "packed_upload")
        with named_oom("packed_upload"):
            # the h2d staging transfers: a device allocation failure here
            # surfaces as TpuOutOfDeviceMemory naming the site + watermark
            devs = [jnp.asarray(bufs[ds]) for ds in order]
    from .. import events as _events

    if _events.enabled():
        _events.emit("transfer", direction="h2d", bytes=int(nbytes),
                     site="packed_upload")
    from .. import obs as _obs

    if _obs.enabled():
        # the dominant host-link direction: without it the live
        # transfer counters would show only d2h/fence
        _obs.inc("tpu_transfers", len(devs), direction="h2d")
        _obs.inc("tpu_transfer_bytes", int(nbytes), direction="h2d")

    key = tuple(layout)

    # NOTE: one unpack program per distinct (dtype, offset, length)
    # layout — ragged row-group layouts (e.g. per-group dictionary
    # sizes) each compile once, the same churn rate as the decode
    # programs keyed on the same lengths; the miss counter makes it
    # visible in explain_metrics() instead of silent
    def build():
        @program("upload_unpack")
        def unpack(*staged):
            outs = []
            with jax.named_scope("upload_unpack"):
                for ds, off, ln, dts in key:
                    seg = jax.lax.slice_in_dim(
                        staged[order.index(ds)], off, off + ln)
                    outs.append(
                        seg != 0 if np.dtype(dts) == np.bool_ else seg)
            return outs

        return jax.jit(unpack)


    with phase("unpack_dispatch"):
        fn = cached_pipeline(_UNPACK_CACHE, key, "upload_unpack", build)
        return fn(*devs)


def arrow_to_batch(table_or_rb, schema: Optional[T.StructType] = None,
                   capacity: Optional[int] = None) -> ColumnarBatch:
    """pyarrow Table/RecordBatch -> device ColumnarBatch: every buffer is
    staged into one pinned-style host buffer and crosses the host link in
    ONE transfer (capacity bucketed so XLA executables are shared)."""
    import pyarrow as pa

    if isinstance(table_or_rb, pa.Table):
        table_or_rb = table_or_rb.combine_chunks()
        arrays = [
            c.chunk(0) if c.num_chunks else pa.array([], type=c.type)
            for c in table_or_rb.columns
        ]
        a_schema = table_or_rb.schema
    else:
        arrays = table_or_rb.columns
        a_schema = table_or_rb.schema
    if schema is None:
        schema = arrow_schema_to_tpu(a_schema)
    n = table_or_rb.num_rows
    cap = capacity or choose_capacity(max(1, n))
    staged: List[np.ndarray] = []
    plans: List[tuple] = []  # per column: ("s", dt) | ("f", dt)
    for arr, f in zip(arrays, schema.fields):
        dt = f.dataType
        parts = _np_from_arrow_array(arr, dt)
        if len(parts) == 3:
            offsets, chars, validity = parts
            nb = int(offsets[n]) if n else 0
            ccap = choose_capacity(max(1, nb), 128)
            o = np.zeros(cap + 1, np.int32)
            o[: n + 1] = offsets[: n + 1]
            o[n + 1:] = nb
            ch = np.zeros(ccap, np.uint8)
            ch[:nb] = chars[:nb]
            v = np.zeros(cap, bool)
            v[:n] = validity
            staged.extend([o, ch, v])
            plans.append(("s", dt))
        else:
            data, validity = parts
            d = np.zeros(cap, data.dtype)
            d[:n] = np.where(validity, data, np.zeros(1, data.dtype))
            v = np.zeros(cap, bool)
            v[:n] = validity
            staged.extend([d, v])
            plans.append(("f", dt))
    devs = packed_upload(staged)
    cols: List[DeviceColumn] = []
    i = 0
    for kind, dt in plans:
        if kind == "s":
            o, ch, v = devs[i], devs[i + 1], devs[i + 2]
            i += 3
            cols.append(DeviceColumn(dt, n, None, v, offsets=o, chars=ch))
        else:
            d, v = devs[i], devs[i + 1]
            i += 2
            cols.append(DeviceColumn(dt, n, d, v))
    return ColumnarBatch(cols, schema, n)


def batch_to_arrow(batch: ColumnarBatch):
    """Device ColumnarBatch -> pyarrow Table (for writers / interop)."""
    import pyarrow as pa

    hosts = batch.host_columns()
    n = batch.num_rows
    arrays = []
    names = []
    for f, h in zip(batch.schema.fields, hosts):
        names.append(f.name)
        dt = f.dataType
        mask = ~h.validity[:n]
        if isinstance(dt, (T.StringType, T.BinaryType)):
            at = pa.string() if isinstance(dt, T.StringType) else pa.binary()
            arrays.append(pa.array(list(h.data[:n]), type=at))
        elif isinstance(dt, T.DateType):
            arrays.append(pa.array(
                h.data[:n].astype(np.int32), type=pa.int32(),
                mask=mask).cast(pa.date32()))
        elif isinstance(dt, T.TimestampType):
            arrays.append(pa.array(
                h.data[:n].astype(np.int64), type=pa.int64(),
                mask=mask).cast(pa.timestamp("us", tz="UTC")))
        elif isinstance(dt, T.DecimalType):
            # build from unscaled ints directly: a numeric int64->decimal128
            # cast both raises ('Precision is not great enough') and would
            # scale the value by 10^scale (advisor finding r2)
            import decimal as _d

            arrays.append(pa.array(
                [None if m else _d.Decimal(int(v)).scaleb(-dt.scale)
                 for v, m in zip(h.data[:n], mask)],
                type=pa.decimal128(dt.precision, dt.scale)))
        else:
            arrays.append(pa.array(h.data[:n], mask=mask))
    return pa.table(dict(zip(names, arrays)))
