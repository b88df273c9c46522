"""TPU-side parquet page decode vs the host arrow decoder.

Differential contract: for any file pyarrow can write, the device decode
path (io/parquet_device.py) must produce exactly what the host decode path
produces — same values, same nulls, same strings. Mirrors the reference's
parquet differential suites (parquet_test.py) for the decoder half."""
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.conf import RapidsConf
from spark_rapids_tpu.exec.scan import TpuFileSourceScanExec
from spark_rapids_tpu.io.parquet import ParquetScanner


def _collect(path, conf_dict):
    conf = RapidsConf(conf_dict)
    sc = ParquetScanner(path, conf)
    ex = TpuFileSourceScanExec(conf, sc, "parquet")
    rows = []
    for p in range(ex.num_partitions):
        for b in ex.execute_partition(p):
            rows.extend(b.to_rows())
    return rows


def _roundtrip(table, tmp_path, name="t.parquet", **write_kw):
    path = os.path.join(str(tmp_path), name)
    pq.write_table(table, path, **write_kw)
    on = _collect(path, {})
    off = _collect(
        path,
        {"spark.rapids.tpu.sql.format.parquet.deviceDecode.enabled": False},
    )
    assert on == off, (on[:5], off[:5])
    return on


def _used_device(path, conf_dict=None):
    conf = RapidsConf(conf_dict or {})
    sc = ParquetScanner(path, conf)
    dev, _ = sc.read_split_device(0)
    return dev is not None


def test_dictionary_int_columns(tmp_path):
    rng = np.random.default_rng(5)
    n = 50_000
    t = pa.table({
        "k32": pa.array(rng.integers(0, 50, n).astype(np.int32)),
        "k64": pa.array(rng.integers(0, 1000, n).astype(np.int64)),
    })
    path = os.path.join(str(tmp_path), "d.parquet")
    pq.write_table(t, path)
    assert _used_device(path)
    rows = _roundtrip(t, tmp_path)
    assert len(rows) == n
    assert rows[0] == (int(t["k32"][0]), int(t["k64"][0]))


def test_dictionary_double_and_float(tmp_path):
    rng = np.random.default_rng(6)
    n = 20_000
    vals = rng.choice(np.round(rng.normal(size=100), 3), n)
    t = pa.table({
        "d": pa.array(vals),
        "f": pa.array(vals.astype(np.float32)),
    })
    _roundtrip(t, tmp_path)


def test_nulls_dictionary(tmp_path):
    rng = np.random.default_rng(7)
    n = 30_000
    base = rng.integers(0, 20, n).astype(np.int64)
    mask = rng.random(n) < 0.3
    arr = pa.array(
        [None if m else int(v) for m, v in zip(mask, base)],
        type=pa.int64())
    t = pa.table({"x": arr})
    rows = _roundtrip(t, tmp_path)
    assert sum(1 for r in rows if r[0] is None) == int(mask.sum())


def test_plain_int_and_float(tmp_path):
    rng = np.random.default_rng(8)
    n = 20_000
    t = pa.table({
        "i32": pa.array(rng.integers(-(2**31), 2**31, n).astype(np.int32)),
        "i64": pa.array(rng.integers(-(2**62), 2**62, n)),
        "f32": pa.array(rng.normal(size=n).astype(np.float32)),
    })
    # near-unique values: pyarrow falls back to PLAIN after dict overflow
    path = os.path.join(str(tmp_path), "p.parquet")
    pq.write_table(t, path, use_dictionary=False)
    on = _collect(path, {})
    off = _collect(
        path,
        {"spark.rapids.tpu.sql.format.parquet.deviceDecode.enabled": False})
    assert on == off
    assert _used_device(path)


def test_plain_double_falls_back(tmp_path):
    rng = np.random.default_rng(9)
    t = pa.table({"d": pa.array(rng.normal(size=1000))})
    path = os.path.join(str(tmp_path), "pd.parquet")
    pq.write_table(t, path, use_dictionary=False)
    # f64 PLAIN can't bitcast on device: whole-split fallback, same rows
    assert not _used_device(path)
    _roundtrip(t, tmp_path, name="pd2.parquet", use_dictionary=False)


def test_string_dictionary(tmp_path):
    rng = np.random.default_rng(10)
    pool = ["alpha", "béta", "", "gamma-long-value", "δ"]
    n = 25_000
    vals = [pool[i] for i in rng.integers(0, len(pool), n)]
    mask = rng.random(n) < 0.1
    t = pa.table({
        "s": pa.array([None if m else v for m, v in zip(mask, vals)]),
        "v": pa.array(np.arange(n, dtype=np.int64) % 97),
    })
    path = os.path.join(str(tmp_path), "s.parquet")
    pq.write_table(t, path)
    assert _used_device(path)
    rows = _roundtrip(t, tmp_path, name="s2.parquet")
    assert rows[0][0] == (None if mask[0] else vals[0])


def test_multiple_row_groups_and_codecs(tmp_path):
    rng = np.random.default_rng(11)
    n = 40_000
    t = pa.table({
        "k": pa.array(rng.integers(0, 10, n).astype(np.int32)),
        "v": pa.array(rng.integers(0, 5, n).astype(np.int64)),
    })
    for codec in ("snappy", "zstd", "none"):
        _roundtrip(
            t, tmp_path, name=f"c_{codec}.parquet",
            compression=codec, row_group_size=7_000)


def test_data_page_v2(tmp_path):
    rng = np.random.default_rng(12)
    n = 15_000
    base = rng.integers(0, 30, n).astype(np.int64)
    mask = rng.random(n) < 0.2
    t = pa.table({
        "x": pa.array(
            [None if m else int(v) for m, v in zip(mask, base)],
            type=pa.int64()),
        "s": pa.array(
            [None if m else f"v{v % 7}" for m, v in zip(mask, base)]),
    })
    _roundtrip(t, tmp_path, name="v2.parquet", data_page_version="2.0")


def test_sorted_runs_rle_heavy(tmp_path):
    # sorted keys produce long RLE runs — exercises the RLE branch
    n = 30_000
    k = np.sort(np.random.default_rng(13).integers(0, 25, n)).astype(np.int32)
    t = pa.table({"k": pa.array(k)})
    _roundtrip(t, tmp_path, name="rle.parquet")


def test_all_null_column(tmp_path):
    t = pa.table({
        "x": pa.array([None] * 5000, type=pa.int32()),
        "y": pa.array(np.arange(5000, dtype=np.int32)),
    })
    rows = _roundtrip(t, tmp_path, name="an.parquet")
    assert all(r[0] is None for r in rows)


def test_through_session_aggregate(tmp_path):
    """End-to-end: session -> scan(device decode) -> filter -> aggregate,
    against the pandas oracle."""
    import pandas as pd

    from spark_rapids_tpu.expr import aggregates as A
    from spark_rapids_tpu.expr import expressions as E
    from spark_rapids_tpu.expr.expressions import col, lit
    from spark_rapids_tpu.sql import TpuSession

    rng = np.random.default_rng(14)
    n = 60_000
    t = pa.table({
        "k": pa.array(rng.integers(0, 12, n).astype(np.int32)),
        "a": pa.array(rng.integers(-50, 50, n).astype(np.int64)),
    })
    path = os.path.join(str(tmp_path), "q")
    os.makedirs(path)
    pq.write_table(t, os.path.join(path, "part0.parquet"),
                   row_group_size=16_000)
    sess = TpuSession({})
    res = (
        sess.read.parquet(path)
        .where(E.GreaterThanOrEqual(col("a"), lit(0)))
        .group_by("k")
        .agg(A.agg(A.Sum(col("a")), "s"), A.agg(A.Count(None), "c"))
        .collect())
    pdf = t.to_pandas()
    exp = pdf[pdf.a >= 0].groupby("k").agg(s=("a", "sum"), c=("a", "count"))
    got = {r[0]: (r[1], r[2]) for r in res}
    assert got == {k: (int(exp.loc[k, "s"]), int(exp.loc[k, "c"]))
                   for k in exp.index}


# ---------------------------------------------------------------------------
# round 14: streamed (tiled) fixed-width unpack + the unpack layout bound
# ---------------------------------------------------------------------------
def test_tiled_unpack_matches_flat_across_torture(tmp_path):
    """The tiled fori_loop unpack (bit-expand -> dictionary gather ->
    validity expand in one streamed program) must be bit-identical to
    the flat program over nullable/non-null, dict/plain, int32/int64
    chunks at several forced (non-divisor) tile sizes."""
    from spark_rapids_tpu.io import parquet_device as PD

    rng = np.random.default_rng(31)
    n = 3000
    table = pa.table({
        "di": pa.array(rng.integers(0, 40, n).astype(np.int32)),
        "dl": pa.array(rng.integers(0, 9, n).astype(np.int64)),
        "dn": pa.array([
            None if i % 7 == 0 else int(rng.integers(0, 12))
            for i in range(n)], type=pa.int32()),
        "pl": pa.array(rng.integers(-2 ** 62, 2 ** 62, n)),
    })
    path = os.path.join(str(tmp_path), "t.parquet")
    pq.write_table(table, path, use_dictionary=["di", "dl", "dn"])
    prev_tile, prev_on = PD.FORCE_UNPACK_TILE_ROWS, PD.TILED_UNPACK
    try:
        PD.TILED_UNPACK = False
        flat = _collect(path, {})
        PD.TILED_UNPACK = True
        for tile in (32, 96, 4096):
            PD.FORCE_UNPACK_TILE_ROWS = tile
            PD._DECODE_CACHE.clear()
            from spark_rapids_tpu.io.scan_cache import DeviceScanCache

            DeviceScanCache.get_instance(RapidsConf({})).invalidate_path(
                path)
            assert _collect(path, {}) == flat, tile
    finally:
        PD.FORCE_UNPACK_TILE_ROWS = prev_tile
        PD.TILED_UNPACK = prev_on
        PD._DECODE_CACHE.clear()


def test_tiled_unpack_program_classifies_radix_bin_not_scatter():
    """The streamed unpack writes its output through multi-element
    dynamic-update-slice tiles — the radix-bin idiom, zero scatters."""
    import jax
    from spark_rapids_tpu.hlo import summarize_hlo
    from spark_rapids_tpu.io import parquet_device as PD
    from spark_rapids_tpu.utils.bucketing import bucket_rows

    rng = np.random.default_rng(5)
    n = 200_000
    validity = rng.random(n) < 0.9
    plan = PD.ChunkPlan(phys="INT64", num_values=n, nullable=True)
    plan.validity = validity
    D = 64
    plan.dict_values = rng.integers(-10 ** 9, 10 ** 9, D).astype(np.int64)
    plan.codes = rng.integers(0, D, int(validity.sum())).astype(np.uint8)
    plan.n_present = int(validity.sum())
    cap = bucket_rows(n)
    args, key, run = PD.plan_decode(plan, T.LONG, cap)
    assert any(isinstance(k, tuple) and k and k[0] == "tile"
               for k in key), key
    dev = PD.stage_decode_args([args])[0]
    c = jax.jit(run).lower(dev).compile()
    s = summarize_hlo(c.as_text(), top_k=32)
    assert s["scatter_count"] == 0, s["top_fusions"]
    assert any(r["class"] == "radix-bin" for r in s["top_fusions"])


# ---------------------------------------------------------------------------
# PR 27: the two reads of a value by form (slice | gather, onehot | twolevel
# | gather), against the flat program
# ---------------------------------------------------------------------------
_TPU_T = {"INT32": T.INT, "INT64": T.LONG, "FLOAT": T.FLOAT,
          "DOUBLE": T.DOUBLE}
_FORM_DS = (1, 100, 129, 2400, 16384, 16385)


def _synthetic_chunk(phys, d, n, nulls, code_dt, seed=0):
    """A dictionary chunk of ``n`` rows made by hand: ``d`` values of
    random BIT PATTERNS (negative zero and NaN payloads among a FLOAT's),
    codes of ``code_dt`` and, where the code dtype can hold one, a
    malformed code ``>= d`` in the middle."""
    from spark_rapids_tpu.io import parquet_device as PD

    rng = np.random.default_rng(seed)
    dt = PD._PHYS_NP[phys]
    plan = PD.ChunkPlan(phys=phys, num_values=n, nullable=nulls)
    present = n
    if nulls:
        plan.validity = rng.random(n) < 0.8
        present = int(plan.validity.sum())
    bits = rng.integers(0, 2 ** 64, d, dtype=np.uint64)
    plan.dict_values = bits.astype(
        np.uint32 if dt.itemsize == 4 else np.uint64).view(dt).copy()
    if phys == "FLOAT" and d >= 4:
        plan.dict_values[:4] = np.array(
            [0x80000000, 0x7FC00001, 0xFFC12345, 0x7F800001],
            np.uint32).view(np.float32)
    plan.codes = rng.integers(0, d, present).astype(code_dt)
    bad = present // 2
    if d + 5 <= np.iinfo(code_dt).max:
        plan.codes[bad] = d + 5
    else:
        bad = None
    plan.n_present = present
    return plan, bad


def _decode(plan, phys, cap):
    import jax

    from spark_rapids_tpu.io import parquet_device as PD

    args, key, run = PD.plan_decode(plan, _TPU_T[phys], cap)
    data, validity = jax.jit(run)(PD.stage_decode_args([args])[0])
    return key, np.asarray(data), np.asarray(validity)


def _reads(key):
    return next(k for k in key if isinstance(k, tuple) and k[0] == "reads")


def _form_cases():
    for phys in ("INT32", "FLOAT", "INT64", "DOUBLE"):
        for nulls in (False, True):
            for code_dt in (np.uint8, np.uint16, np.int32):
                for d in _FORM_DS:
                    if d - 1 > np.iinfo(code_dt).max:
                        continue
                    yield pytest.param(
                        phys, nulls, code_dt, d,
                        id=f"{phys}-{'nulls' if nulls else 'full'}-"
                           f"{np.dtype(code_dt).name}-D{d}")


@pytest.mark.parametrize("phys,nulls,code_dt,d", list(_form_cases()))
def test_tiled_reads_match_flat_by_form(phys, nulls, code_dt, d):
    """Whatever form the two reads take, the streamed unpack returns the
    flat program's (data, validity) bit for bit: chunks without nulls and
    with them, every code dtype, 32- and 64-bit dictionaries on both sides
    of each threshold, ``n`` below the capacity and no multiple of the
    tile (a last partial tile, and trips past ``n``), a code ``>= D``."""
    from spark_rapids_tpu.io import parquet_device as PD
    from spark_rapids_tpu.utils.bucketing import bucket_rows

    n = 5000 + d % 7
    cap = bucket_rows(n)
    plan, bad = _synthetic_chunk(phys, d, n, nulls, code_dt, seed=d)
    prev_tile, prev_on = PD.FORCE_UNPACK_TILE_ROWS, PD.TILED_UNPACK
    try:
        PD.TILED_UNPACK = False
        flat_key, flat_data, flat_valid = _decode(plan, phys, cap)
        assert _reads(flat_key)[2] == "gather"
        PD.TILED_UNPACK = True
        for tile in (32, 96, 4096):
            PD.FORCE_UNPACK_TILE_ROWS = tile
            key, data, valid = _decode(plan, phys, cap)
            assert _reads(key) == (
                "reads", "gather" if nulls else "slice",
                PD.dict_read_form(phys, d)), key
            assert valid.tobytes() == flat_valid.tobytes(), tile
            assert data.dtype == flat_data.dtype
            assert data.tobytes() == flat_data.tobytes(), tile
    finally:
        PD.FORCE_UNPACK_TILE_ROWS = prev_tile
        PD.TILED_UNPACK = prev_on
    if bad is not None:
        # the malformed code reads the dictionary's last value
        row = (np.flatnonzero(plan.validity)[bad] if nulls else bad)
        assert (flat_data[row].tobytes()
                == plan.dict_values[d - 1].tobytes())


@pytest.mark.parametrize("d,form", [(100, "onehot"), (1024, "onehot"),
                                    (1025, "twolevel"), (16384, "twolevel"),
                                    (16385, "gather")])
@pytest.mark.parametrize("nulls", [False, True])
def test_tiled_reads_match_flat_at_the_derived_tile(d, form, nulls):
    """The same at a capacity that takes the streamed path by itself
    (>= 2^16), with the tile the look-up's form derives."""
    from spark_rapids_tpu.io import parquet_device as PD

    n, cap = 70_001, 1 << 17
    plan, _ = _synthetic_chunk("INT32", d, n, nulls, np.int32, seed=d)
    prev_on = PD.TILED_UNPACK
    try:
        PD.TILED_UNPACK = False
        _, flat_data, flat_valid = _decode(plan, "INT32", cap)
        PD.TILED_UNPACK = True
        key, data, valid = _decode(plan, "INT32", cap)
    finally:
        PD.TILED_UNPACK = prev_on
    assert _reads(key)[2] == form == PD.dict_read_form("INT32", d)
    tile = next(k for k in key if isinstance(k, tuple) and k[0] == "tile")[1]
    assert tile % 32 == 0 and tile == min(
        32768, PD._lookup_tile_rows(form, d, 4) or 32768)
    assert (data.tobytes(), valid.tobytes()) == (
        flat_data.tobytes(), flat_valid.tobytes())


def test_plain_chunks_read_by_slice_match_flat(tmp_path):
    """PLAIN INT32/FLOAT/INT64 chunks without nulls slice their values;
    with nulls they keep the gather. Both match the flat program."""
    from spark_rapids_tpu.io import parquet_device as PD

    rng = np.random.default_rng(27)
    n = 3001
    nulls = rng.random(n) < 0.2
    table = pa.table({
        "i": pa.array(rng.integers(-2 ** 31, 2 ** 31, n).astype(np.int32)),
        "f": pa.array(rng.normal(size=n).astype(np.float32)),
        "l": pa.array(rng.integers(-2 ** 62, 2 ** 62, n)),
        "ln": pa.array(rng.integers(-2 ** 62, 2 ** 62, n), mask=nulls),
    })
    path = os.path.join(str(tmp_path), "p.parquet")
    pq.write_table(table, path, use_dictionary=False)
    prev_tile, prev_on = PD.FORCE_UNPACK_TILE_ROWS, PD.TILED_UNPACK
    try:
        PD.TILED_UNPACK = False
        flat = _collect(path, {})
        PD.TILED_UNPACK = True
        for tile in (32, 96, 4096):
            PD.FORCE_UNPACK_TILE_ROWS = tile
            PD._DECODE_CACHE.clear()
            from spark_rapids_tpu.io.scan_cache import DeviceScanCache

            DeviceScanCache.get_instance(RapidsConf({})).invalidate_path(
                path)
            assert _collect(path, {}) == flat, tile
            forms = {k[1]: _reads(k) for k in PD._DECODE_CACHE}
            assert forms["INT32"] == forms["FLOAT"] == (
                "reads", "slice", "none")
            assert {_reads(k) for k in PD._DECODE_CACHE
                    if k[1] == "INT64"} == {("reads", "slice", "none"),
                                            ("reads", "gather", "none")}
    finally:
        PD.FORCE_UNPACK_TILE_ROWS = prev_tile
        PD.TILED_UNPACK = prev_on
        PD._DECODE_CACHE.clear()


def _gather_ops(text):
    from spark_rapids_tpu.hlo import parse_hlo_module

    mod = parse_hlo_module(text)
    return sum(ins.opcode == "gather" for comp in mod.computations
               for ins in mod.instrs(comp))


@pytest.mark.parametrize("nulls", [False, True])
def test_tiled_unpack_program_gathers_only_what_its_key_says(nulls):
    """A non-null INT32 dictionary chunk of 100 values at a capacity
    >= 2^16 compiles to a program with NO gather; the same chunk with
    nulls keeps exactly the code read's. The key's tag counts the same."""
    import jax
    from spark_rapids_tpu.hlo import summarize_hlo
    from spark_rapids_tpu.io import parquet_device as PD
    from spark_rapids_tpu.utils.bucketing import bucket_rows

    n = 70_000
    plan, _ = _synthetic_chunk("INT32", 100, n, nulls, np.uint8)
    cap = bucket_rows(n)
    assert cap >= PD.TILED_UNPACK_MIN_CAP
    args, key, run = PD.plan_decode(plan, T.INT, cap)
    assert _reads(key) == ("reads", "gather" if nulls else "slice",
                           "onehot")
    text = jax.jit(run).lower(
        PD.stage_decode_args([args])[0]).compile().as_text()
    s = summarize_hlo(text, top_k=64)
    assert not [r for r in s["top_fusions"] if r["class"] == "gather"]
    assert s["scatter_count"] == 0
    assert _gather_ops(text) == PD.key_gathers(key) == int(nulls)
    # the same chunk under a dictionary past both thresholds: one more
    long_plan, _ = _synthetic_chunk("INT32", PD.TWOLEVEL_MAX_D + 1, n,
                                    nulls, np.int32)
    args, key, run = PD.plan_decode(long_plan, T.INT, cap)
    text = jax.jit(run).lower(
        PD.stage_decode_args([args])[0]).compile().as_text()
    assert _gather_ops(text) == PD.key_gathers(key) == 1 + int(nulls)


def test_decode_spans_carry_the_gathers_their_keys_count(tmp_path,
                                                         monkeypatch):
    """``TpuFileSourceScanExec.decode_dispatch`` (a program a column) and
    ``TpuHashAggregateExec.stage`` (the fused stage, served-from-cache row
    groups included) carry ``gathers``: the sum of ``key_gathers`` over the
    chunks whose programs they dispatch or splice."""
    import jax

    from spark_rapids_tpu.expr import aggregates as A
    from spark_rapids_tpu.expr.expressions import col
    from spark_rapids_tpu.io import parquet_device as PD
    from spark_rapids_tpu.sql import TpuSession

    rng = np.random.default_rng(28)
    n = 70_000  # capacity 2^17: the streamed path by itself
    mask = rng.random(n) < 0.1
    table = pa.table({
        # one-hot, by slice: 0 gathers
        "q": pa.array(rng.integers(1, 101, n).astype(np.int32)),
        # DOUBLE dictionary: 1
        "w": pa.array(rng.integers(0, 300, n) / 4.0),
        # nulls, one-hot: 1 (the code read)
        "d": pa.array(rng.integers(0, 50, n).astype(np.int32), mask=mask),
    })
    directory = os.path.join(str(tmp_path), "t")
    os.makedirs(directory)
    pq.write_table(table, os.path.join(directory, "t.parquet"),
                   row_group_size=35_000)
    seen = []

    class Records:
        def __init__(self, name, **counts):
            self.name, self.counts = name, dict(counts)
            seen.append(self)

        def set_metadata(self, **counts):
            self.counts.update(counts)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Records)
    keys = []
    real = PD.plan_decode

    def spy(*args, **kwargs):
        out = real(*args, **kwargs)
        keys.append(out[1])
        return out

    monkeypatch.setattr(PD, "plan_decode", spy)
    conf = {"spark.rapids.tpu.sql.trace.enabled": True,
            "spark.rapids.tpu.sql.variableFloatAgg.enabled": True}

    def gathers(section):
        return [a.counts["gathers"] for a in seen
                if a.name.endswith("." + section) and "gathers" in a.counts]

    def query(fusion):
        sess = TpuSession(dict(conf, **{
            "spark.rapids.tpu.sql.stageFusion": fusion}))
        return (sess.read.parquet(directory).group_by("q")
                .agg(A.agg(A.Sum(col("w")), "s"),
                     A.agg(A.Count(col("d")), "c")).collect())

    assert len(query("OFF")) == 100
    assert len(keys) == 6 and sum(map(PD.key_gathers, keys)) == 4
    assert gathers("decode_dispatch") == [2, 2]  # a row group each
    first = query("ON")
    assert gathers("stage") == [4]
    assert sum(map(PD.key_gathers, keys[6:])) == 4
    # every row group from the scan cache: the same programs are spliced
    planned = len(keys)
    assert query("ON") == first and len(keys) == planned
    assert gathers("stage") == [4, 4]


def test_parquet_scan_footprint_and_predict_exec_hbm(tmp_path):
    """The unpack site finally has a layout bound: predict_exec_hbm over
    a live parquet scan tree is non-null (uploaded payloads + decoded
    planes from the footers), so a parquet scan's byte amplification
    has a denominator."""
    from spark_rapids_tpu.plugin.plananalysis import (
        parquet_scan_footprint,
        predict_exec_hbm,
    )

    rng = np.random.default_rng(7)
    n = 4000
    table = pa.table({
        "k": pa.array(rng.integers(0, 16, n).astype(np.int32)),
        "v": pa.array(rng.integers(0, 999, n).astype(np.int64)),
    })
    path = os.path.join(str(tmp_path), "t.parquet")
    pq.write_table(table, path, row_group_size=1024)
    conf = RapidsConf({})
    sc = ParquetScanner(path, conf)
    ex = TpuFileSourceScanExec(conf, sc, "parquet")
    fp = parquet_scan_footprint(sc, ex.output_schema)
    assert fp is not None and fp["nrg"] == 4
    assert fp["decoded"] > 0 and fp["upload_total"] > 0
    bound = predict_exec_hbm(ex)
    assert bound is not None
    assert bound == 2 * (fp["decoded"] + fp["upload_total"])
    # and a non-parquet-boundable tree still degrades to None
    from spark_rapids_tpu.io.csv import CsvScanner

    csv_path = os.path.join(str(tmp_path), "t.csv")
    with open(csv_path, "w") as f:
        f.write("a,b\n1,2\n3,4\n")
    csv_ex = TpuFileSourceScanExec(
        conf, CsvScanner(csv_path, conf), "csv")
    assert predict_exec_hbm(csv_ex) is None
