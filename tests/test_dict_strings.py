"""Dict-encoded string columns (late materialization): every string op
through BOTH the dict path and forced materialization, diffed against the
CPU oracle — plus the exec seams (group-by on codes, exchange, concat)
and the full session round trip.

The toggle is ``columnar.column.DICT_MATERIALIZE_EAGERLY`` (monkeypatched
per test): when set, dict columns expand to the plain Arrow layout before
entering any traced program, so the same query exercises the non-dict
lowering — results must be identical bit for bit.
"""
import random

import numpy as np
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import ColumnarBatch
from spark_rapids_tpu.columnar import column as colmod
from spark_rapids_tpu.columnar.batch import schema_of
from spark_rapids_tpu.columnar.column import (
    column_from_pylist,
    dict_column_from_pylist,
)
from spark_rapids_tpu.conf import RapidsConf
from spark_rapids_tpu.cpu import eval_expression_rows
from spark_rapids_tpu.exec import (
    InMemoryScanExec,
    TpuFilterExec,
    TpuHashAggregateExec,
    TpuProjectExec,
)
from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
from spark_rapids_tpu.expr import aggregates as A
from spark_rapids_tpu.expr import bind_references, evaluate_projection
from spark_rapids_tpu.expr import expressions as E
from spark_rapids_tpu.expr.expressions import col, lit
from spark_rapids_tpu.shuffle.partition import HashPartitioning

from data_gen import approx_equal

CONF = RapidsConf()
N = 96

# low-cardinality pool — the shape dictionary encoding exists for; mixes
# empties, case, pattern metacharacters, multibyte UTF-8, pads, numerics
POOL = [
    "alpha-001", "beta-smallX", "", "Gamma%_x", "delta verylong-value-42",
    "üñé-mixed", "a.b.c", "  pad  ", "X", "tail-9", "42", "-7",
]

SCHEMA = schema_of(s=T.STRING, t=T.STRING)


def make_rows(seed=0, n=N, null_prob=0.15):
    rng = random.Random(seed)
    gen = lambda: (None if rng.random() < null_prob else rng.choice(POOL))
    return [gen() for _ in range(n)], [gen() for _ in range(n)]


def make_dict_batch(seed=0, n=N, null_prob=0.15):
    """Batch with 's' DICT-encoded and 't' plain — the mixed layout every
    multi-input op must cope with."""
    s, t = make_rows(seed, n, null_prob)
    cols = [dict_column_from_pylist(s, T.STRING),
            column_from_pylist(t, T.STRING)]
    return ColumnarBatch(cols, SCHEMA, n), s, t


@pytest.fixture(params=["dict", "materialized"])
def dict_mode(request, monkeypatch):
    """Run the test body twice: once on the dict lowering, once with the
    forced-materialization toggle flipped (the fallback path)."""
    monkeypatch.setattr(colmod, "DICT_MATERIALIZE_EAGERLY",
                        request.param == "materialized")
    return request.param


def check_dict(expr, seed=0, null_prob=0.15):
    batch, s, t = make_dict_batch(seed, null_prob=null_prob)
    bound = bind_references(expr, SCHEMA)
    [tpu_col] = evaluate_projection([bound], batch)
    tpu_vals = tpu_col.to_pylist()
    rows = list(zip(s, t))
    cpu_vals = eval_expression_rows(bound, rows)
    assert len(tpu_vals) == len(cpu_vals)
    for i, (tv, cv) in enumerate(zip(tpu_vals, cpu_vals)):
        assert approx_equal(tv, cv), (
            f"row {i}: tpu={tv!r} cpu={cv!r} expr={expr} inputs={rows[i]!r}"
        )


# ---------------------------------------------------------------------------
# every string op, dict path vs forced materialization vs CPU oracle
# ---------------------------------------------------------------------------
STRING_OPS = [
    ("upper", lambda: E.Upper(col("s"))),
    ("lower", lambda: E.Lower(col("s"))),
    ("initcap", lambda: E.InitCap(col("s"))),
    ("length", lambda: E.Length(col("s"))),
    ("substring", lambda: E.Substring(col("s"), lit(2), lit(3))),
    ("substring_neg", lambda: E.Substring(col("s"), lit(-4), lit(3))),
    ("trim", lambda: E.StringTrim(col("s"))),
    ("ltrim", lambda: E.StringTrimLeft(col("s"))),
    ("rtrim", lambda: E.StringTrimRight(col("s"))),
    ("startswith", lambda: E.StartsWith(col("s"), lit("a"))),
    ("endswith", lambda: E.EndsWith(col("s"), lit("1"))),
    ("contains", lambda: E.Contains(col("s"), lit("X"))),
    ("like", lambda: E.Like(col("s"), lit("%a%1%"))),
    ("like_underscore", lambda: E.Like(col("s"), lit("_ail-_"))),
    ("like_exact", lambda: E.Like(col("s"), lit("X"))),
    ("rlike", lambda: E.RLike(col("s"), lit("a.b"))),
    ("regexp_replace", lambda: E.RegExpReplace(col("s"), lit("a"), lit("_Q_"))),
    ("replace", lambda: E.StringReplace(col("s"), lit("a"), lit("zzz"))),
    ("replace_empty", lambda: E.StringReplace(col("s"), lit(""), lit("zz"))),
    ("locate", lambda: E.StringLocate(lit("a"), col("s"), lit(1))),
    ("locate_null_start",
     lambda: E.StringLocate(lit("a"), col("s"), lit(None))),
    ("lpad", lambda: E.StringLPad(col("s"), lit(8), lit("*"))),
    ("rpad", lambda: E.StringRPad(col("s"), lit(8), lit("*"))),
    ("substring_index", lambda: E.SubstringIndex(col("s"), lit("-"), lit(1))),
    ("split_part", lambda: E.StringSplitPart(col("s"), lit("-"), lit(2))),
    ("eq_lit", lambda: E.EqualTo(col("s"), lit("alpha-001"))),
    ("eq_null_safe_lit", lambda: E.EqualNullSafe(col("s"), lit("X"))),
    ("eq_null_safe_null",
     lambda: E.EqualNullSafe(col("s"), E.Literal(None, T.STRING))),
    ("lt_lit", lambda: E.LessThan(col("s"), lit("delta"))),
    ("ge_lit_flipped", lambda: E.GreaterThanOrEqual(lit("delta"), col("s"))),
    ("cmp_dict_vs_plain", lambda: E.LessThanOrEqual(col("s"), col("t"))),
    ("in_list", lambda: E.In(col("s"), ("X", "üñé-mixed", "", "nope"))),
    ("in_list_null", lambda: E.In(col("s"), ("42", None))),
    ("cast_int", lambda: E.Cast(col("s"), T.INT)),
    ("cast_string_identity", lambda: E.Cast(col("s"), T.STRING)),
    ("concat_mixed", lambda: E.Concat((col("s"), lit("-"), col("t")))),
    ("concat_dict_dict", lambda: E.Concat((col("s"), col("s")))),
    ("if_mixed",
     lambda: E.If(E.Contains(col("s"), lit("a")), col("s"), col("t"))),
    ("coalesce", lambda: E.Coalesce((col("s"), col("t")))),
]


@pytest.mark.parametrize(
    "make", [m for _, m in STRING_OPS], ids=[k for k, _ in STRING_OPS])
def test_string_op_dict_vs_oracle(make, dict_mode):
    check_dict(make(), seed=7)


def test_all_null_dict_column(dict_mode):
    check_dict(E.Upper(col("s")), seed=11, null_prob=1.0)
    check_dict(E.EqualTo(col("s"), lit("X")), seed=12, null_prob=1.0)


# ---------------------------------------------------------------------------
# column layer: materialize() / host decode round trips
# ---------------------------------------------------------------------------
def test_dict_column_roundtrip_and_materialize():
    s, _ = make_rows(seed=3)
    dc = dict_column_from_pylist(s, T.STRING)
    assert dc.is_dict and dc.is_string
    assert dc.to_pylist() == s
    mat = dc.materialize()
    assert not mat.is_dict
    assert mat.to_pylist() == s
    # host_columns path on a dict batch (the collect fast path)
    batch = ColumnarBatch([dc], schema_of(s=T.STRING), len(s))
    assert [r[0] for r in batch.to_rows()] == s


def test_dict_device_memory_is_codes_not_chars():
    # 10k rows over a tiny pool: the dict layout must account ~4B/row,
    # not the expanded byte pool
    s = [POOL[i % 4] for i in range(10_000)]
    dc = dict_column_from_pylist(s, T.STRING)
    plain = dc.materialize()
    assert dc.device_memory_size() < plain.device_memory_size() / 2


# ---------------------------------------------------------------------------
# exec seams
# ---------------------------------------------------------------------------
def _groupby_oracle(keys, vals):
    out = {}
    for k, v in zip(keys, vals):
        c, s = out.get(k, (0, 0))
        out[k] = (c + 1, s + (v or 0))
    return sorted((k, c, s) for k, (c, s) in out.items())


def test_groupby_on_dict_key(dict_mode):
    n = 128
    rng = random.Random(21)
    keys = [rng.choice(POOL[:6]) for _ in range(n)]
    vals = list(range(n))
    kcol = dict_column_from_pylist(keys, T.STRING)
    vcol = column_from_pylist(vals, T.LONG)
    schema = schema_of(k=T.STRING, v=T.LONG)
    batch = ColumnarBatch([kcol, vcol], schema, n)
    agg = TpuHashAggregateExec(
        CONF, [col("k")],
        [A.agg(A.Count(col("v")), "c"), A.agg(A.Sum(col("v")), "sv")],
        InMemoryScanExec(CONF, [[batch]], schema))
    rows = sorted((k, c, s) for k, c, s in agg.collect())
    assert rows == _groupby_oracle(keys, vals)


def test_groupby_on_transformed_dict_key(dict_mode):
    # upper() clears the unique bit (entries can merge): grouping must
    # fall back to byte order and still agree with the oracle
    n = 96
    rng = random.Random(22)
    keys = [rng.choice(["ab", "AB", "aB", "c", ""]) for _ in range(n)]
    vals = [rng.randrange(100) for _ in range(n)]
    schema = schema_of(k=T.STRING, v=T.LONG)
    batch = ColumnarBatch(
        [dict_column_from_pylist(keys, T.STRING),
         column_from_pylist(vals, T.LONG)], schema, n)
    proj = TpuProjectExec(
        CONF, [E.Alias(E.Upper(col("k")), "k"), col("v")],
        InMemoryScanExec(CONF, [[batch]], schema))
    agg = TpuHashAggregateExec(
        CONF, [col("k")],
        [A.agg(A.Count(col("v")), "c"), A.agg(A.Sum(col("v")), "sv")], proj)
    rows = sorted(agg.collect())
    assert rows == _groupby_oracle([k.upper() for k in keys], vals)


def test_filter_project_keeps_dict_then_collects(dict_mode):
    batch, s, t = make_dict_batch(seed=31)
    filt = TpuFilterExec(
        CONF, E.Contains(col("s"), lit("a")),
        InMemoryScanExec(CONF, [[batch]], SCHEMA))
    proj = TpuProjectExec(
        CONF,
        [E.Alias(E.Substring(E.Upper(col("s")), lit(1), lit(6)), "u"),
         E.Alias(E.Length(col("s")), "ln")], filt)
    expect = [(sv.upper()[:6], len(sv)) for sv in s
              if sv is not None and "a" in sv]
    assert proj.collect() == expect


@pytest.mark.parametrize("slots", [None, 2048])
def test_dict_key_through_exchange(dict_mode, slots):
    # ``slots``: the batch's capacity where it is not the bucket of its
    # rows (the map side then cuts the codes to the bucket BEFORE the
    # dictionary is expanded), with the count a device scalar
    import jax.numpy as jnp

    from spark_rapids_tpu.columnar.column import HostColumn

    n = 120
    rng = random.Random(41)
    keys = [rng.choice(POOL[:5]) for _ in range(n)]
    vals = [rng.randrange(1000) for _ in range(n)]
    schema = schema_of(k=T.STRING, v=T.LONG)
    batch = ColumnarBatch(
        [dict_column_from_pylist(keys, T.STRING, capacity=slots),
         HostColumn.from_pylist(vals, T.LONG).to_device(slots)],
        schema, jnp.int32(n) if slots else n)
    P = 4
    ex = TpuShuffleExchangeExec(
        CONF, InMemoryScanExec(CONF, [[batch]], schema),
        HashPartitioning([0], P))
    got = []
    seen_parts = 0
    for p in range(P):
        part_rows = [r for b in ex.execute_partition(p)
                     for r in b.to_rows()]
        # same key lands in ONE partition (grouping correctness)
        seen_parts += bool(part_rows)
        got.extend(part_rows)
    assert sorted(got) == sorted(zip(keys, vals))
    assert seen_parts >= 2  # the hash actually spread the 5 keys


def test_mixed_dict_plain_concat_exec(dict_mode):
    # two batches of the SAME column, one dict-encoded and one plain,
    # through a coalescing exec boundary (different dictionaries per
    # batch is the general case — plain is the extreme of it)
    s1, _ = make_rows(seed=51, n=40)
    s2, _ = make_rows(seed=52, n=24)
    schema = schema_of(s=T.STRING)
    b1 = ColumnarBatch([dict_column_from_pylist(s1, T.STRING)], schema, 40)
    b2 = ColumnarBatch([column_from_pylist(s2, T.STRING)], schema, 24)
    from spark_rapids_tpu.exec import TpuCoalesceBatchesExec

    co = TpuCoalesceBatchesExec(
        CONF, InMemoryScanExec(CONF, [[b1, b2]], schema), target_rows=1000)
    assert [r[0] for r in co.collect()] == s1 + s2


# ---------------------------------------------------------------------------
# session round trip: scan -> filter -> project -> groupby -> collect
# ---------------------------------------------------------------------------
def _session_query(tmp_path, dict_strings: bool):
    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu.io.scan_cache import DeviceScanCache
    from spark_rapids_tpu.sql import TpuSession

    DeviceScanCache.reset()
    rng = random.Random(61)
    n = 500
    cats = [rng.choice(POOL[:8]) for _ in range(n)]
    qty = [rng.randrange(1, 50) for _ in range(n)]
    path = str(tmp_path / "t.parquet")
    pq.write_table(
        pa.table({"cat": pa.array(cats), "qty": pa.array(qty, pa.int64())}),
        path, use_dictionary=True)
    sess = TpuSession({
        "spark.rapids.tpu.sql.format.parquet.dictStrings.enabled":
            dict_strings,
    })
    df = (
        sess.read.parquet(str(tmp_path))
        .where(E.Contains(col("cat"), lit("a")))
        .group_by("cat")
        .agg(A.agg(A.Sum(col("qty")), "s"), A.agg(A.Count(col("qty")), "c"))
    )
    rows = sorted(df.collect())
    oracle = {}
    for c, q in zip(cats, qty):
        if "a" in c:
            s_, n_ = oracle.get(c, (0, 0))
            oracle[c] = (s_ + q, n_ + 1)
    assert rows == sorted((k, s_, n_) for k, (s_, n_) in oracle.items())
    return rows


def test_session_roundtrip_dict_vs_plain(tmp_path):
    on = _session_query(tmp_path, True)
    off = _session_query(tmp_path, False)
    assert on == off
