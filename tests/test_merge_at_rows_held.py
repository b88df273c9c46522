"""The synced merge of an aggregate's partials (``exec/aggregate._merge``),
which every merge of two or more partials takes on the CPU backend: one
pull of the partials' row counts, then ONE program that cuts each partial
to the bucket of the rows it holds, expands its dictionary keys there and
splices the parts. Held to the merge it replaced, kept here as the
reference: every dictionary key expanded at the partial's capacity, the
row counts and string byte lengths pulled, the parts spliced one eager
operation at a time. The rows, their order and the float sums must be the
reference's to the bit, and the merge span's ``slots`` and ``cut`` what the
partials' group counts give.

A second part holds the program's cache: process-wide, keyed by structure,
so a query's merge compiles nothing that an earlier query's plan compiled,
dispatches one program whatever its partial count, and keeps no plan
alive."""
import gc
import random

import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import ColumnarBatch
from spark_rapids_tpu.columnar.batch import schema_of
from spark_rapids_tpu.columnar.column import (
    choose_capacity,
    column_from_pylist,
    dict_column_from_pylist,
)
from spark_rapids_tpu.conf import RapidsConf
from spark_rapids_tpu.exec import InMemoryScanExec, TpuHashAggregateExec
from spark_rapids_tpu.exec import aggregate as XA
from spark_rapids_tpu.expr import aggregates as A
from spark_rapids_tpu.expr import expressions as E
from spark_rapids_tpu.expr.expressions import col

CONF = RapidsConf({"spark.rapids.tpu.sql.variableFloatAgg.enabled": True})
SCHEMA = schema_of(s=T.STRING, i=T.INT, v=T.DOUBLE, q=T.LONG)
#: strings of 0 to 23 bytes, multibyte UTF-8 among them
POOL = ["A", "N", "R", "", "alpha-001", "üñé-mixed", "  pad  ",
        "delta verylong-value-42", "X", "tail-9", "a.b.c", "Gamma%_x"]
#: the group keys of each kind of merge
KEYS = {"dict": ("s",), "plain": ("s",), "dict_int": ("s", "i"),
        "fixed": ("i",)}


class _Span:
    """What the merge span is handed, with tracing on."""

    on = True

    def __init__(self):
        self.counts = {}

    def set(self, **counts):
        self.counts.update(counts)


def _rows(rng, n, pool, null=False):
    rows = {"s": [rng.choice(pool) for _ in range(n)],
            "i": [rng.randrange(4) for _ in range(n)],
            "v": [rng.uniform(-1e3, 1e3) for _ in range(n)],
            "q": [rng.randrange(-10**12, 10**12) for _ in range(n)]}
    if null and n:
        rows["s"][0] = rows["i"][0] = None
    return rows


def _inputs(kind, nparts, seed):
    """The input batch of each partial and the group count it will hold.
    The first batch has a null in its keys, the second (of three or more)
    holds no row, and the last holds as many groups as it has slots, so
    that the merge takes it whole; the others draw their strings from
    pools of different sizes, so their dictionaries differ in size."""
    rng = random.Random(seed)
    batches, groups = [], []
    for p in range(nparts):
        full = p == nparts - 1
        if full:
            n = 128
            rows = _rows(rng, n, POOL)
            rows["s"] = [f"full-{j:03d}-" + "x" * (j % 9) for j in range(n)]
            rows["i"] = list(range(n))
        else:
            n = (0 if (p == 1 and nparts >= 3) else 600 if p == 0
                 else rng.choice((40, 130, 300, 600)))
            rows = _rows(rng, n, POOL[:3 + p % 9], null=p == 0)
        s = (dict_column_from_pylist(rows["s"], T.STRING)
             if kind.startswith("dict") else
             column_from_pylist(rows["s"], T.STRING))
        batches.append(ColumnarBatch(
            [s, column_from_pylist(rows["i"], T.INT),
             column_from_pylist(rows["v"], T.DOUBLE),
             column_from_pylist(rows["q"], T.LONG)], SCHEMA, n))
        keys = list(zip(*(rows[k] for k in KEYS[kind])))
        groups.append(len(set(keys)))
    return batches, groups


def _aggregate(kind, batches):
    return TpuHashAggregateExec(
        CONF, [col(k) for k in KEYS[kind]],
        [A.agg(A.Sum(col("v")), "sv"), A.agg(A.Count(col("q")), "cq"),
         A.agg(A.Average(col("v")), "av"), A.agg(A.Min(col("q")), "mq"),
         A.agg(A.Max(col("v")), "xv")],
        InMemoryScanExec(CONF, [batches], SCHEMA), mode=A.PARTIAL)


def _reference_merge(agg, partials):
    """The merge as it was: dictionary keys expanded at capacity, one pull
    of the row counts and byte lengths, one eager splice a part and plane."""
    from spark_rapids_tpu.exec.base import (
        batch_from_vals, host_pull, materialized_batch, vals_of_batch)
    from spark_rapids_tpu.ops import concat as concat_ops

    partials = [materialized_batch(b) for b in partials]
    str_cols = [j for j, f in enumerate(agg._buffer_schema.fields)
                if isinstance(f.dataType, T.StringType)]
    nb = len(partials)
    head = [b.num_rows_lazy for b in partials]
    for b in partials:
        for j in str_cols:
            c = b.columns[j]
            nr = b.num_rows_lazy
            head.append(c.offsets[min(nr, c.offsets.shape[0] - 1)
                                  if isinstance(nr, int) else nr])
    pulled = [int(x) for x in host_pull(head)]
    lengths, ns = pulled[:nb], len(str_cols)
    byte_lengths = [pulled[nb + i * ns:nb + (i + 1) * ns] for i in range(nb)]
    out_cap = choose_capacity(sum(lengths), CONF.shape_bucket_min)
    char_caps = [choose_capacity(max(1, sum(bl[k] for bl in byte_lengths)),
                                 128) for k in range(ns)]
    cols, n = concat_ops.concat_batches_cols(
        [vals_of_batch(b) for b in partials], lengths, byte_lengths,
        out_cap, char_caps)
    merged_in = batch_from_vals(cols, agg._buffer_schema, n)
    nk = len(agg._key_fields)
    saved = agg._bound_keys
    agg._bound_keys = [E.BoundReference(i, f.dataType, f.nullable)
                       for i, f in enumerate(agg._key_fields)]
    try:
        return agg._run_batch(merged_in, agg._merge_ops, [
            E.BoundReference(nk + j, f.dataType, True)
            for j, f in enumerate(agg._buf_fields)])
    finally:
        agg._bound_keys = saved


@pytest.mark.parametrize("nparts", [2, 3, 13, 29])
@pytest.mark.parametrize("kind", sorted(KEYS))
def test_merge_at_the_rows_held_is_the_old_merge_to_the_bit(kind, nparts):
    batches, groups = _inputs(kind, nparts, seed=nparts * 7 + len(kind))
    agg = _aggregate(kind, batches)
    partials = [agg._run_batch(b, agg._update_ops, agg._update_exprs)
                for b in batches]
    caps = [b.capacity for b in partials]
    if kind.startswith("dict"):
        assert all(c.is_dict for c in (b.columns[0] for b in partials))
        assert len({b.columns[0].dictv.dict_size for b in partials}) > 1
    want = _reference_merge(agg, list(partials)).to_rows()
    span = _Span()
    got = agg._merge(list(partials), span).to_rows()
    assert got == want  # rows, order, float bits
    assert len(got) == len({r[:len(KEYS[kind])] for r in got})
    # the merge takes each partial that holds a row at the bucket of its
    # groups, or whole where they fill it; the full last one is not cut
    takes = [min(c, choose_capacity(g, CONF.shape_bucket_min))
             for c, g in zip(caps, groups) if g]
    assert caps[-1] == groups[-1] == 128
    assert span.counts == {
        "slots": sum(takes),
        "cut": sum(t < c for t, c in zip(
            takes, [c for c, g in zip(caps, groups) if g]))}
    assert span.counts["cut"] >= 1


# ---------------------------------------------------------------------------
# the program's cache
# ---------------------------------------------------------------------------
def _write(directory, row_groups):
    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = random.Random(row_groups)
    n = 700 * row_groups
    table = pa.table({
        "s": pa.array([rng.choice(POOL[:5]) for _ in range(n)]),
        "v": pa.array([rng.uniform(0, 100) for _ in range(n)]),
    })
    pq.write_table(table, str(directory / "t.parquet"), row_group_size=700)
    return str(directory)


@pytest.mark.parametrize("row_groups", [2, 5])
def test_a_later_querys_merge_compiles_nothing_and_keeps_no_plan(
        row_groups, tmp_path, monkeypatch):
    from spark_rapids_tpu.exec.base import TpuExec, compile_snapshot
    from spark_rapids_tpu.sql import TpuSession

    directory = _write(tmp_path, row_groups)
    sess = TpuSession({"spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
                       "spark.rapids.tpu.sql.stageFusion": "OFF"})
    dispatched = []
    real = XA._merge_concat

    def counted(*key):
        fn = real(*key)

        def dispatch(parts, counts):
            dispatched.append(len(parts))
            return fn(parts, counts)

        return dispatch

    monkeypatch.setattr(XA, "_merge_concat", counted)

    def run():
        return (sess.read.parquet(directory).group_by("s")
                .agg(A.agg(A.Sum(col("v")), "sv")).order_by("s").collect())

    first = run()
    assert dispatched == [row_groups]
    _, before = compile_snapshot()
    assert run() == first
    _, after = compile_snapshot()
    assert {site: n - before.get(site, 0) for site, n in after.items()
            if n != before.get(site, 0)} == {}
    assert dispatched == [row_groups] * 2  # one program a merge
    # the program closes over its key, not over an exec of the plan
    for fn in XA._MERGE_CACHE.values():
        cells = [c.cell_contents for c in fn.__wrapped__.__closure__ or ()]
        assert not any(isinstance(c, TpuExec) for c in cells), cells
    def live():
        gc.collect()
        return sum(isinstance(o, TpuHashAggregateExec)
                   for o in gc.get_objects())

    before = live()
    for _ in range(3):
        run()
    assert live() <= before  # the last plan's, as before
