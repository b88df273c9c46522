"""Native runtime library tests: the C++ LZ4 block codec behind the
shuffle serializer SPI (reference: NvcompLZ4CompressionCodec behind
TableCompressionCodec; SURVEY §2.12 item 4)."""
import os
import random

import pytest

from spark_rapids_tpu import native

pytestmark = pytest.mark.skipif(
    not native.available(),
    reason=f"native toolchain unavailable: {native.load_error()}")


def test_lz4_round_trip_patterns():
    cases = [
        b"",
        b"a",
        b"hello world " * 1000,
        bytes(range(256)) * 64,
        b"\x00" * 100_000,
        os.urandom(50_000),  # incompressible
        b"abcabcabcabc" + os.urandom(17) + b"zzzzzzzzzzzzzzzzzzzzz",
    ]
    for raw in cases:
        comp = native.lz4_compress(raw)
        back = native.lz4_decompress(comp, len(raw))
        assert back == raw, f"round trip failed for {raw[:20]!r}..."


def test_lz4_compresses_redundant_data():
    raw = (b"spark-rapids-tpu " * 5000)
    comp = native.lz4_compress(raw)
    assert len(comp) < len(raw) // 10


def test_lz4_fuzz_round_trip():
    rng = random.Random(7)
    for _ in range(40):
        n = rng.randint(0, 20000)
        # mixed compressibility: runs + random
        raw = b"".join(
            bytes([rng.randint(0, 255)]) * rng.randint(1, 50)
            if rng.random() < 0.5 else os.urandom(rng.randint(1, 50))
            for _ in range(n // 25 + 1)
        )[:n]
        comp = native.lz4_compress(raw)
        assert native.lz4_decompress(comp, len(raw)) == raw


def test_lz4_rejects_corrupt_payload():
    comp = native.lz4_compress(b"hello world, hello world, hello world")
    with pytest.raises((ValueError, RuntimeError)):
        native.lz4_decompress(comp[:-3] + b"\xff\xff\xff", 37 + 50)


def test_serializer_lz4_round_trip():
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar import ColumnarBatch
    from spark_rapids_tpu.columnar.batch import schema_of
    from spark_rapids_tpu.shuffle.serializer import (
        deserialize_batch,
        serialize_batch,
    )

    schema = schema_of(a=T.LONG, s=T.STRING, b=T.DOUBLE)
    batch = ColumnarBatch.from_pydict(
        {"a": [1, None, 3] * 50, "s": ["xy", None, "zzz"] * 50,
         "b": [1.5, 2.5, None] * 50}, schema)
    wire = serialize_batch(batch, codec="lz4")
    back = deserialize_batch(wire)
    assert back.to_rows() == batch.to_rows()


def test_exchange_with_lz4_codec():
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.expr import aggregates as A
    from spark_rapids_tpu.expr.expressions import col
    from spark_rapids_tpu.sql import TpuSession

    sess = TpuSession({
        "spark.rapids.tpu.shuffle.mode": "host",
        "spark.rapids.tpu.shuffle.transport.class": "host",
        "spark.rapids.tpu.shuffle.compression.codec": "lz4",
    })
    schema = T.StructType([T.StructField("k", T.INT),
                           T.StructField("v", T.LONG)])
    df = sess.create_dataframe(
        {"k": [i % 5 for i in range(500)], "v": list(range(500))},
        schema, num_partitions=3)
    rows = sorted(df.group_by("k").agg(A.agg(A.Sum(col("v")), "sv")).collect())
    expect = {}
    for i in range(500):
        expect[i % 5] = expect.get(i % 5, 0) + i
    assert rows == sorted(expect.items())
