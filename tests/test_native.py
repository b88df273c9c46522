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


# ---------------------------------------------------------------------------
# the snappy decoder parquet pages go through (native/src/snappy.cpp)
# ---------------------------------------------------------------------------
def _pyarrow_snappy():
    import pyarrow as pa

    return pa.Codec("snappy")


def _snappy(block, size):
    """``srtpu_snappy_decompress`` of ``block`` into ``size`` bytes; a
    ValueError where it returns its error code or another size."""
    import ctypes

    fn = native._load().srtpu_snappy_decompress
    fn.restype = ctypes.c_int64
    fn.argtypes = [ctypes.c_char_p, ctypes.c_int64, ctypes.c_char_p,
                   ctypes.c_int64]
    out = ctypes.create_string_buffer(max(size, 1))
    n = fn(block, len(block), out, size)
    if n != size:
        raise ValueError(f"snappy block refused ({n} != {size})")
    return out.raw[:n]


def _snappy_payloads():
    rng = random.Random(41)
    yield b""
    yield b"x"
    for n in (59, 60, 61, 62, 63, 64, 65, 255, 256, 257, 4096):
        yield bytes(rng.randrange(256) for _ in range(n))  # literal only
    yield os.urandom(70_000)  # literals past one 64 KiB fragment
    yield b"abcd" * 30_000  # copies of every reach, overlapping
    yield bytes(range(256)) * 600
    yield b"".join(  # runs and noise: copies with 1- and 2-byte offsets
        bytes([rng.randrange(256)]) * rng.randint(1, 80)
        if rng.random() < 0.5 else os.urandom(rng.randint(1, 80))
        for _ in range(4000))


@pytest.mark.parametrize("payload", list(_snappy_payloads()),
                         ids=lambda p: f"{len(p)}B")
def test_snappy_decodes_pyarrows_blocks(payload):
    comp = _pyarrow_snappy().compress(payload).to_pybytes()
    assert _snappy(comp, len(payload)) == payload


def _literal(data, form):
    """A literal element with its length in the tag (form 0) or in 1..4
    bytes after it (forms 60..63)."""
    n = len(data) - 1
    if form == 0:
        return bytes([n << 2]) + data
    return bytes([form << 2]) + n.to_bytes(form - 59, "little") + data


def _copy(offset, length, form):
    """A copy element: form 1 (1-byte offset, length 4..11), 2 (2-byte
    offset) or 4 (4-byte offset), lengths 1..64."""
    if form == 1:
        return bytes([((offset >> 8) << 5) | ((length - 4) << 2) | 1,
                      offset & 0xFF])
    width = 2 if form == 2 else 4
    return (bytes([((length - 1) << 2) | (2 if form == 2 else 3)])
            + offset.to_bytes(width, "little"))


def _hand_block():
    """Every element form: literals of inline lengths and of 1-, 2-, 3- and
    4-byte lengths, copies with 1-, 2- and 4-byte offsets, overlapping
    copies, and a copy that reaches back past 64 KiB."""
    rng = random.Random(5)
    out = bytearray()
    body = bytearray()

    def lit(n, form):
        data = bytes(rng.randrange(256) for _ in range(n))
        body.extend(_literal(data, form))
        out.extend(data)

    def copy(offset, length, form):
        body.extend(_copy(offset, length, form))
        for _ in range(length):
            out.append(out[-offset])

    lit(1, 0)
    lit(60, 0)
    lit(61, 60)
    lit(256, 60)
    lit(300, 61)
    copy(1, 11, 1)     # overlapping: one byte repeated
    copy(600, 4, 1)
    copy(3, 64, 2)
    copy(700, 33, 2)
    lit(70_000, 62)
    copy(2047, 11, 1)
    copy(65_600, 64, 4)
    copy(5, 17, 4)
    lit(9, 63)
    return _uvarint(len(out)) + bytes(body), bytes(out)


def _uvarint(v):
    out = bytearray()
    while True:
        out.append((v & 0x7F) | (0x80 if v >> 7 else 0))
        v >>= 7
        if not v:
            return bytes(out)


def test_snappy_decodes_every_element_form_as_pyarrow_does():
    block, want = _hand_block()
    assert _pyarrow_snappy().decompress(block, len(want)).to_pybytes() == want
    assert _snappy(block, len(want)) == want


def _corrupt_blocks():
    block, want = _hand_block()
    good = _pyarrow_snappy().compress(b"hello snappy " * 50).to_pybytes()
    n = len(b"hello snappy " * 50)
    yield "truncated", good[:-3], n
    yield "cut in a literal", good[:5], n
    yield "longer than stated", good, n - 1
    yield "shorter than stated", good, n + 1
    yield "empty", b"", 1
    yield "preamble only", _uvarint(10), 10
    yield "bad preamble", b"\xff\xff\xff\xff\xff\xff", 10
    yield "copy before any output", _uvarint(8) + _copy(1, 8, 2), 8
    yield "offset zero", _uvarint(9) + _literal(b"a", 0) + _copy(0, 8, 2), 9
    yield ("offset past the output", _uvarint(9) + _literal(b"a", 0)
           + _copy(2, 8, 4), 9)
    yield ("copy past the stated length", _uvarint(5) + _literal(b"a", 0)
           + _copy(1, 8, 2), 5)
    yield ("literal past the input", _uvarint(50)
           + _literal(b"x" * 50, 0)[:20], 50)
    yield "whole block, cut", block[: len(block) // 2], len(want)


@pytest.mark.parametrize(
    "name,block,size",
    [pytest.param(*c, id=c[0]) for c in _corrupt_blocks()])
def test_snappy_refuses_corrupt_blocks(name, block, size):
    with pytest.raises(ValueError):
        _snappy(block, size)


#: run in a child: each input is placed to end where an unreadable page
#: begins, so a read past it ends the child with SIGSEGV (and so does a
#: write that runs far past a buffer the planner sized)
_GUARDED = r"""
import ctypes, mmap, sys
lib = ctypes.CDLL(sys.argv[1])
lib.srtpu_snappy_decompress.restype = ctypes.c_int64
lib.srtpu_snappy_decompress.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                        ctypes.c_void_p, ctypes.c_int64]
lib.srtpu_pq_plan_chunk.restype = ctypes.c_void_p
lib.srtpu_pq_plan_chunk.argtypes = [
    ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
    ctypes.c_int32, ctypes.c_int32, ctypes.c_int64, ctypes.c_int32,
    ctypes.c_int32, ctypes.c_void_p]
libc = ctypes.CDLL(None)
libc.mprotect.argtypes = [ctypes.c_void_p, ctypes.c_size_t, ctypes.c_int]
page = mmap.PAGESIZE
cases = eval(sys.stdin.read())
area = mmap.mmap(-1, 64 * page)
base = ctypes.addressof(ctypes.c_char.from_buffer(area))
fence = base + 48 * page
assert libc.mprotect(fence, 16 * page, 0) == 0
out = ctypes.create_string_buffer(1 << 20)
info = (ctypes.c_int64 * 10)()
for kind, data, size, *chunk in cases:
    at = fence - len(data)
    ctypes.memmove(at, data, len(data))
    if kind == "snappy":
        rc = lib.srtpu_snappy_decompress(at, len(data), out, size)
        assert rc != size, (kind, rc)
    else:
        n, max_def, has_nulls = chunk
        for codec in (0, 1):
            handle = lib.srtpu_pq_plan_chunk(at, len(data), 4, 4 + size,
                                             codec, 0, n, max_def, has_nulls,
                                             info)
            assert not handle and info[0] == 9, (codec, info[0])
print("ok", len(cases))
"""


def _i32(fid_delta, v):
    """A thrift compact i32 field: its header byte and zigzag varint."""
    return bytes([(fid_delta << 4) | 5]) + _uvarint((v << 1) ^ (v >> 63))


def _data_page(payload, num_values, num_nulls=None):
    """A page header and its uncompressed payload: a v1 data page, or a v2
    page (no levels bytes) where ``num_nulls`` is given; RLE_DICTIONARY."""
    size = _i32(1, len(payload)) + _i32(1, len(payload))
    if num_nulls is None:
        inner = _i32(1, num_values) + _i32(1, 8)
        return _i32(1, 0) + size + b"\x2c" + inner + b"\x00\x00" + payload
    inner = (_i32(1, num_values) + _i32(1, num_nulls) + _i32(1, num_values)
             + _i32(1, 8) + _i32(1, 0) + _i32(1, 0))
    return _i32(1, 3) + size + b"\x5c" + inner + b"\x00\x00" + payload


def _over_counted_chunks():
    """Chunks of 1,000 INT32 values at bit width 20 (4-byte codes) whose
    second page states more values than the chunk has left: 2^62 values
    (a v1 page), or -2^62 nulls (a v2 page). Sized by the stated count, a
    code buffer of (held + 2^62) * 4 bytes wraps to the bytes it holds, and
    the page's RLE run of 2^28 codes is then written past its end."""
    first = _data_page(b"\x14\x10\x01\x00\x00", 8)  # 8 times code 1
    run = b"\x14" + _uvarint(1 << 29) + b"\x01\x00\x00"  # 2^28 times
    for second, max_def in ((_data_page(run, 1 << 62), 0),
                            (_data_page(run, 8, num_nulls=-(1 << 62)), 1)):
        chunk = b"PAR1" + first + second
        yield chunk, len(chunk) - 4, 1000, max_def, 0  # no levels to read


def test_corrupt_input_reads_nothing_past_its_end():
    """Corrupt snappy blocks and truncated or over-counted column chunks,
    each ending at an unreadable page: the decoder and the planner return
    their error code and touch nothing past their buffers (a read past the
    input, or a write far past a buffer, would kill the child)."""
    import subprocess
    import sys

    from spark_rapids_tpu.native import _load

    path = _load()._name
    cases = [("snappy", block, size) for _, block, size in _corrupt_blocks()]
    # a chunk whose page headers say more bytes than the file holds
    header = bytes([0x15, 0x00, 0x15, 0xC8, 0x01, 0x15, 0xC8, 0x01, 0x2C,
                    0x15, 0xD0, 0x0F, 0x15, 0x10, 0x00, 0x00])
    for cut in (1, 5, len(header), len(header) + 7):
        cases.append(("chunk", b"PAR1" + (header + b"\x01" * 99)[:cut],
                      len(header) + 100, 1000, 1, 1))
    cases += [("chunk", *c) for c in _over_counted_chunks()]
    done = subprocess.run([sys.executable, "-c", _GUARDED, path],
                          input=repr(cases), capture_output=True, text=True,
                          timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
    assert done.stdout.split() == ["ok", str(len(cases))]
