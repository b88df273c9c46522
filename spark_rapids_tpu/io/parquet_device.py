"""TPU-offloaded parquet page decode.

Reference analog: the GPU half of the reference's parquet scan — the host
reads raw column-chunk BYTES and the accelerator decodes pages
(GpuParquetScan.scala:1775 structure; GPU decode via ``Table.readParquet``
at :1157, cudf's parquet decoder). The TPU split is chosen by what each
side is fast at:

  * HOST (cheap, vectorized numpy — no per-value python): thrift page
    headers, codec decompress (pyarrow), RLE/bit-packed hybrid expansion
    of dictionary INDICES to the narrowest integer (u8/u16/i32 by bit
    width) via ``np.unpackbits`` reshape tricks, validity BITS re-packed
    to words.
  * WIRE: the narrow codes + packed validity + the dictionary — typically
    1-2 bytes/value instead of 4-8 raw, so host->device transfer shrinks
    by the dictionary ratio. That is the same bytes-not-values contract
    the reference's host half honors.
  * DEVICE (XLA): validity bit expansion (elementwise shifts), present->
    row scatter via prefix sums, and the expensive part — DICTIONARY
    EXPANSION, one packed row gather per column (small-table fast path),
    plus 64-bit reassembly for PLAIN int64 (arithmetic: the x64 rewriter
    has no 64-bit bitcast).

Scope: flat schemas (max_repetition_level == 0), PLAIN int32/int64/float,
RLE_DICTIONARY / PLAIN_DICTIONARY for int32/int64/float/double and
BYTE_ARRAY (strings), definition levels for nullable columns, v1 and v2
data pages, snappy/zstd/gzip/uncompressed codecs. Pages of one chunk may
use different dictionary bit widths. Anything else falls back to the host
arrow decoder per-column.
"""
from __future__ import annotations

import dataclasses
import struct as _struct
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

# thrift compact type ids
_T_STOP = 0
_T_TRUE = 1
_T_FALSE = 2
_T_BYTE = 3
_T_I16 = 4
_T_I32 = 5
_T_I64 = 6
_T_DOUBLE = 7
_T_BINARY = 8
_T_LIST = 9
_T_SET = 10
_T_MAP = 11
_T_STRUCT = 12

# parquet page types
DATA_PAGE = 0
DICTIONARY_PAGE = 2
DATA_PAGE_V2 = 3

# parquet encodings
ENC_PLAIN = 0
ENC_PLAIN_DICTIONARY = 2
ENC_RLE = 3
ENC_RLE_DICTIONARY = 8

#: host-side guardrail: pages with more hybrid runs than this fall back
#: (the python run parser is O(runs); typical pages have few runs)
MAX_RUNS_PER_PAGE = 1 << 16


class _Reader:
    """Minimal thrift compact-protocol struct reader (header-only needs)."""

    __slots__ = ("buf", "pos")

    def __init__(self, buf: bytes, pos: int = 0):
        self.buf = buf
        self.pos = pos

    def varint(self) -> int:
        r = 0
        shift = 0
        while True:
            b = self.buf[self.pos]
            self.pos += 1
            r |= (b & 0x7F) << shift
            if not b & 0x80:
                return r
            shift += 7

    def zigzag(self) -> int:
        v = self.varint()
        return (v >> 1) ^ -(v & 1)

    def skip(self, ftype: int) -> None:
        if ftype in (_T_TRUE, _T_FALSE):
            return
        if ftype == _T_BYTE:
            self.pos += 1
        elif ftype in (_T_I16, _T_I32, _T_I64):
            self.varint()
        elif ftype == _T_DOUBLE:
            self.pos += 8
        elif ftype == _T_BINARY:
            # NOTE: must read the varint BEFORE adding — `pos += varint()`
            # loads pos before varint() advances it
            ln = self.varint()
            self.pos += ln
        elif ftype in (_T_LIST, _T_SET):
            b = self.buf[self.pos]
            self.pos += 1
            size = b >> 4
            et = b & 0x0F
            if size == 15:
                size = self.varint()
            for _ in range(size):
                self.skip(et)
        elif ftype == _T_MAP:
            size = self.varint()
            if size:
                kv = self.buf[self.pos]
                self.pos += 1
                for _ in range(size):
                    self.skip(kv >> 4)
                    self.skip(kv & 0x0F)
        elif ftype == _T_STRUCT:
            self.read_struct(lambda fid, ft, rd: rd.skip(ft))
        else:
            raise ValueError(f"thrift type {ftype}")

    def read_struct(self, on_field) -> None:
        """on_field(field_id, ftype, reader) must CONSUME the value."""
        fid = 0
        while True:
            b = self.buf[self.pos]
            self.pos += 1
            if b == _T_STOP:
                return
            delta = b >> 4
            ftype = b & 0x0F
            fid = fid + delta if delta else self.zigzag()
            on_field(fid, ftype, self)


@dataclasses.dataclass
class PageHeader:
    type: int
    uncompressed_size: int
    compressed_size: int
    num_values: int = 0
    encoding: int = ENC_PLAIN
    # v2 extras
    num_nulls: int = 0
    def_levels_len: int = 0
    rep_levels_len: int = 0
    v2_is_compressed: bool = True
    header_len: int = 0


def parse_page_header(buf: bytes, pos: int) -> PageHeader:
    rd = _Reader(buf, pos)
    ph = PageHeader(-1, 0, 0)

    def sub_data(fid, ft, r):
        if fid == 1:
            ph.num_values = r.zigzag()
        elif fid == 2:
            ph.encoding = r.zigzag()
        else:
            r.skip(ft)

    def sub_dict(fid, ft, r):
        if fid == 1:
            ph.num_values = r.zigzag()
        elif fid == 2:
            ph.encoding = r.zigzag()
        else:
            r.skip(ft)

    def sub_v2(fid, ft, r):
        if fid == 1:
            ph.num_values = r.zigzag()
        elif fid == 2:
            ph.num_nulls = r.zigzag()
        elif fid == 4:
            ph.encoding = r.zigzag()
        elif fid == 5:
            ph.def_levels_len = r.zigzag()
        elif fid == 6:
            ph.rep_levels_len = r.zigzag()
        elif fid == 7:
            ph.v2_is_compressed = ft == _T_TRUE
        else:
            r.skip(ft)

    def top(fid, ft, r):
        if fid == 1:
            ph.type = r.zigzag()
        elif fid == 2:
            ph.uncompressed_size = r.zigzag()
        elif fid == 3:
            ph.compressed_size = r.zigzag()
        elif fid == 5 and ft == _T_STRUCT:
            r.read_struct(sub_data)
        elif fid == 7 and ft == _T_STRUCT:
            r.read_struct(sub_dict)
        elif fid == 8 and ft == _T_STRUCT:
            r.read_struct(sub_v2)
        else:
            r.skip(ft)

    rd.read_struct(top)
    ph.header_len = rd.pos - pos
    return ph


# ---------------------------------------------------------------------------
# RLE / bit-packed hybrid expansion (host side, vectorized numpy)
# ---------------------------------------------------------------------------
class _FallbackError(Exception):
    """Column can't take the device path; fall back to host decode."""


#: safety bound on hybrid runs per stream (each run costs one cheap numpy
#: slice; this only guards adversarial files)
MAX_RUNS = 1 << 20

_POWS = {bw: (1 << np.arange(bw, dtype=np.int64)).astype(np.int32)
         for bw in range(1, 25)}


def hybrid_decode_np(data: bytes, pos: int, end: int, bw: int,
                     n: int) -> Tuple[np.ndarray, int]:
    """Expand one RLE/bit-packed hybrid stream to n int32 values.

    Per-RUN python loop, per-VALUE numpy (`np.unpackbits` + a reshape dot)
    — the host cost is a few ns/value, ~100x under arrow's full decode to
    raw 64-bit columns. Returns (values, byte position after stream)."""
    if bw == 0:
        return np.zeros(n, np.int32), pos
    if bw > 24:
        raise _FallbackError(f"bit width {bw}")
    out = np.zeros(n, np.int32)
    byte_w = (bw + 7) // 8
    pows = _POWS[bw]
    got = 0
    nruns = 0
    while got < n and pos < end:
        nruns += 1
        if nruns > MAX_RUNS:
            raise _FallbackError("too many hybrid runs")
        header = 0
        shift = 0
        while True:
            b = data[pos]
            pos += 1
            header |= (b & 0x7F) << shift
            if not b & 0x80:
                break
            shift += 7
        if header & 1:  # bit-packed run of (header>>1) groups of 8
            groups = header >> 1
            count = groups * 8
            nbytes = groups * bw
            arr = np.frombuffer(data, np.uint8, nbytes, pos)
            bits = np.unpackbits(arr, bitorder="little")
            take = min(count, n - got)
            m = take  # only decode what the stream logically holds
            vals = bits[: m * bw].reshape(m, bw) @ pows
            out[got : got + take] = vals
            pos += nbytes
            got += count  # padding values advance the logical count too
        else:  # RLE run
            count = header >> 1
            v = int.from_bytes(data[pos : pos + byte_w], "little")
            pos += byte_w
            take = min(count, n - got)
            out[got : got + take] = v
            got += count
    if got < n:
        raise _FallbackError(f"short hybrid stream: {got}/{n}")
    return out, pos


def _code_dtype(bw: int):
    return (np.uint8 if bw <= 8 else
            np.uint16 if bw <= 16 else np.int32)


def hybrid_decode(data, pos: int, end: int, bw: int,
                  n: int) -> Tuple[np.ndarray, int]:
    """Hybrid-stream decode, native C++ when available (releases the GIL,
    so the per-column planning pool gets real parallelism; reference
    analog: cudf's native page decode behind GpuParquetScan.scala:1157).
    Output dtype is the narrowest holding the bit width."""
    if bw == 0:
        return np.zeros(n, np.uint8), pos
    if bw > 24:
        raise _FallbackError(f"bit width {bw}")
    from ..native import pq_hybrid_decode

    out = np.empty(n, _code_dtype(bw))
    try:
        newpos = pq_hybrid_decode(data, pos, end, bw, n, out)
    except ValueError as e:
        raise _FallbackError(str(e))
    if newpos is None:  # no native toolchain: vectorized-numpy fallback
        vals, newpos = hybrid_decode_np(data, pos, end, bw, n)
        return vals.astype(out.dtype, copy=False), newpos
    return out, newpos


# ---------------------------------------------------------------------------
# host planning: file bytes -> upload arrays per column chunk
# ---------------------------------------------------------------------------
_PHYS_NP = {
    "INT32": np.dtype(np.int32),
    "INT64": np.dtype(np.int64),
    "FLOAT": np.dtype(np.float32),
    "DOUBLE": np.dtype(np.float64),
    "BOOLEAN": np.dtype(np.bool_),
}


@dataclasses.dataclass
class ChunkPlan:
    """Host-normalized upload payloads of one column chunk."""

    phys: str  # parquet physical type
    num_values: int  # rows in the chunk
    nullable: bool
    # dictionary (None for PLAIN data pages)
    dict_values: Optional[np.ndarray] = None  # numeric dicts
    dict_offsets: Optional[np.ndarray] = None  # string dicts
    dict_chars: Optional[np.ndarray] = None
    # per-PRESENT dictionary code, narrowest dtype (u8/u16/i32)
    codes: Optional[np.ndarray] = None
    # per-row validity (None = no nulls)
    validity: Optional[np.ndarray] = None
    # PLAIN page payloads (concatenated raw value bytes, present only)
    plain_bytes: Optional[bytes] = None
    n_present: int = 0


def _decompress(codec: str, data: bytes, out_size: int) -> bytes:
    codec = codec.upper()
    if codec == "UNCOMPRESSED":
        return data
    import pyarrow as pa

    try:
        c = pa.Codec(codec.lower())
    except Exception as e:  # codec not built into this pyarrow
        raise _FallbackError(f"codec {codec}: {e}")
    return c.decompress(data, out_size).to_pybytes()


def plan_chunk(
    file_bytes: bytes, col_meta, max_def: int, max_rep: int
) -> ChunkPlan:
    """Parse one column chunk's pages into a ChunkPlan (host side).

    Raises _FallbackError for unsupported shapes/encodings."""
    if max_rep != 0:
        raise _FallbackError("nested (repeated) column")
    phys = col_meta.physical_type
    if phys not in _PHYS_NP and phys != "BYTE_ARRAY":
        raise _FallbackError(f"physical type {phys}")
    codec = col_meta.compression
    n = col_meta.num_values
    st = col_meta.statistics
    has_nulls = (
        max_def > 0
        and (st is None or st.null_count is None or st.null_count > 0)
    )

    doff = col_meta.dictionary_page_offset
    off = doff if doff is not None and doff > 0 else col_meta.data_page_offset
    end = off + col_meta.total_compressed_size

    plan = ChunkPlan(phys=phys, num_values=n, nullable=max_def > 0)
    pos = off
    values_seen = 0
    code_pages: List[np.ndarray] = []
    valid_pages: List[np.ndarray] = []
    plain_parts: List[bytes] = []
    saw_dict_page = False
    saw_plain_page = False

    def handle_values(raw: bytes, p: int, pend: int, enc: int,
                      presents: int) -> None:
        nonlocal saw_dict_page, saw_plain_page
        if enc in (ENC_RLE_DICTIONARY, ENC_PLAIN_DICTIONARY):
            bw = raw[p] if p < len(raw) else 0
            vals, _ = hybrid_decode(raw, p + 1, pend, bw, presents)
            code_pages.append(vals)
            saw_dict_page = True
        elif enc == ENC_PLAIN:
            if phys in ("BYTE_ARRAY", "BOOLEAN", "DOUBLE"):
                # BYTE_ARRAY plain needs per-value host parsing; f64 needs
                # a 64-bit device bitcast the x64 rewriter lacks
                raise _FallbackError(f"PLAIN {phys}")
            dt = _PHYS_NP[phys]
            need = presents * dt.itemsize
            plain_parts.append(raw[p : p + need])
            saw_plain_page = True
        else:
            raise _FallbackError(f"encoding {enc}")
        if saw_dict_page and saw_plain_page:
            # mixed dict+plain pages (dict overflow mid-chunk): the device
            # program would need both paths; punt to the host decoder
            raise _FallbackError("mixed dict/plain pages")

    while pos < end and values_seen < n:
        ph = parse_page_header(file_bytes, pos)
        pos += ph.header_len
        payload = file_bytes[pos : pos + ph.compressed_size]
        pos += ph.compressed_size
        if ph.type == DICTIONARY_PAGE:
            if ph.encoding not in (ENC_PLAIN, ENC_PLAIN_DICTIONARY):
                raise _FallbackError(f"dict encoding {ph.encoding}")
            raw = _decompress(codec, payload, ph.uncompressed_size)
            _load_dictionary(plan, raw, ph.num_values)
            continue
        if ph.type == DATA_PAGE:
            raw = _decompress(codec, payload, ph.uncompressed_size)
            p = 0
            presents = ph.num_values
            if max_def > 0:
                (ln,) = _struct.unpack_from("<I", raw, p)
                p += 4
                if has_nulls:
                    levels, _ = hybrid_decode(
                        raw, p, p + ln, 1, ph.num_values)
                    vp = levels == 1
                    valid_pages.append(vp)
                    presents = int(vp.sum())
                p += ln
            handle_values(raw, p, len(raw), ph.encoding, presents)
            values_seen += ph.num_values
            continue
        if ph.type == DATA_PAGE_V2:
            if ph.rep_levels_len:
                raise _FallbackError("repeated column (v2)")
            presents = ph.num_values - (
                ph.num_nulls if max_def > 0 else 0)
            if max_def > 0 and has_nulls:
                if ph.def_levels_len:
                    levels, _ = hybrid_decode(
                        payload, 0, ph.def_levels_len, 1, ph.num_values)
                    valid_pages.append(levels == 1)
                else:
                    valid_pages.append(
                        np.ones(ph.num_values, np.bool_))
            vals = payload[ph.def_levels_len :]
            if ph.v2_is_compressed and codec.upper() != "UNCOMPRESSED":
                vals = _decompress(
                    codec, vals, ph.uncompressed_size - ph.def_levels_len)
            handle_values(vals, 0, len(vals), ph.encoding, presents)
            values_seen += ph.num_values
            continue
        # index pages etc: skip
    if values_seen < n:
        raise _FallbackError(f"short chunk: {values_seen}/{n} values")
    if valid_pages:
        plan.validity = np.concatenate(valid_pages)
    if code_pages:
        # pages already decoded to the narrowest dtype for their bit width;
        # concatenate promotes to the widest page's dtype
        codes = (np.concatenate(code_pages) if len(code_pages) > 1
                 else code_pages[0])
        plan.n_present = codes.shape[0]
        if codes.dtype.itemsize > 1 and codes.shape[0]:
            # narrow further when the observed max allows (pages of one
            # chunk may carry a wider bit width than the values need)
            mx = int(codes.max())
            want = (np.uint8 if mx < 256 else
                    np.uint16 if mx < 65536 else None)
            if want is not None and np.dtype(want).itemsize < codes.dtype.itemsize:
                codes = codes.astype(want)
        plan.codes = codes
    elif plain_parts:
        plan.plain_bytes = b"".join(plain_parts)
        dt = _PHYS_NP[phys]
        plan.n_present = len(plan.plain_bytes) // dt.itemsize
    else:
        plan.n_present = 0
        plan.codes = np.zeros(0, np.uint8)
    return plan


def _load_dictionary(plan: ChunkPlan, raw: bytes, count: int) -> None:
    if plan.phys == "BYTE_ARRAY":
        from ..native import pq_binary_dict

        offs32 = np.empty(count + 1, np.int32)
        cap = max(1, len(raw) - 4 * count)
        chars_buf = np.empty(cap, np.uint8)
        try:
            total = pq_binary_dict(raw, count, offs32, chars_buf)
        except ValueError:
            raise _FallbackError("malformed binary dictionary")
        if total is not None:
            plan.dict_offsets = offs32.astype(np.int64)
            plan.dict_chars = (chars_buf[:total].copy() if total
                               else np.zeros(1, np.uint8))
            return
        offs = np.zeros(count + 1, np.int64)
        chars = []
        p = 0
        for i in range(count):
            (ln,) = _struct.unpack_from("<I", raw, p)
            p += 4
            chars.append(raw[p : p + ln])
            p += ln
            offs[i + 1] = offs[i] + ln
        plan.dict_offsets = offs
        pool = b"".join(chars)
        plan.dict_chars = (
            np.frombuffer(pool, np.uint8).copy() if pool
            else np.zeros(1, np.uint8))
    elif plan.phys == "BOOLEAN":
        raise _FallbackError("boolean dictionary")
    else:
        dt = _PHYS_NP[plan.phys]
        plan.dict_values = np.frombuffer(
            raw[: count * dt.itemsize], dt).copy()


# ---------------------------------------------------------------------------
# device decode (XLA kernels)
# ---------------------------------------------------------------------------
#: stream the fixed-width unpack (bit-expand -> code read -> dictionary
#: look-up -> validity expand) through one tiled fori_loop instead of
#: materializing full-width intermediate planes (the cap-sized
#: widened-codes and present->row index planes). Module-level because
#: plan_decode has no session conf in scope; tests flip it to diff the
#: flat path.
TILED_UNPACK = True
#: below this output capacity the flat program's intermediates are noise
#: and the loop only costs dispatch overhead
TILED_UNPACK_MIN_CAP = 1 << 16
#: test hook: force the unpack tile row count (0 = derive); rounded up
#: to a multiple of 32 so validity-word slices stay aligned
FORCE_UNPACK_TILE_ROWS = 0
#: the longest dictionary of 32-bit planes (INT32, FLOAT, INT64 as two)
#: whose look-up is a matmul against a one-hot of the code, and the
#: longest whose look-up is the two-level form, instead of a per-element
#: gather: where each read at least 2x faster a value than the gather on
#: a v5e (docs/tuning.md "The streamed (tiled) unpack" has the readings)
ONEHOT_MAX_D = 1024
TWOLEVEL_MAX_D = 16384
#: the most bytes a tile's one-hot operand or two-level product may take
_LOOKUP_TILE_BYTES = 8 << 20
_TWOLEVEL_LANES = 128


def unpack_bit_words(words, out_cap: int):
    """bits[j] = bit j of the LSB-first u32 word stream — pure reshape/
    elementwise, ZERO gathers (a gather costs ~8 ns a value on a v5e)."""
    import jax.numpy as jnp

    need_w = -(-out_cap // 32)
    w = words
    if w.shape[0] < need_w:
        w = jnp.concatenate(
            [w, jnp.zeros(need_w - w.shape[0], jnp.uint32)])
    else:
        w = w[:need_w]
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = ((w[:, None] >> shifts[None, :]) & jnp.uint32(1)) != 0
    return bits.reshape(need_w * 32)[:out_cap]


def _unpack_tile_rows(cap: int, most: int = 0) -> int:
    """Rows a trip of the streamed unpack handles: a multiple of 32 (the
    validity-word slices align) and, where the dictionary look-up holds a
    temporary of several planes a row, at most ``most``."""
    if FORCE_UNPACK_TILE_ROWS:
        rows = FORCE_UNPACK_TILE_ROWS
    else:
        from ..ops.radix_bin import default_tile_rows

        # the loop body's working set is ~3 tile-sized planes; reuse the
        # radix-bin sizing rule (fast-memory-resident tiles, 2^12..2^16).
        # default_tile_rows' own results are powers of two >= 2^12, but a
        # test driving radix_bin.FORCE_TILE_ROWS (the AGG tiling hook) can
        # leak a non-multiple of 32 through it
        rows = max(32, default_tile_rows(cap, 3))
    if most:
        rows = min(rows, most)
    return -(-rows // 32) * 32


def dict_read_form(phys: str, d: int) -> str:
    """How a tile looks its codes up in a dictionary of ``d`` values:
    ``onehot`` and ``twolevel`` are matmuls over the values' 8-bit limbs
    (32-bit planes only: the chip has no bit-exact route for DOUBLE),
    ``gather`` the per-element ``jnp.take``."""
    if phys in ("INT32", "FLOAT", "INT64"):
        if d <= ONEHOT_MAX_D:
            return "onehot"
        if d <= TWOLEVEL_MAX_D:
            return "twolevel"
    return "gather"


def _lookup_tile_rows(form: str, d: int, itemsize: int) -> int:
    """The most rows a tile may have so that the look-up's temporary (the
    bf16 one-hot ``[d, rows]``, or the two-level f32 product
    ``[limbs * 128, rows]``) stays under ``_LOOKUP_TILE_BYTES``; a power
    of two, 0 for no limit."""
    if form == "onehot":
        rows = _LOOKUP_TILE_BYTES // (2 * max(1, d))
    elif form == "twolevel":
        rows = _LOOKUP_TILE_BYTES // (4 * itemsize * _TWOLEVEL_LANES)
    else:
        return 0
    tile = 32  # a tile, not a capacity: halved until the temporary fits
    while tile * 2 <= rows:
        tile *= 2
    return tile


def key_gathers(key) -> int:
    """Per-element gathers left in the program of one chunk, from the
    ``("reads", codes, dict)`` tag of its ``plan_decode`` key."""
    for k in key:
        if isinstance(k, tuple) and k and k[0] == "reads":
            return sum(form == "gather" for form in k[1:])
    return 0


def stage_gathers(stage) -> int:
    """The same over every chunk of a fused stage's row groups
    (``ParquetScanner.device_stage_plans``), cached ones included: what
    the span that splices their programs carries as ``gathers``."""
    return sum(key_gathers(key) for (_, _, entries) in stage
               for (_, key, _, _) in entries)


def _limb_table(dvals, pad_to: int = 0):
    """``[limbs, D]`` bf16: the 8-bit limbs of a dictionary's 32-bit
    planes, low limb first (four for INT32/FLOAT, eight for INT64). A limb
    is exact in bf16, so a matmul of it against a one-hot of the code (one
    non-zero product a row, f32 accumulation) returns it exactly."""
    import jax.numpy as jnp
    from jax import lax

    if dvals.dtype.itemsize == 4:
        planes = [lax.bitcast_convert_type(dvals, jnp.uint32)]
    else:
        from ..ops.filter_gather import _split64_i32

        planes = [lax.bitcast_convert_type(h, jnp.uint32)
                  for h in _split64_i32(dvals)]
    limbs = jnp.stack([(p >> jnp.uint32(8 * j)) & jnp.uint32(0xFF)
                       for p in planes for j in range(4)])
    if pad_to > limbs.shape[1]:
        limbs = jnp.pad(limbs, ((0, 0), (0, pad_to - limbs.shape[1])))
    return limbs.astype(jnp.bfloat16)


def _from_limbs(limbs_f32, out_dt):
    """``[limbs, rows]`` f32 limbs (whole numbers under 256) back to the
    values whose bit patterns they spell."""
    import jax.numpy as jnp
    from jax import lax

    u = limbs_f32.astype(jnp.uint32)
    planes = [u[i] | (u[i + 1] << jnp.uint32(8)) | (u[i + 2] << jnp.uint32(16))
              | (u[i + 3] << jnp.uint32(24))
              for i in range(0, u.shape[0], 4)]
    if len(planes) == 1:
        return lax.bitcast_convert_type(planes[0], out_dt)
    from ..ops.filter_gather import _join64

    return _join64(lax.bitcast_convert_type(planes[0], jnp.int32),
                   lax.bitcast_convert_type(planes[1], jnp.int32), out_dt)


def _onehot_lookup(table, codes_t):
    """``table[:, codes_t]`` of a ``[limbs, D]`` limb table as a matmul
    against the one-hot of the tile's codes: ``[limbs, rows]`` f32."""
    import jax.numpy as jnp

    d = table.shape[1]
    onehot = (jnp.arange(d, dtype=jnp.int32)[:, None]
              == codes_t[None, :]).astype(jnp.bfloat16)
    return jnp.dot(table, onehot, preferred_element_type=jnp.float32)


def _twolevel_table(dvals):
    """The limb table of ``_twolevel_lookup``: ``[limbs * 128, H]`` with
    ``row (l, j)``, ``column h`` the limb ``l`` of value ``h * 128 + j``."""
    lanes = _TWOLEVEL_LANES
    h = -(-dvals.shape[0] // lanes)
    table = _limb_table(dvals, h * lanes)
    limbs = table.shape[0]
    return table.reshape(limbs, h, lanes).transpose(0, 2, 1).reshape(
        limbs * lanes, h)


def _twolevel_lookup(table, codes_t):
    """The same look-up at a cost that does not grow with ``D``: a code is
    ``hi * 128 + lo``; the one-hot of ``hi`` picks, by matmul, the 128
    values of its block (``[limbs * 128, rows]``), and ``lo`` selects one
    of them (a masked sum with one non-zero term: exact)."""
    import jax.numpy as jnp

    lanes = _TWOLEVEL_LANES
    h = table.shape[1]
    limbs = table.shape[0] // lanes
    hi, lo = codes_t // lanes, codes_t % lanes
    onehot = (jnp.arange(h, dtype=jnp.int32)[:, None]
              == hi[None, :]).astype(jnp.bfloat16)
    block = jnp.dot(table, onehot, preferred_element_type=jnp.float32)
    block = block.reshape(limbs, lanes, codes_t.shape[0])
    pick = jnp.arange(lanes, dtype=jnp.int32)[:, None] == lo[None, :]
    return jnp.sum(jnp.where(pick[None], block, jnp.float32(0)), axis=1)


def _pad_for_slices(x, length: int):
    """``x`` zero-padded to ``length``: ``dynamic_slice`` clamps a start
    that runs off the end, which would silently shift a tile's rows."""
    import jax.numpy as jnp

    short = length - x.shape[0]
    if short <= 0:
        return x
    return jnp.concatenate([x, jnp.zeros(short, x.dtype)])


def tiled_fixed_unpack(vwords, out_dt, n: int, cap: int, has_def: bool,
                       tile: int, read_tile):
    """The streamed fixed-width unpack: ONE ``lax.fori_loop`` walks the
    output in validity-word-aligned tiles of ``tile`` rows and writes
    (data, validity) through a sliding dynamic-update-slice window — the
    radix-bin loop pattern (ops/radix_bin.py). No cap-sized widened-code
    plane, no cap-sized cumsum plane, no full-width bit matrix.

    Without nulls (``has_def`` False) row ``i`` holds present value ``i``:
    a trip reads its values with ``read_tile(start, None)``, a contiguous
    slice at ``start``. With nulls a trip bit-expands its slice of the
    packed validity words, derives the present->row index stream IN the
    tile (a carried present-count + tile-local prefix sum) and reads
    ``read_tile(None, vidx_tile)``: a true expand of the present-only
    stream, by gather. ``read_tile`` returns ``tile`` values of dtype
    ``out_dt``."""
    import jax.numpy as jnp
    from jax import lax

    trips = -(-cap // tile)
    wpad = -(-(trips * tile) // 32)
    if has_def:
        w = vwords
        if w.shape[0] < wpad:
            w = jnp.concatenate(
                [w, jnp.zeros(wpad - w.shape[0], jnp.uint32)])
        else:
            w = w[:wpad]
    row_ids = jnp.arange(tile, dtype=jnp.int32)
    shifts = jnp.arange(32, dtype=jnp.uint32)

    def body(t, carry):
        nseen, data_buf, valid_buf = carry
        start = t * tile
        in_n = (start + row_ids) < n
        if has_def:
            ws = lax.dynamic_slice(w, (start // 32,), (tile // 32,))
            bits = ((ws[:, None] >> shifts[None, :]) & jnp.uint32(1)) != 0
            valid_t = bits.reshape(tile) & in_n
            vidx_t = nseen + jnp.cumsum(valid_t.astype(jnp.int32)) - 1
            data_t = read_tile(None, jnp.clip(vidx_t, 0, None))
        else:
            valid_t = in_n
            data_t = read_tile(start, None)
        data_t = jnp.where(valid_t, data_t, jnp.zeros((), out_dt))
        data_buf = lax.dynamic_update_slice(data_buf, data_t, (start,))
        valid_buf = lax.dynamic_update_slice(valid_buf, valid_t, (start,))
        return ((nseen + jnp.sum(valid_t.astype(jnp.int32))).astype(
                    jnp.int32),
                data_buf, valid_buf)

    init = (jnp.int32(0),
            jnp.zeros(trips * tile, out_dt),
            jnp.zeros(trips * tile, jnp.bool_))
    _, data, validity = lax.fori_loop(0, trips, body, init)
    return data[:cap], validity[:cap]


def _pack_validity_words(validity: np.ndarray) -> np.ndarray:
    b = np.packbits(validity, bitorder="little")
    pad = (-b.shape[0]) % 4
    if pad:
        b = np.concatenate([b, np.zeros(pad, np.uint8)])
    return b.view(np.uint32)


_DECODE_CACHE: Dict[tuple, Any] = {}


def _np_plain_words(plan: ChunkPlan) -> np.ndarray:
    raw = plan.plain_bytes or b""
    pad = (-len(raw)) % 8  # even word count so int64 lo/hi halves align
    if pad:
        raw = raw + b"\x00" * pad
    return (
        np.frombuffer(raw, np.uint32).copy()
        if raw else np.zeros(2, np.uint32)
    )


def plan_decode(plan: ChunkPlan, dtype_tpu, cap: int,
                dict_strings: bool = False):
    """Build the device half of one chunk decode WITHOUT dispatching:
    returns ``(args, key, run)`` where ``args`` are the host arrays to
    upload, ``key`` is the structural cache key, and ``run(arglist)`` is a
    PURE traced function producing ``(data, validity)`` for fixed-width,
    ``(offsets, chars, validity)`` for strings, or — with
    ``dict_strings`` — a :class:`~..expr.values.DictV` for dictionary-
    encoded BYTE_ARRAY chunks: the codes and the file's own dictionary
    upload AS-IS and no chars expansion ever happens (late
    materialization; the reference's cudf decoder likewise hands back
    dictionary32 columns). Callers either jit one column
    (chunk_to_device_column) or splice many columns — and whole
    exec chains — into a single fused stage program (exec/aggregate's
    scan→agg stage; reference contrast: cudf decodes a whole table in one
    kernel launch batch, GpuParquetScan.scala:1157)."""
    import jax
    import jax.numpy as jnp

    from ..columnar.column import choose_capacity

    n = plan.num_values
    has_def = plan.validity is not None
    is_dict = plan.codes is not None
    is_str = plan.phys == "BYTE_ARRAY"
    if is_str and not is_dict:
        raise _FallbackError("PLAIN BYTE_ARRAY")
    if n == 0:
        if is_str:
            def run_empty_str(arglist):
                return (jnp.zeros(cap + 1, jnp.int32),
                        jnp.zeros(1, jnp.uint8), jnp.zeros(cap, jnp.bool_))
            return [], ("pqdec0", "str", cap), _named_decode(run_empty_str)
        dt = _PHYS_NP[plan.phys]

        def run_empty(arglist):
            return jnp.zeros(cap, dt), jnp.zeros(cap, jnp.bool_)
        return [], ("pqdec0", str(dt), cap), _named_decode(run_empty)

    keep_dict = bool(dict_strings) and is_str and is_dict
    if is_dict:
        # all-null chunks can carry an EMPTY dictionary: pad one zero slot
        # so the device look-up has a valid (masked-out) target
        if plan.dict_values is not None and plan.dict_values.shape[0] == 0:
            plan.dict_values = np.zeros(1, plan.dict_values.dtype)
        if plan.dict_offsets is not None and plan.dict_offsets.shape[0] < 2:
            plan.dict_offsets = np.zeros(2, np.int64)
    # streamed fixed-width unpack (tiled_fixed_unpack): bit-expand -> code
    # read -> dictionary look-up -> validity expand fuse into one
    # fori_loop over output tiles, so no full-width intermediate plane
    # (widened codes, present->row cumsum, bit matrix) ever materializes
    tiled = (TILED_UNPACK and not is_str
             and (cap >= TILED_UNPACK_MIN_CAP or FORCE_UNPACK_TILE_ROWS))
    # the two reads of a value, each in the cheapest form that what is
    # observed here allows: a per-element gather only where the index is
    # really arbitrary (the present-only stream of a chunk with nulls; a
    # long or DOUBLE dictionary)
    codes_form = "gather" if has_def else "slice"
    dict_form = "none"
    if is_dict and not keep_dict:
        dict_form = "gather"
        if tiled:
            dict_form = dict_read_form(plan.phys, plan.dict_values.shape[0])
    tile = 0
    if tiled:
        tile = _unpack_tile_rows(cap, _lookup_tile_rows(
            dict_form, plan.dict_values.shape[0] if is_dict else 0,
            _PHYS_NP[plan.phys].itemsize))
        tile = min(tile, -(-cap // 32) * 32)
    args: List[Any] = []
    key: List[Any] = ["pqdec", plan.phys, str(dtype_tpu), cap, n, has_def,
                      is_dict, keep_dict,
                      ("tile", tile) if tiled else False,
                      ("reads", codes_form, dict_form)]

    if has_def:
        vwords = _pack_validity_words(plan.validity)
        args.append(np.ascontiguousarray(vwords))
        key.append(int(vwords.shape[0]))
    if is_dict:
        codes = plan.codes
        pcap = choose_capacity(max(1, codes.shape[0]))
        if codes.shape[0] < pcap:
            codes = np.concatenate(
                [codes, np.zeros(pcap - codes.shape[0], codes.dtype)])
        args.append(np.ascontiguousarray(codes))
        key += [str(codes.dtype), pcap]
        if is_str:
            D = plan.dict_offsets.shape[0] - 1
            lens = np.diff(plan.dict_offsets)
            total_bytes = int(
                np.bincount(
                    np.clip(plan.codes.astype(np.int64), 0, D - 1),
                    minlength=D,
                ) @ lens
            ) if plan.codes.shape[0] else 0
            ccap = choose_capacity(max(1, total_bytes), 128)
            max_len = int(lens.max()) if D > 0 and lens.size else 0
            args += [np.ascontiguousarray(plan.dict_offsets.astype(np.int32)),
                     np.ascontiguousarray(plan.dict_chars)]
            key += [D, int(plan.dict_chars.shape[0]), ccap, max_len]
        else:
            args.append(np.ascontiguousarray(plan.dict_values))
            key += [int(plan.dict_values.shape[0])]
    else:
        words = _np_plain_words(plan)
        args.append(np.ascontiguousarray(words))
        key.append(int(words.shape[0]))

    phys = plan.phys

    def run_tiled(arglist):
        """Streamed fixed-width unpack (see `tiled` above)."""
        ai = 0
        vwords = None
        if has_def:
            vwords = arglist[ai]
            ai += 1
        padded = -(-cap // tile) * tile

        def stream_reader(stream):
            """Present values of a tile from the narrow ``stream``: the
            slice at ``start`` without nulls, else the gather at the
            tile's present->row indices."""
            if not has_def:
                stream = _pad_for_slices(stream, padded)

            def read(start, vidx_t):
                if vidx_t is None:
                    return jax.lax.dynamic_slice(stream, (start,), (tile,))
                return jnp.take(stream, jnp.clip(
                    vidx_t, 0, stream.shape[0] - 1), mode="clip")

            return read

        if is_dict:
            read_codes = stream_reader(arglist[ai])  # narrowest dtype
            dvals_ = arglist[ai + 1]
            D_ = dvals_.shape[0]
            out_dt = dvals_.dtype
            if dict_form == "onehot":
                table = _limb_table(dvals_)
            elif dict_form == "twolevel":
                table = _twolevel_table(dvals_)

            def read_tile(start, vidx_t):
                # clipped BEFORE any look-up: a malformed code reads
                # dvals[D-1] in every form
                ct = jnp.clip(read_codes(start, vidx_t).astype(jnp.int32),
                              0, D_ - 1)
                if dict_form == "onehot":
                    return _from_limbs(_onehot_lookup(table, ct), out_dt)
                if dict_form == "twolevel":
                    return _from_limbs(_twolevel_lookup(table, ct), out_dt)
                return jnp.take(dvals_, ct, mode="clip")
        else:
            words_ = arglist[ai]
            # the bitcast view of the uploaded payload is the INPUT
            # surface itself, not an amplified plane — tiles read
            # straight from it
            if phys in ("INT32", "FLOAT"):
                arr = jax.lax.bitcast_convert_type(words_, _PHYS_NP[phys])
            else:  # INT64
                from ..ops.filter_gather import _join64

                lo = jax.lax.bitcast_convert_type(words_[0::2], jnp.int32)
                hi = jax.lax.bitcast_convert_type(words_[1::2], jnp.int32)
                arr = _join64(lo, hi, jnp.int64)
            read_tile = stream_reader(arr)
            out_dt = arr.dtype
        return tiled_fixed_unpack(vwords, out_dt, n, cap, has_def, tile,
                                  read_tile)

    def run(arglist):
            ai = 0
            if has_def:
                validity = unpack_bit_words(arglist[ai], cap)
                ai += 1
                validity = validity & (
                    jnp.arange(cap, dtype=jnp.int32) < n)
                vidx = jnp.clip(
                    jnp.cumsum(validity.astype(jnp.int32)) - 1, 0, cap - 1)
            else:
                validity = jnp.arange(cap, dtype=jnp.int32) < n
                vidx = None
            if is_dict:
                codes_ = arglist[ai].astype(jnp.int32)
                ai += 1
                if vidx is not None:
                    codes_ = jnp.take(codes_, vidx, mode="clip")
                elif codes_.shape[0] != cap:
                    codes_ = (
                        jnp.concatenate([
                            codes_,
                            jnp.zeros(cap - codes_.shape[0], jnp.int32)])
                        if codes_.shape[0] < cap else codes_[:cap]
                    )
                if is_str:
                    doff_, dch_ = arglist[ai], arglist[ai + 1]
                    from ..expr.eval import StrV
                    from ..ops.filter_gather import gather_string

                    D_ = doff_.shape[0] - 1
                    dsv = StrV(doff_, dch_, jnp.ones(D_, jnp.bool_))
                    if keep_dict:
                        from ..expr.values import DictV

                        return DictV(
                            jnp.clip(codes_, 0, D_ - 1), dsv, validity,
                            mat_cap=ccap, max_len=max_len, unique=True)
                    out = gather_string(
                        dsv, jnp.clip(codes_, 0, D_ - 1), validity, ccap)
                    return out.offsets, out.chars, validity
                dvals_ = arglist[ai]
                data = jnp.take(
                    dvals_, jnp.clip(codes_, 0, dvals_.shape[0] - 1),
                    mode="clip")
                data = jnp.where(validity, data,
                                 jnp.zeros((), data.dtype))
                return data, validity
            words_ = arglist[ai]
            if phys in ("INT32", "FLOAT"):
                dt = _PHYS_NP[phys]
                arr = jax.lax.bitcast_convert_type(words_, dt)
            else:  # INT64 (words padded to even count on host)
                from ..ops.filter_gather import _join64

                lo = jax.lax.bitcast_convert_type(words_[0::2], jnp.int32)
                hi = jax.lax.bitcast_convert_type(words_[1::2], jnp.int32)
                arr = _join64(lo, hi, jnp.int64)
            arr = (
                jnp.concatenate(
                    [arr, jnp.zeros(cap - arr.shape[0], arr.dtype)])
                if arr.shape[0] < cap else arr[:cap]
            )
            if vidx is not None:
                arr = jnp.take(arr, vidx, mode="clip")
            arr = jnp.where(validity, arr, jnp.zeros((), arr.dtype))
            return arr, validity

    return args, tuple(key), _named_decode(run_tiled if tiled else run)


def _named_decode(run):
    """A chunk decode under its word: the program is ``jit_pq_decode`` when
    it is jitted alone (``_run_decode``), and its operations carry the
    scope ``pq_decode`` when it is spliced into a fused stage program."""
    import jax

    from ..exec.base import program

    @program("pq_decode")
    def decode(arglist):
        with jax.named_scope("pq_decode"):
            return run(arglist)

    return decode


def stage_decode_args(per_col_args: Sequence[Sequence[np.ndarray]]):
    """Coalesce EVERY column's decode payloads (codes, validity words,
    dictionaries, plain words) into ONE host staging buffer and cross the
    host link in ONE transfer per row group, split/bitcast device-side by
    one jitted program — instead of one upload per buffer per column.
    Profiler-motivated (see docs/tuning.md): the parquet shape's scan time
    was dominated by per-buffer dispatch latency, ~3 buffers x N columns
    transfers per row group. Reference analog: the single
    HostMemoryBuffer the coalescing reader stitches before one cudf
    upload (GpuParquetScan.scala:880-900)."""
    from .arrow_convert import packed_upload

    flat = [a for args in per_col_args for a in args]
    if not flat:
        return [list(args) for args in per_col_args]
    devs = packed_upload(flat)
    out = []
    i = 0
    for args in per_col_args:
        out.append(list(devs[i: i + len(args)]))
        i += len(args)
    return out


def _run_decode(plan: ChunkPlan, dtype_tpu, key_t, run, dev_args):
    """Dispatch one column's cached decode program over its (already
    uploaded) args and wrap the result as a DeviceColumn."""
    import jax

    from ..exec.base import cached_pipeline

    fn = cached_pipeline(_DECODE_CACHE, key_t, "pq_decode",
                         lambda: jax.jit(run))
    out = fn(dev_args)
    from ..columnar.column import DeviceColumn
    from ..expr.values import DictV

    n = plan.num_values
    if isinstance(out, DictV):
        return DeviceColumn.dict_encoded(dtype_tpu, n, out)
    if plan.phys == "BYTE_ARRAY":
        offsets, chars, validity = out
        return DeviceColumn(dtype_tpu, n, None, validity, offsets, chars)
    data, validity = out
    return DeviceColumn(dtype_tpu, n, data, validity)


def chunk_to_device_column(plan: ChunkPlan, dtype_tpu, cap: int,
                           dict_strings: bool = False):
    """Upload a ChunkPlan's payloads (one staged transfer) and expand to a
    DeviceColumn in ONE jitted program (per structural cache key)."""
    args, key_t, run = plan_decode(plan, dtype_tpu, cap, dict_strings)
    dev_args = stage_decode_args([args])[0]
    return _run_decode(plan, dtype_tpu, key_t, run, dev_args)


# ---------------------------------------------------------------------------
# row group -> ColumnarBatch (with per-column host fallback)
# ---------------------------------------------------------------------------
import threading as _threading

_PQDEC_POOL = None
# created at import time: a lazily-created lock would itself need a lock
_PQDEC_POOL_LOCK = _threading.Lock()


def _decode_pool():
    """The PROCESS-SHARED srtpu-pqdec host-decode pool. One pool instead
    of one-per-call: the pipelined reader keeps tasks from several row
    groups in flight at once, and per-call pools would serialize at the
    row-group boundary (plus pay thread churn per row group). The native
    hybrid-decode calls release the GIL, so the pool gets real
    parallelism. IMPORTANT: tasks submitted here must never block on
    other tasks of this pool (deadlock); both submitters — _plan_columns
    and read_row_groups_pipelined — only submit leaf chunk-decode work."""
    global _PQDEC_POOL
    if _PQDEC_POOL is None:
        with _PQDEC_POOL_LOCK:
            if _PQDEC_POOL is None:
                import os
                from concurrent.futures import ThreadPoolExecutor

                _PQDEC_POOL = ThreadPoolExecutor(
                    max_workers=min(8, os.cpu_count() or 4),
                    thread_name_prefix="srtpu-pqdec")
    return _PQDEC_POOL


def _plan_columns(path, pf, rgmd, pqschema, name_to_ci, columns, file_bytes):
    """Host-plan every requested column chunk of one row group.
    Returns (plans by name, fallback column names)."""
    candidates = []
    fallback_cols: List[str] = []
    for name in columns:
        ci = name_to_ci.get(name)
        if ci is None:
            fallback_cols.append(name)
        else:
            candidates.append((name, ci))
    from ..exec.base import carry, phase

    plans: Dict[str, ChunkPlan] = {}
    if candidates:
        if file_bytes is None:
            with phase("read_file") as span, open(path, "rb") as f:
                file_bytes = f.read()
                span.set(file_bytes=len(file_bytes))

        def plan_one(item):
            name, ci = item
            pqcol = pqschema.column(ci)
            # the chunk's pages are the file bytes this plan touches
            with phase("page_plan",
                       file_bytes=rgmd.column(ci).total_compressed_size):
                try:
                    return name, plan_chunk(
                        file_bytes, rgmd.column(ci),
                        pqcol.max_definition_level,
                        pqcol.max_repetition_level)
                except Exception:
                    return name, None

        # chunk planning is native-decode-heavy (the C++ calls release the
        # GIL): plan all columns of the row group in parallel (reference
        # analog: the COALESCING reader's copy thread pool,
        # GpuParquetScan.scala:900)
        if len(candidates) > 1:
            with phase("plan_wait"):
                results = list(
                    _decode_pool().map(carry(plan_one), candidates))
        else:
            results = [plan_one(candidates[0])]
        for name, plan in results:
            if plan is None:
                fallback_cols.append(name)
            else:
                plans[name] = plan
    return plans, fallback_cols


def read_row_groups_pipelined(
    path: str, pf, rgs: Sequence[int], columns: Sequence[str], tpu_fields,
    file_bytes: Optional[bytes] = None, dict_strings: bool = False,
    max_in_flight: int = 3,
):
    """Pipelined decode→upload over many row groups: a generator yielding
    ``(rg, ColumnarBatch-or-None)`` in row-group order (None = no column
    took the device path; the caller falls back to the plain reader for
    the split). ``max_in_flight=1`` reproduces the round-6 serial
    decode→upload order exactly.

    The round-6 reader host-decoded a WHOLE row group, then staged one
    packed upload, then dispatched the device unpack — strictly serial,
    so the host link and the decoder thread pool took turns idling
    (parquet lost to pandas 0.94x in BENCH_r05 precisely here). Now:

      * row groups N+1..N+maxInFlight-1 host-decode on the shared
        srtpu-pqdec pool while row group N's staged transfer and device
        unpack run on the consumer thread (the bounded window caps host
        memory at ~maxInFlight decoded payloads);
      * within one row group, the first half of the column chunks to
        finish decoding stages+uploads immediately (double-buffered
        staging: two alternating packed transfers per row group) while
        the remaining chunks still decompress — decode of independent
        chunks overlaps the upload of already-finished ones;
      * columns the device decoder cannot take host-decode via pyarrow
        per column, exactly as before.

    Reference analog: the coalescing multithreaded reader's
    decode-while-copy pipeline (GpuParquetScan.scala:880-900, :1299).
    Abandoning the generator mid-flight is safe: outstanding pool tasks
    finish and their results are dropped."""
    import time as _time

    from concurrent.futures import FIRST_COMPLETED, wait

    from .. import events as _events
    from .. import obs as _obs
    from ..columnar.batch import ColumnarBatch
    from ..columnar.column import choose_capacity
    from ..exec.base import carry, phase
    from ..types import StructType
    from .arrow_convert import arrow_to_batch

    md = pf.metadata
    pqschema = pf.schema
    pool = _decode_pool()
    if file_bytes is None:
        with phase("read_file") as span, open(path, "rb") as f:
            file_bytes = f.read()
            span.set(file_bytes=len(file_bytes))
    fields_by_name = {f.name: f for f in tpu_fields}

    def plan_one(rg, rgmd, name, ci):
        t0 = _time.perf_counter_ns()
        if ci is None:
            return name, None, 0
        pqcol = pqschema.column(ci)
        with phase("page_plan",
                   file_bytes=rgmd.column(ci).total_compressed_size):
            try:
                plan = plan_chunk(
                    file_bytes, rgmd.column(ci),
                    pqcol.max_definition_level,
                    pqcol.max_repetition_level)
            except Exception:
                return name, None, 0
        if _events.enabled():
            _events.emit(
                "pq_pipeline", stage="decode", rg=rg,
                bytes=int(rgmd.column(ci).total_uncompressed_size),
                dur=_time.perf_counter_ns() - t0)
        if _obs.enabled():
            _obs.inc("tpu_pq_pipeline_stages", 1, stage="decode")
            _obs.inc("tpu_pq_pipeline_bytes",
                     int(rgmd.column(ci).total_uncompressed_size),
                     stage="decode")
        return name, plan, 0

    pending: Dict[int, tuple] = {}  # pos -> (rg, rgmd, [futures])

    def submit(pos):
        rg = rgs[pos]
        rgmd = md.row_group(rg)
        name_to_ci = {
            rgmd.column(i).path_in_schema: i
            for i in range(rgmd.num_columns)
        }
        # the tasks carry this thread's open section and query: the
        # page plans are spans of the same scan on the pool's threads
        task = carry(plan_one)
        futs = [
            pool.submit(task, rg, rgmd, name, name_to_ci.get(name))
            for name in columns
        ]
        pending[pos] = (rg, rgmd, futs)

    window = max(1, int(max_in_flight))
    for pos in range(min(window, len(rgs))):
        submit(pos)

    for pos in range(len(rgs)):
        rg, rgmd, futs = pending.pop(pos)
        n = rgmd.num_rows
        cap = choose_capacity(max(1, n))
        plans: Dict[str, ChunkPlan] = {}
        decoded: Dict[str, tuple] = {}   # name -> (key, run)
        dev_args: Dict[str, list] = {}
        fallback_cols: List[str] = []
        staged_names: List[str] = []
        flushed = False
        # deterministic double-buffer split: buffer A is the decoded
        # subset of the FIRST half of the declared column list, buffer B
        # the rest, each flushed in declared order. Decode COMPLETION
        # order must not leak into the packed layout: packed_upload keys
        # its unpack pipeline on the chunk layout tuple, so an order-
        # dependent split mints a fresh key per timing — the residual
        # warm compile miss on the bench cold_start parquet lane.
        order = {name: i for i, name in enumerate(columns)}
        first_half = frozenset(columns[:(len(columns) + 1) // 2])
        resolved: Set[str] = set()

        def flush(names):
            if not names:
                return
            t0 = _time.perf_counter_ns()
            staged = stage_decode_args([decoded[nm][0] for nm in names])
            nbytes = sum(
                a.size * a.dtype.itemsize
                for nm in names for a in decoded[nm][0])
            for nm, da in zip(names, staged):
                dev_args[nm] = da
            if _events.enabled():
                _events.emit("pq_pipeline", stage="upload", rg=rg,
                             bytes=int(nbytes),
                             dur=_time.perf_counter_ns() - t0)
            if _obs.enabled():
                _obs.inc("tpu_pq_pipeline_stages", 1, stage="upload")
                _obs.inc("tpu_pq_pipeline_bytes", int(nbytes),
                         stage="upload")

        remaining = set(futs)
        while remaining:
            with phase("plan_wait"):
                done, remaining = wait(remaining,
                                       return_when=FIRST_COMPLETED)
            for fut in done:
                name, plan, _ = fut.result()
                resolved.add(name)
                if plan is None:
                    fallback_cols.append(name)
                    continue
                try:
                    with phase("page_plan"):
                        args, key_t, run = plan_decode(
                            plan, fields_by_name[name].dataType, cap,
                            dict_strings)
                except _FallbackError:
                    fallback_cols.append(name)
                    continue
                plans[name] = plan
                decoded[name] = (args, key_t, run)
                staged_names.append(name)
            # double-buffered staging: once the whole first half has
            # resolved (decoded or fallen back), cross the link with
            # buffer A while the second half still decompresses
            if not flushed and first_half <= resolved:
                flush(sorted((nm for nm in staged_names
                              if nm in first_half),
                             key=order.__getitem__))
                staged_names = [nm for nm in staged_names
                                if nm not in first_half]
                flushed = True
        flush(sorted(staged_names, key=order.__getitem__))

        if not plans:
            yield rg, None
            continue
        # the columns the device decoder declined go through pyarrow on
        # the host, named and counted (ROADMAP B-I 4)
        host_cols = {}
        if fallback_cols:
            with phase("host_decode") as span:
                if span.on:
                    span.set(columns=len(fallback_cols),
                             names=",".join(fallback_cols))
                host_table = pf.read_row_groups([rg], columns=fallback_cols)
                for name in fallback_cols:
                    b = arrow_to_batch(
                        host_table.select([name]),
                        StructType((fields_by_name[name],)))
                    host_cols[name] = b.columns[0]

        t0 = _time.perf_counter_ns()
        cols = []
        fields = []
        with phase("decode_dispatch") as span:
            if span.on:
                span.set(gathers=sum(key_gathers(decoded[name][1])
                                     for name in plans))
            for name, f in zip(columns, tpu_fields):
                if name in plans:
                    _, key_t, run = decoded[name]
                    cols.append(_run_decode(
                        plans[name], f.dataType, key_t, run,
                        dev_args[name]))
                else:
                    cols.append(host_cols[name])
                fields.append(f)
        batch = ColumnarBatch(cols, StructType(tuple(fields)), n)
        if _events.enabled():
            _events.emit("pq_pipeline", stage="unpack", rg=rg, bytes=0,
                         dur=_time.perf_counter_ns() - t0)
        if _obs.enabled():
            _obs.inc("tpu_pq_pipeline_stages", 1, stage="unpack")
        # advance the window BEFORE yielding: the next row group's chunks
        # decode while the consumer touches this batch
        nxt = pos + window
        if nxt < len(rgs):
            submit(nxt)
        yield rg, batch


def row_group_device_plans(
    path: str, pf, rg: int, columns: Sequence[str], tpu_fields,
    file_bytes: Optional[bytes] = None, dict_strings: bool = False,
):
    """Stage-fusion variant of the row-group decode: host-plan ALL
    columns and return ``(num_rows, cap, entries)`` with entries =
    ``[(args, key, run, field), ...]`` — no device dispatch happens here
    beyond the argument uploads, so the consumer can splice ``run`` into
    one fused stage program. Returns None when ANY column needs the host
    decoder (the fused program has no host path)."""
    from ..columnar.column import choose_capacity

    md = pf.metadata
    rgmd = md.row_group(rg)
    pqschema = pf.schema
    name_to_ci = {
        rgmd.column(i).path_in_schema: i for i in range(rgmd.num_columns)
    }
    n = rgmd.num_rows
    cap = choose_capacity(max(1, n))
    plans, fallback_cols = _plan_columns(
        path, pf, rgmd, pqschema, name_to_ci, columns, file_bytes)
    if fallback_cols or len(plans) != len(columns):
        return None
    from ..exec.base import phase

    staged = []
    with phase("page_plan"):
        for name, f in zip(columns, tpu_fields):
            args, key, run = plan_decode(plans[name], f.dataType, cap,
                                         dict_strings)
            staged.append((args, key, run, f))
    # ONE host->device transfer for the whole row group's payloads
    dev_args = stage_decode_args([s[0] for s in staged])
    entries = [
        (da, key, run, f) for da, (_, key, run, f) in zip(dev_args, staged)
    ]
    return n, cap, entries
