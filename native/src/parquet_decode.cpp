// Parquet RLE/bit-packed hybrid stream decoder, C++17, no dependencies.
//
// Reference analog: the native half of the reference's parquet decode —
// cudf's gpuDecodePages kernels behind GpuParquetScan.scala:1157. On TPU
// the dictionary-code EXPANSION happens on-device (XLA gathers,
// io/parquet_device.py); this native routine covers the host half that was
// previously vectorized-numpy: expanding the RLE/bit-packed hybrid streams
// (dictionary indices and definition levels) into narrow integer arrays.
// srtpu_pq_plan_chunk walks a whole column chunk in one call (page headers,
// snappy, dictionary, levels and codes), so ctypes drops the GIL once a
// chunk. A call a page (~106 a chunk at pyarrow's 20,000 rows a page)
// between Python header parses and decompress copies that hold the GIL
// does not scale: planning 28 TPC-H lineitem chunks of 2^21 rows on an
// 8-core x86 host took 495-640 ms on one thread and 548-965 ms on eight,
// whose tasks computed a quarter of their wall; one call a chunk takes
// 173-250 ms on one thread and 29-66 ms on eight.
#include <algorithm>
#include <cstdint>
#include <cstring>
#include <vector>

extern "C" int64_t srtpu_snappy_decompress(const uint8_t* src, int64_t n,
                                           uint8_t* dst, int64_t cap);

namespace {

// Decode one hybrid stream of n values (bit width bw) into out[0..n).
// Returns the byte position just after the stream, or -1 on malformed /
// short input. T is the output element (u8/u16/i32 picked by caller).
template <typename T>
int64_t decode_hybrid(const uint8_t* data, int64_t pos, int64_t end, int bw,
                      int64_t n, T* out) {
    if (bw == 0) {
        std::memset(out, 0, sizeof(T) * static_cast<size_t>(n));
        return pos;
    }
    if (bw < 0 || bw > 24) return -1;
    const int byte_w = (bw + 7) / 8;
    const uint32_t mask = (1u << bw) - 1;
    constexpr int64_t kMaxRuns = int64_t{1} << 20;  // adversarial-file guard
    int64_t got = 0;
    int64_t runs = 0;
    while (got < n && pos < end) {
        if (++runs > kMaxRuns) return -1;
        uint64_t header = 0;
        int shift = 0;
        while (true) {
            if (pos >= end || shift > 56) return -1;
            uint8_t b = data[pos++];
            header |= static_cast<uint64_t>(b & 0x7F) << shift;
            if (!(b & 0x80)) break;
            shift += 7;
        }
        if (header & 1) {  // bit-packed run: (header>>1) groups of 8
            const int64_t groups = static_cast<int64_t>(header >> 1);
            // bound BEFORE multiplying: a huge varint must not wrap the
            // products negative and slip past the range checks below
            if (groups < 0 || groups > (end - pos) / bw + 8) return -1;
            const int64_t count = groups * 8;
            const int64_t nbytes = groups * bw;
            if (pos + nbytes > end) return -1;
            const int64_t take = std::min(count, n - got);
            const uint8_t* p = data + pos;
            uint64_t buf = 0;
            int bits = 0;
            int64_t bi = 0;
            for (int64_t i = 0; i < take; ++i) {
                while (bits < bw) {
                    buf |= static_cast<uint64_t>(p[bi++]) << bits;
                    bits += 8;
                }
                out[got + i] = static_cast<T>(buf & mask);
                buf >>= bw;
                bits -= bw;
            }
            pos += nbytes;
            got += count;  // trailing pad values advance the logical count
        } else {  // RLE run
            const int64_t count = static_cast<int64_t>(header >> 1);
            if (pos + byte_w > end) return -1;
            uint32_t v = 0;
            for (int i = 0; i < byte_w; ++i)
                v |= static_cast<uint32_t>(data[pos + i]) << (8 * i);
            pos += byte_w;
            const int64_t take = std::min(count, n - got);
            std::fill(out + got, out + got + take, static_cast<T>(v));
            got += count;
        }
    }
    return got < n ? -1 : pos;
}

// ---------------------------------------------------------------------------
// the chunk planner: io/parquet_device.plan_chunk's page loop, page by page
// the same decisions, so both planners give the same ChunkPlan
// ---------------------------------------------------------------------------

// why a chunk is refused (io/parquet_device._NATIVE_REFUSALS says each in
// the words of the Python loop's _FallbackError)
enum Reason : int64_t {
    kOk = 0,
    kDictEncoding = 1,  // detail: the dictionary page's encoding
    kEncoding = 2,      // detail: the data page's encoding
    kPlainType = 3,     // PLAIN DOUBLE, BOOLEAN or BYTE_ARRAY values
    kMixed = 4,         // dictionary and PLAIN data pages in one chunk
    kBitWidth = 5,      // detail: the code bit width over 24
    kRepeated = 6,      // a v2 page with repetition levels
    kBoolDict = 7,      // a BOOLEAN dictionary
    kShortChunk = 8,    // detail: the values the pages held
    kMalformed = 9,     // a short or corrupt header, stream or page
};

// parquet physical types and codecs, as spark_rapids_tpu/native.py numbers
// them (_PQ_PHYS, PQ_PLAN_CODECS)
enum Phys : int32_t {
    kInt32 = 0, kInt64 = 1, kFloat = 2, kDouble = 3, kBoolean = 4,
    kByteArray = 5,
};

enum Codec : int32_t { kUncompressed = 0, kSnappy = 1 };

constexpr int32_t kDataPage = 0;
constexpr int32_t kDictionaryPage = 2;
constexpr int32_t kDataPageV2 = 3;
constexpr int32_t kEncPlain = 0;
constexpr int32_t kEncPlainDictionary = 2;
constexpr int32_t kEncRleDictionary = 8;
constexpr int64_t kMaxPageBytes = int64_t{1} << 31;
// a page header's num_values is a thrift i32; a chunk's values are bounded
// so that no count of them times a value's bytes wraps a size_t
constexpr int64_t kMaxPageValues = INT32_MAX;
constexpr int64_t kMaxChunkValues = int64_t{1} << 40;

// thrift compact types
constexpr int kTStop = 0, kTTrue = 1, kTFalse = 2, kTByte = 3, kTI16 = 4,
              kTI32 = 5, kTI64 = 6, kTDouble = 7, kTBinary = 8, kTList = 9,
              kTSet = 10, kTMap = 11, kTStruct = 12;

// A refusal unwinds the page loop to the extern "C" boundary.
struct Refusal {
    int64_t reason;
    int64_t detail;
};

[[noreturn]] void refuse(int64_t reason, int64_t detail = 0) {
    throw Refusal{reason, detail};
}

// Thrift compact-protocol reader over [0, end): the Python _Reader's rules
// (a field id by delta or zigzag, skip by type; a bool field's value is its
// type), every read bounds-checked.
struct Thrift {
    const uint8_t* buf;
    int64_t pos;
    int64_t end;

    uint8_t byte() {
        if (pos >= end) refuse(kMalformed);
        return buf[pos++];
    }
    uint64_t varint() {
        uint64_t r = 0;
        for (int shift = 0; shift < 64; shift += 7) {
            const uint8_t b = byte();
            r |= static_cast<uint64_t>(b & 0x7F) << shift;
            if (!(b & 0x80)) return r;
        }
        refuse(kMalformed);
    }
    int64_t zigzag() {
        const uint64_t v = varint();
        return static_cast<int64_t>(v >> 1) ^ -static_cast<int64_t>(v & 1);
    }
    void advance(uint64_t n) {
        if (n > static_cast<uint64_t>(end - pos)) refuse(kMalformed);
        pos += static_cast<int64_t>(n);
    }
    void skip(int type, int depth) {
        if (depth > 32) refuse(kMalformed);
        switch (type) {
            case kTTrue:
            case kTFalse:
                return;
            case kTByte:
                advance(1);
                return;
            case kTI16:
            case kTI32:
            case kTI64:
                varint();
                return;
            case kTDouble:
                advance(8);
                return;
            case kTBinary:
                advance(varint());
                return;
            case kTList:
            case kTSet: {
                const uint8_t b = byte();
                uint64_t size = b >> 4;
                if (size == 15) size = varint();
                for (uint64_t i = 0; i < size; ++i) skip(b & 0x0F, depth + 1);
                return;
            }
            case kTMap: {
                const uint64_t size = varint();
                if (size) {
                    const uint8_t kv = byte();
                    for (uint64_t i = 0; i < size; ++i) {
                        skip(kv >> 4, depth + 1);
                        skip(kv & 0x0F, depth + 1);
                    }
                }
                return;
            }
            case kTStruct:
                fields(depth + 1,
                       [&](int64_t, int ft) { skip(ft, depth + 1); });
                return;
            default:
                refuse(kMalformed);
        }
    }
    // on_field(field id, type) must consume the value
    template <typename F>
    void fields(int depth, F on_field) {
        if (depth > 32) refuse(kMalformed);
        int64_t fid = 0;
        while (true) {
            const uint8_t b = byte();
            if (b == kTStop) return;
            const int delta = b >> 4;
            const int ft = b & 0x0F;
            fid = delta ? fid + delta : zigzag();
            on_field(fid, ft);
        }
    }
};

struct PageHeader {
    int64_t type = -1;
    int64_t uncompressed_size = 0;
    int64_t compressed_size = 0;
    int64_t num_values = 0;
    int64_t encoding = kEncPlain;
    int64_t num_nulls = 0;
    int64_t def_levels_len = 0;
    int64_t rep_levels_len = 0;
    bool v2_is_compressed = true;
};

// io/parquet_device.parse_page_header, field for field
PageHeader parse_page_header(Thrift& rd) {
    PageHeader ph;
    rd.fields(0, [&](int64_t fid, int ft) {
        if (fid == 1) {
            ph.type = rd.zigzag();
        } else if (fid == 2) {
            ph.uncompressed_size = rd.zigzag();
        } else if (fid == 3) {
            ph.compressed_size = rd.zigzag();
        } else if ((fid == 5 || fid == 7) && ft == kTStruct) {
            // data page and dictionary page headers: the same two fields
            rd.fields(1, [&](int64_t f, int t) {
                if (f == 1) ph.num_values = rd.zigzag();
                else if (f == 2) ph.encoding = rd.zigzag();
                else rd.skip(t, 1);
            });
        } else if (fid == 8 && ft == kTStruct) {
            rd.fields(1, [&](int64_t f, int t) {
                if (f == 1) ph.num_values = rd.zigzag();
                else if (f == 2) ph.num_nulls = rd.zigzag();
                else if (f == 4) ph.encoding = rd.zigzag();
                else if (f == 5) ph.def_levels_len = rd.zigzag();
                else if (f == 6) ph.rep_levels_len = rd.zigzag();
                else if (f == 7) ph.v2_is_compressed = t == kTTrue;
                else rd.skip(t, 1);
            });
        } else {
            rd.skip(ft, 0);
        }
    });
    return ph;
}

int code_width(int bw) { return bw <= 8 ? 1 : bw <= 16 ? 2 : 4; }

// What one chunk plans to: ChunkPlan's arrays, held until the caller has
// sized its own and srtpu_pq_plan_take copies them out.
struct ChunkOut {
    std::vector<uint8_t> validity;  // 0/1 a row; only where has_nulls
    bool has_validity = false;
    // a present value's dictionary code, `cw` bytes each: the widest
    // page's width (1, 2 or 4 by bit width) until finish() narrows them
    std::vector<uint8_t> codes;
    int cw = 1;
    bool saw_dict = false;
    bool saw_plain = false;
    std::vector<uint8_t> plain;     // PLAIN pages' value bytes
    bool has_dict = false;
    int64_t dict_count = 0;
    std::vector<uint8_t> dict_values;
    std::vector<int64_t> dict_offsets;
    std::vector<uint8_t> dict_chars;
};

// Code i of `codes`, `width` bytes each.
inline int32_t code_at(const uint8_t* codes, int width, size_t i) {
    if (width == 1) return codes[i];
    if (width == 2) {
        uint16_t c;
        std::memcpy(&c, codes + 2 * i, 2);
        return c;
    }
    int32_t c;
    std::memcpy(&c, codes + 4 * i, 4);
    return c;
}

// f(code) over `count` codes of `width` bytes each, the width resolved once.
template <typename F>
void each_code(const uint8_t* codes, int width, size_t count, F f) {
    if (width == 1) {
        for (size_t i = 0; i < count; ++i) f(static_cast<int32_t>(codes[i]));
    } else if (width == 2) {
        for (size_t i = 0; i < count; ++i) f(code_at(codes, 2, i));
    } else {
        for (size_t i = 0; i < count; ++i) f(code_at(codes, 4, i));
    }
}

// Rewrite `count` codes of `from` bytes each as `to` bytes each, in place
// (the vector already holds count * max(from, to) bytes).
void rewidth(uint8_t* codes, size_t count, int from, int to) {
    auto put = [&](size_t i, int32_t c) {
        if (to == 1) codes[i] = static_cast<uint8_t>(c);
        else if (to == 2) {
            const uint16_t v = static_cast<uint16_t>(c);
            std::memcpy(codes + 2 * i, &v, 2);
        } else {
            std::memcpy(codes + 4 * i, &c, 4);
        }
    };
    if (to < from) {  // narrower: front to back never overwrites unread
        for (size_t i = 0; i < count; ++i) put(i, code_at(codes, from, i));
    } else {          // wider: back to front
        for (size_t i = count; i-- > 0;) put(i, code_at(codes, from, i));
    }
}

struct Planner {
    const uint8_t* file;
    int64_t file_len;
    int32_t codec;
    int32_t phys;
    int64_t itemsize;
    int32_t max_def;
    bool has_nulls;
    int64_t num_values;  // the chunk's, to size its arrays once
    ChunkOut out;
    std::vector<uint8_t> scratch;

    // the bytes [pos, pos + n) of the file that exist, as a Python slice
    // takes them
    int64_t avail(int64_t pos, int64_t n) const {
        if (pos >= file_len || n <= 0) return 0;
        return std::min(n, file_len - pos);
    }

    // a page's payload, decompressed to `size` bytes
    const uint8_t* inflate(const uint8_t* src, int64_t n, int64_t size,
                           int64_t* len) {
        if (codec == kUncompressed) {
            *len = n;
            return src;
        }
        if (size < 0 || size > kMaxPageBytes) refuse(kMalformed);
        scratch.resize(static_cast<size_t>(std::max<int64_t>(size, 1)));
        if (srtpu_snappy_decompress(src, n, scratch.data(), size) != size)
            refuse(kMalformed);
        *len = size;
        return scratch.data();
    }

    // a page's definition levels (bit width 1, as the Python loop reads
    // them) appended to the validity; returns the present count
    int64_t levels(const uint8_t* data, int64_t end, int64_t count) {
        if (count < 0) refuse(kMalformed);
        if (!out.has_validity)
            out.validity.reserve(static_cast<size_t>(num_values));
        const size_t at = out.validity.size();
        out.validity.resize(at + static_cast<size_t>(count));
        uint8_t* v = out.validity.data() + at;
        if (decode_hybrid(data, 0, end, 1, count, v) < 0) refuse(kMalformed);
        int64_t presents = 0;
        for (int64_t i = 0; i < count; ++i) {
            v[i] = v[i] == 1;
            presents += v[i];
        }
        out.has_validity = true;
        return presents;
    }

    void dictionary(const uint8_t* raw, int64_t len, int64_t count) {
        if (count < 0 || count > kMaxPageValues) refuse(kMalformed);
        if (phys == kByteArray) {
            if (count > len / 4) refuse(kMalformed);  // a length an entry
            std::vector<int64_t> offsets(static_cast<size_t>(count) + 1);
            std::vector<uint8_t> chars;
            chars.reserve(static_cast<size_t>(std::max<int64_t>(
                0, len - 4 * count)));
            int64_t p = 0;
            for (int64_t i = 0; i < count; ++i) {
                if (p + 4 > len) refuse(kMalformed);
                uint32_t ln;
                std::memcpy(&ln, raw + p, 4);
                p += 4;
                if (ln > len - p) refuse(kMalformed);
                chars.insert(chars.end(), raw + p, raw + p + ln);
                p += ln;
                offsets[static_cast<size_t>(i) + 1] =
                    static_cast<int64_t>(chars.size());
            }
            out.dict_offsets.swap(offsets);
            out.dict_chars.swap(chars);
        } else if (phys == kBoolean) {
            refuse(kBoolDict);
        } else {
            if (count > len / itemsize) refuse(kMalformed);
            out.dict_values.assign(raw, raw + count * itemsize);
        }
        out.has_dict = true;
        out.dict_count = count;
    }

    // the Python loop's handle_values: one data page's values
    void values(const uint8_t* raw, int64_t p, int64_t len, int64_t enc,
                int64_t presents) {
        // walk() bounds it by the page's num_values: presents * 8 and
        // held + presents (both at most the chunk's values) cannot wrap
        if (presents < 0 || presents > kMaxPageValues) refuse(kMalformed);
        if (enc == kEncRleDictionary || enc == kEncPlainDictionary) {
            const int bw = p < len ? raw[p] : 0;
            if (bw > 24) refuse(kBitWidth, bw);
            const int w = code_width(bw);
            const size_t held = out.codes.size() / out.cw;
            if (!out.saw_dict) {
                out.cw = w;
                out.codes.reserve(static_cast<size_t>(num_values) * w);
            } else if (w > out.cw) {  // a wider page: widen what is held
                out.codes.resize(held * w);
                rewidth(out.codes.data(), held, out.cw, w);
                out.cw = w;
            }
            out.codes.resize((held + static_cast<size_t>(presents)) * out.cw);
            uint8_t* at = out.codes.data() + held * out.cw;
            const int64_t rc =
                out.cw == 1 ? decode_hybrid(raw, p + 1, len, bw, presents, at)
                : out.cw == 2
                    ? decode_hybrid(raw, p + 1, len, bw, presents,
                                    reinterpret_cast<uint16_t*>(at))
                    : decode_hybrid(raw, p + 1, len, bw, presents,
                                    reinterpret_cast<int32_t*>(at));
            if (rc < 0) refuse(kMalformed);
            out.saw_dict = true;
        } else if (enc == kEncPlain) {
            if (phys == kByteArray || phys == kBoolean || phys == kDouble)
                refuse(kPlainType);
            const int64_t take =
                p < len ? std::min(presents * itemsize, len - p) : 0;
            out.plain.insert(out.plain.end(), raw + p, raw + p + take);
            out.saw_plain = true;
        } else {
            refuse(kEncoding, enc);
        }
        if (out.saw_dict && out.saw_plain) refuse(kMixed);
    }

    // the page loop; returns the values the data pages held
    int64_t walk(int64_t pos, int64_t end, int64_t n, int64_t* pages) {
        int64_t seen = 0;
        while (pos < end && seen < n) {
            if (pos < 0 || pos >= file_len) refuse(kMalformed);
            Thrift rd{file, pos, file_len};
            const PageHeader ph = parse_page_header(rd);
            ++*pages;
            pos = rd.pos;
            if (ph.compressed_size < 0 || ph.compressed_size > kMaxPageBytes)
                refuse(kMalformed);
            const uint8_t* payload = file + pos;
            const int64_t plen = avail(pos, ph.compressed_size);
            pos += ph.compressed_size;
            if ((ph.type == kDataPage || ph.type == kDataPageV2) &&
                (ph.num_values < 0 || ph.num_values > kMaxPageValues ||
                 ph.num_values > n - seen || ph.num_nulls < 0 ||
                 ph.num_nulls > ph.num_values))
                refuse(kMalformed);  // out of range, or past the chunk's values
            if (ph.type == kDictionaryPage) {
                if (ph.encoding != kEncPlain &&
                    ph.encoding != kEncPlainDictionary)
                    refuse(kDictEncoding, ph.encoding);
                int64_t len;
                const uint8_t* raw =
                    inflate(payload, plen, ph.uncompressed_size, &len);
                dictionary(raw, len, ph.num_values);
            } else if (ph.type == kDataPage) {
                int64_t len;
                const uint8_t* raw =
                    inflate(payload, plen, ph.uncompressed_size, &len);
                int64_t p = 0;
                int64_t presents = ph.num_values;
                if (max_def > 0) {
                    if (len < 4) refuse(kMalformed);
                    uint32_t ln;
                    std::memcpy(&ln, raw, 4);
                    p = 4;
                    if (has_nulls)
                        presents = levels(raw + p, std::min<int64_t>(
                            ln, len - p), ph.num_values);
                    p += ln;
                }
                values(raw, p, len, ph.encoding, presents);
                seen += ph.num_values;
            } else if (ph.type == kDataPageV2) {
                if (ph.rep_levels_len) refuse(kRepeated);
                if (ph.def_levels_len < 0) refuse(kMalformed);
                const int64_t presents =
                    ph.num_values - (max_def > 0 ? ph.num_nulls : 0);
                if (max_def > 0 && has_nulls) {
                    if (ph.def_levels_len) {
                        levels(payload, std::min(ph.def_levels_len, plen),
                               ph.num_values);
                    } else {
                        out.validity.insert(out.validity.end(),
                                            static_cast<size_t>(
                                                ph.num_values), 1);
                        out.has_validity = true;
                    }
                }
                const int64_t skip = std::min(ph.def_levels_len, plen);
                const uint8_t* vals = payload + skip;
                int64_t vlen = plen - skip;
                if (ph.v2_is_compressed && codec != kUncompressed)
                    vals = inflate(vals, vlen,
                                   ph.uncompressed_size - ph.def_levels_len,
                                   &vlen);
                values(vals, 0, vlen, ph.encoding, presents);
                seen += ph.num_values;
            }
            // index pages and the like: skipped
        }
        return seen;
    }

    // the codes at the narrowest width that holds what the chunk holds
    // (its pages' widest when it holds none); returns the byte pool a
    // BYTE_ARRAY dictionary's strings fill, sum of len[min(code, D - 1)]
    int64_t finish() {
        const int cw = out.cw;
        const size_t count = out.codes.size() / cw;
        const uint8_t* codes = out.codes.data();
        if (count && cw > 1) {
            int32_t mx = 0;
            each_code(codes, cw, count,
                      [&](int32_t c) { mx = std::max(mx, c); });
            const int width = mx < 256 ? 1 : mx < 65536 ? 2 : 4;
            if (width < cw) {
                rewidth(out.codes.data(), count, cw, width);
                out.codes.resize(count * width);
                out.cw = width;
            }
        }
        int64_t total = 0;
        if (phys == kByteArray && out.has_dict && out.dict_count > 0) {
            const int32_t last = static_cast<int32_t>(
                std::min<int64_t>(out.dict_count - 1, INT32_MAX));
            std::vector<int64_t> uses(static_cast<size_t>(last) + 1);
            if (out.cw == 1) {  // four tallies: no store waits on the last
                int64_t four[4][256] = {};
                const uint8_t* c = out.codes.data();
                size_t i = 0;
                for (; i + 4 <= count; i += 4)
                    for (int k = 0; k < 4; ++k) ++four[k][c[i + k]];
                for (; i < count; ++i) ++four[0][c[i]];
                for (int v = 0; v < 256; ++v)
                    uses[std::min<int32_t>(v, last)] +=
                        four[0][v] + four[1][v] + four[2][v] + four[3][v];
            } else {
                each_code(out.codes.data(), out.cw, count,
                          [&](int32_t c) { ++uses[std::min(c, last)]; });
            }
            const int64_t* off = out.dict_offsets.data();
            for (int32_t k = 0; k <= last; ++k)
                total += uses[k] * (off[k + 1] - off[k]);
        }
        return total;
    }
};

}  // namespace

extern "C" {

// out_width selects the output element size: 1 (u8), 2 (u16), 4 (i32).
// Returns the byte position after the stream, or -1 on error.
int64_t srtpu_pq_hybrid_decode(const uint8_t* data, int64_t pos, int64_t end,
                               int32_t bw, int64_t n, int32_t out_width,
                               void* out) {
    switch (out_width) {
        case 1:
            return decode_hybrid(data, pos, end, bw, n,
                                 static_cast<uint8_t*>(out));
        case 2:
            return decode_hybrid(data, pos, end, bw, n,
                                 static_cast<uint16_t*>(out));
        case 4:
            return decode_hybrid(data, pos, end, bw, n,
                                 static_cast<int32_t*>(out));
        default:
            return -1;
    }
}

// Parse a BYTE_ARRAY PLAIN dictionary page: count (u32-len, bytes) entries.
// Writes count+1 int32 offsets and the concatenated chars; returns total
// char bytes, or -1 if the payload is malformed / chars overflow char_cap.
int64_t srtpu_pq_binary_dict(const uint8_t* raw, int64_t len, int64_t count,
                             int32_t* offsets, uint8_t* chars,
                             int64_t char_cap) {
    int64_t p = 0;
    int64_t total = 0;
    offsets[0] = 0;
    for (int64_t i = 0; i < count; ++i) {
        if (p + 4 > len) return -1;
        uint32_t ln;
        std::memcpy(&ln, raw + p, 4);
        p += 4;
        if (p + ln > len || total + ln > char_cap) return -1;
        std::memcpy(chars + total, raw + p, ln);
        p += ln;
        total += ln;
        offsets[i + 1] = static_cast<int32_t>(total);
    }
    return total;
}

// The words srtpu_pq_plan_chunk writes to info[]; the layout is
// spark_rapids_tpu/native.py's _PLAN_INFO.
enum Info {
    kReason = 0,       // 0, or why the chunk was refused
    kDetail = 1,       // the refusal's number (an encoding, a bit width)
    kPages = 2,        // page headers walked
    kValidity = 3,     // validity length, -1 for none
    kKind = 4,         // 0 no values, 1 codes, 2 PLAIN bytes
    kCount = 5,        // codes, or PLAIN bytes
    kWidth = 6,        // bytes a code
    kDict = 7,         // dictionary entries, -1 for no dictionary page
    kDictChars = 8,    // a BYTE_ARRAY dictionary's char bytes
    kStringBytes = 9,  // sum of len[code], -1 unless a BYTE_ARRAY dictionary
    kInfoWords = 10,
};

// Plan one column chunk: the pages in file[off, end) of a flat column of
// num_values values (io/parquet_device.plan_chunk's arguments, the codec
// and the physical type by number). Returns a handle for
// srtpu_pq_plan_take, with info[] sizing what it holds; or null with
// info[kReason] set when the chunk is refused. Never reads outside
// file[0, file_len).
void* srtpu_pq_plan_chunk(const uint8_t* file, int64_t file_len, int64_t off,
                          int64_t end, int32_t codec, int32_t phys,
                          int64_t num_values, int32_t max_def,
                          int32_t has_nulls, int64_t* info) {
    std::fill(info, info + kInfoWords, 0);
    static const int64_t kItem[] = {4, 8, 4, 8, 1, 0};
    if ((codec != kUncompressed && codec != kSnappy) || phys < kInt32 ||
        phys > kByteArray || num_values < 0 ||
        num_values > kMaxChunkValues) {
        info[kReason] = kMalformed;
        return nullptr;
    }
    Planner* pl = nullptr;
    try {
        pl = new Planner{file,    file_len,       codec,      phys,
                         kItem[phys], max_def, has_nulls != 0, num_values,
                         {},      {}};
        const int64_t seen = pl->walk(off, end, num_values, &info[kPages]);
        if (seen < num_values) refuse(kShortChunk, seen);
        const int64_t total = pl->finish();
        const ChunkOut& o = pl->out;
        info[kValidity] = o.has_validity
            ? static_cast<int64_t>(o.validity.size()) : -1;
        info[kKind] = o.saw_dict ? 1 : o.saw_plain ? 2 : 0;
        info[kCount] = o.saw_dict
            ? static_cast<int64_t>(o.codes.size()) / o.cw
            : static_cast<int64_t>(o.plain.size());
        info[kWidth] = o.cw;
        info[kDict] = o.has_dict ? o.dict_count : -1;
        info[kDictChars] = static_cast<int64_t>(o.dict_chars.size());
        info[kStringBytes] = phys == kByteArray && o.has_dict ? total : -1;
        return pl;
    } catch (const Refusal& r) {
        info[kReason] = r.reason;
        info[kDetail] = r.detail;
    } catch (...) {  // an allocation a corrupt size asked for
        info[kReason] = kMalformed;
    }
    delete pl;
    return nullptr;
}

// Copy a planned chunk's arrays into the caller's (each sized by info[];
// null for one it does not want) and free the handle.
void srtpu_pq_plan_take(void* handle, uint8_t* validity, void* codes,
                        uint8_t* plain, uint8_t* dict_values,
                        int64_t* dict_offsets, uint8_t* dict_chars) {
    Planner* pl = static_cast<Planner*>(handle);
    const ChunkOut& o = pl->out;
    auto copy = [](void* dst, const auto& v) {
        if (dst && !v.empty())
            std::memcpy(dst, v.data(), v.size() * sizeof(v[0]));
    };
    copy(validity, o.validity);
    copy(codes, o.codes);
    copy(plain, o.plain);
    copy(dict_values, o.dict_values);
    copy(dict_offsets, o.dict_offsets);
    copy(dict_chars, o.dict_chars);
    delete pl;
}

}  // extern "C"
