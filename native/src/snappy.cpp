// Snappy raw-block decompressor, C++17, no dependencies.
//
// The codec of most parquet files (the writers' default): every page is one
// raw snappy block, not the framed stream format. The block is a varint of
// the uncompressed length, then elements whose tag byte's low two bits say
// what follows:
//   00 literal: (tag >> 2) + 1 bytes, or for 60..63 a 1..4-byte LE length-1;
//   01 copy: length 4 + ((tag >> 2) & 7), offset ((tag >> 5) << 8) | 1 byte;
//   10 copy: length (tag >> 2) + 1, offset a 2-byte LE;
//   11 copy: length (tag >> 2) + 1, offset a 4-byte LE.
// A copy may overlap its own output (offset < length): it repeats bytes.
// Every read and write is bounds-checked: corrupt input returns -1.
#include <cstdint>
#include <cstring>

namespace {

// The uncompressed length the block's preamble states, or -1 when the
// varint is malformed. *header_len receives the preamble's byte count.
int64_t preamble_length(const uint8_t* src, int64_t n, int64_t* header_len) {
    uint64_t len = 0;
    int64_t p = 0;
    for (int shift = 0; shift <= 28; shift += 7) {
        if (p >= n) return -1;
        const uint8_t b = src[p++];
        len |= static_cast<uint64_t>(b & 0x7F) << shift;
        if (!(b & 0x80)) {
            if (len > 0xFFFFFFFFu) return -1;
            *header_len = p;
            return static_cast<int64_t>(len);
        }
    }
    return -1;
}

}  // namespace

extern "C" {

// Decompress one raw block into dst[0..cap). Returns the byte count written
// (the preamble's length), or -1 when the block is corrupt, short, or does
// not fit in cap.
int64_t srtpu_snappy_decompress(const uint8_t* src, int64_t n, uint8_t* dst,
                                int64_t cap) {
    int64_t p = 0;
    const int64_t want = preamble_length(src, n, &p);
    if (want < 0 || want > cap) return -1;
    int64_t o = 0;
    while (p < n) {
        const uint8_t tag = src[p++];
        int64_t len;
        int64_t off;
        switch (tag & 3) {
            case 0: {  // literal
                len = (tag >> 2) + 1;
                if (len > 60) {
                    const int extra = static_cast<int>(len - 60);  // 1..4
                    if (p + extra > n) return -1;
                    uint32_t v = 0;
                    for (int i = 0; i < extra; ++i)
                        v |= static_cast<uint32_t>(src[p + i]) << (8 * i);
                    p += extra;
                    len = static_cast<int64_t>(v) + 1;
                }
                if (len > n - p || len > want - o) return -1;
                std::memcpy(dst + o, src + p, static_cast<size_t>(len));
                p += len;
                o += len;
                continue;
            }
            case 1:
                if (p + 1 > n) return -1;
                len = 4 + ((tag >> 2) & 7);
                off = (static_cast<int64_t>(tag >> 5) << 8) | src[p];
                p += 1;
                break;
            case 2:
                if (p + 2 > n) return -1;
                len = (tag >> 2) + 1;
                off = src[p] | (static_cast<int64_t>(src[p + 1]) << 8);
                p += 2;
                break;
            default: {
                if (p + 4 > n) return -1;
                len = (tag >> 2) + 1;
                uint32_t v;
                std::memcpy(&v, src + p, 4);
                off = v;
                p += 4;
                break;
            }
        }
        if (off <= 0 || off > o || len > want - o) return -1;
        uint8_t* d = dst + o;
        const uint8_t* s = d - off;
        if (off >= len) {
            std::memcpy(d, s, static_cast<size_t>(len));
        } else {  // overlapping: each byte may read one this copy wrote
            for (int64_t i = 0; i < len; ++i) d[i] = s[i];
        }
        o += len;
    }
    return o == want ? o : -1;
}

}  // extern "C"
