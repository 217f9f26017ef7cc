// Native host-side hot path for the serving batcher.
//
// The reference keeps its entire runtime on the JVM and delegates native
// execution to external binaries (SURVEY.md §2.3); here the TPU compute path
// is XLA/Pallas and THIS file is the native runtime for the host side of the
// request path: the fold/pack/pad batch assembly that sits between protobuf
// decode and device transfer. The numpy implementation of the same steps
// (ops/transfer.py + batcher padding) makes several full passes and
// temporaries per batch; these kernels do each transform in one pass.
//
// Exposed via a C ABI for ctypes (pybind11 is not in this image). All
// functions are thread-safe (pure element-wise transforms on caller-owned
// buffers).

#include <chrono>
#include <cstdint>
#include <cstring>
#include <type_traits>

namespace {

// One multiply-xor round: full 128-bit product folded to 64 bits. The
// multiply diffuses every input bit across the word; the xor of hi/lo keeps
// both halves.
inline uint64_t mix64(uint64_t a, uint64_t b) {
  __uint128_t m = static_cast<__uint128_t>(a) * b;
  return static_cast<uint64_t>(m) ^ static_cast<uint64_t>(m >> 64);
}

// THE fold: mathematical mod (result in [0, vocab)), pow2 fast path.
// Shared by fold_i32 and the batch assembler so the semantics cannot
// drift between them.
inline int64_t fold1(int64_t v, int64_t vocab, bool pow2, int64_t mask) {
  if (pow2) return v & mask;
  int64_t r = v % vocab;
  return r < 0 ? r + vocab : r;
}

// f32 bits -> bf16 bits, round-to-nearest-even with NaN quieting (the one
// rounding rule, shared by the exported f32_to_bf16 and the batch assembler).
inline uint16_t bf16_bits(uint32_t u) {
  if ((u & 0x7fffffffu) > 0x7f800000u) {   // NaN: keep quiet, drop payload
    return static_cast<uint16_t>((u >> 16) | 0x0040u);
  }
  uint32_t rounding = 0x7fffu + ((u >> 16) & 1u);
  return static_cast<uint16_t>((u + rounding) >> 16);
}

// The combined upload's word format (ops/transfer.py): a segment of
// BITS-wide values travels as PLANES of whole rows, the planes' values at one
// position concatenated little-endian into whole 32-bit words, so the device
// rebuilds each plane with element-wise shifts and masks on uint32 arrays and
// never sees a sub-word tensor with a 2-, 3- or 4-wide minor dimension.
//   32 bits: 1 plane, 1 word    16 bits: 2 planes, 1 word
//    8 bits: 4 planes, 1 word   24 bits: 4 planes, 3 words
// pack_words writes `count` positions: word g of position i goes to
// out[g * stride + i]; load(p, i) is plane p's value there (its low BITS
// bits are kept).
template <int BITS, class Load>
inline void pack_words(Load load, int64_t count, uint32_t* out,
                       int64_t stride) {
  static_assert(BITS == 8 || BITS == 16 || BITS == 24, "sub-word widths");
  for (int64_t i = 0; i < count; ++i) {
    if constexpr (BITS == 16) {
      out[i] = (load(0, i) & 0xffffu) | (load(1, i) << 16);
    } else if constexpr (BITS == 8) {
      out[i] = (load(0, i) & 0xffu) | ((load(1, i) & 0xffu) << 8) |
               ((load(2, i) & 0xffu) << 16) | (load(3, i) << 24);
    } else {
      const uint32_t v0 = load(0, i) & 0xffffffu, v1 = load(1, i) & 0xffffffu,
                     v2 = load(2, i) & 0xffffffu, v3 = load(3, i);
      out[i] = v0 | (v1 << 24);
      out[stride + i] = (v1 >> 8) | (v2 << 16);
      out[2 * stride + i] = (v2 >> 16) | (v3 << 8);
    }
  }
}

constexpr int planes_of(int bits) { return bits == 16 ? 2 : 4; }

// One padded [n_rows, inner] array -> its segment of words. Plane p holds
// rows [p * q, (p + 1) * q), q = ceil(n_rows / planes); rows past n_rows
// read as zero. value(k) is the BITS-wide value of flat element k. The
// positions every plane has a row for take the loop without bounds checks.
template <int BITS, class Value>
inline void pack_planes(Value value, int64_t n_rows, int64_t inner,
                        uint32_t* out) {
  constexpr int P = planes_of(BITS);
  const int64_t q = (n_rows + P - 1) / P;
  const int64_t stride = q * inner, total = n_rows * inner;
  int64_t full = total - (P - 1) * stride;  // the last plane's elements
  if (full < 0) full = 0;
  pack_words<BITS>(
      [&](int p, int64_t i) { return value(p * stride + i); }, full, out,
      stride);
  pack_words<BITS>(
      [&](int p, int64_t i) {
        const int64_t k = p * stride + full + i;
        return k < total ? value(k) : 0u;
      },
      stride - full, out + full, stride);
}

// ----------------------------------------------------------------------
// blake2b (RFC 7693), keyless, 16-byte digest — the EXACT function
// hashlib.blake2b(digest_size=16) computes. Unlike hash128 above (a fast
// non-cryptographic mix private to the device-input cache), these digests
// are a WIRE contract: the row-cache keys, the dedup row identity, and
// the label-join keys clients compute over the bytes they sent must all
// be byte-identical with or without the compiled host ops — so the native
// batched path must be the real blake2b, not a lookalike.

constexpr uint64_t kB2bIV[8] = {
    0x6a09e667f3bcc908ull, 0xbb67ae8584caa73bull, 0x3c6ef372fe94f82bull,
    0xa54ff53a5f1d36f1ull, 0x510e527fade682d1ull, 0x9b05688c2b3e6c1full,
    0x1f83d9abfb41bd6bull, 0x5be0cd19137e2179ull};

constexpr uint8_t kB2bSigma[12][16] = {
    {0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15},
    {14, 10, 4, 8, 9, 15, 13, 6, 1, 12, 0, 2, 11, 7, 5, 3},
    {11, 8, 12, 0, 5, 2, 15, 13, 10, 14, 3, 6, 7, 1, 9, 4},
    {7, 9, 3, 1, 13, 12, 11, 14, 2, 6, 5, 10, 4, 0, 15, 8},
    {9, 0, 5, 7, 2, 4, 10, 15, 14, 1, 11, 12, 6, 8, 3, 13},
    {2, 12, 6, 10, 0, 11, 8, 3, 4, 13, 7, 5, 15, 14, 1, 9},
    {12, 5, 1, 15, 14, 13, 4, 10, 0, 7, 6, 3, 9, 2, 8, 11},
    {13, 11, 7, 14, 12, 1, 3, 9, 5, 0, 15, 4, 8, 6, 2, 10},
    {6, 15, 14, 9, 11, 3, 0, 8, 12, 2, 13, 7, 1, 4, 10, 5},
    {10, 2, 8, 4, 7, 6, 1, 5, 15, 11, 9, 14, 3, 12, 13, 0},
    {0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15},
    {14, 10, 4, 8, 9, 15, 13, 6, 1, 12, 0, 2, 11, 7, 5, 3}};

inline uint64_t rotr64(uint64_t x, int n) { return (x >> n) | (x << (64 - n)); }

inline void b2b_g(uint64_t* v, int a, int b, int c, int d, uint64_t x,
                  uint64_t y) {
  v[a] = v[a] + v[b] + x;
  v[d] = rotr64(v[d] ^ v[a], 32);
  v[c] = v[c] + v[d];
  v[b] = rotr64(v[b] ^ v[c], 24);
  v[a] = v[a] + v[b] + y;
  v[d] = rotr64(v[d] ^ v[a], 16);
  v[c] = v[c] + v[d];
  v[b] = rotr64(v[b] ^ v[c], 63);
}

// One 128-byte block. `t` is the byte counter INCLUDING this block (the
// messages here are < 2^64 bytes, so the high counter word stays 0).
void b2b_compress(uint64_t h[8], const uint8_t block[128], uint64_t t,
                  bool last) {
  uint64_t m[16], v[16];
  for (int i = 0; i < 16; ++i) std::memcpy(&m[i], block + 8 * i, 8);
  for (int i = 0; i < 8; ++i) {
    v[i] = h[i];
    v[i + 8] = kB2bIV[i];
  }
  v[12] ^= t;
  if (last) v[14] = ~v[14];
  for (int r = 0; r < 12; ++r) {
    const uint8_t* s = kB2bSigma[r];
    b2b_g(v, 0, 4, 8, 12, m[s[0]], m[s[1]]);
    b2b_g(v, 1, 5, 9, 13, m[s[2]], m[s[3]]);
    b2b_g(v, 2, 6, 10, 14, m[s[4]], m[s[5]]);
    b2b_g(v, 3, 7, 11, 15, m[s[6]], m[s[7]]);
    b2b_g(v, 0, 5, 10, 15, m[s[8]], m[s[9]]);
    b2b_g(v, 1, 6, 11, 12, m[s[10]], m[s[11]]);
    b2b_g(v, 2, 7, 8, 13, m[s[12]], m[s[13]]);
    b2b_g(v, 3, 4, 9, 14, m[s[14]], m[s[15]]);
  }
  for (int i = 0; i < 8; ++i) h[i] ^= v[i] ^ v[i + 8];
}

// blake2b-128 of the two-segment message header||body (the row digest's
// shape: a per-batch structure header prefixed to every row's bytes,
// without materializing the concatenation).
void blake2b16_2seg(const uint8_t* s1, int64_t n1, const uint8_t* s2,
                    int64_t n2, uint8_t* out16) {
  uint64_t h[8];
  for (int i = 0; i < 8; ++i) h[i] = kB2bIV[i];
  h[0] ^= 0x01010000ull ^ 16ull;  // digest_length=16, key=0, fanout=depth=1
  uint8_t block[128];
  const int64_t total = n1 + n2;
  if (total == 0) {
    std::memset(block, 0, 128);
    b2b_compress(h, block, 0, true);
  } else {
    int64_t off = 0;
    uint64_t t = 0;
    while (off < total) {
      const int64_t take = (total - off < 128) ? (total - off) : 128;
      int64_t filled = 0;
      while (filled < take) {
        const int64_t pos = off + filled;
        if (pos < n1) {
          const int64_t c =
              (n1 - pos < take - filled) ? (n1 - pos) : (take - filled);
          std::memcpy(block + filled, s1 + pos, static_cast<size_t>(c));
          filled += c;
        } else {
          const int64_t c = take - filled;
          std::memcpy(block + filled, s2 + (pos - n1),
                      static_cast<size_t>(c));
          filled += c;
        }
      }
      if (take < 128) {
        std::memset(block + take, 0, static_cast<size_t>(128 - take));
      }
      off += take;
      t += static_cast<uint64_t>(take);
      b2b_compress(h, block, t, off >= total);
    }
  }
  std::memcpy(out16, h, 16);  // little-endian h[0..1] = the first 16 bytes
}

// The batch assembler's parts (assemble_batch below): how one part of one
// input is read.
enum : uint8_t { kRaw32 = 0, kFold64 = 1, kBf16F32 = 2, kRaw16 = 3, kRaw8 = 4 };

// `count` values of one part, from its element `first`, widened to words.
inline void load_values(const void* src, uint8_t kind, int64_t first,
                        int64_t count, int64_t vocab, uint32_t* dst) {
  switch (kind) {
    case kRaw32:
      std::memcpy(dst, static_cast<const uint32_t*>(src) + first,
                  static_cast<size_t>(count) * 4);
      break;
    case kFold64: {
      const int64_t* s = static_cast<const int64_t*>(src) + first;
      const int64_t mask = vocab - 1;
      const bool pow2 = (vocab & mask) == 0;
      for (int64_t i = 0; i < count; ++i) {
        dst[i] = static_cast<uint32_t>(fold1(s[i], vocab, pow2, mask));
      }
      break;
    }
    case kBf16F32: {
      const uint32_t* s = static_cast<const uint32_t*>(src) + first;
      for (int64_t i = 0; i < count; ++i) dst[i] = bf16_bits(s[i]);
      break;
    }
    case kRaw16: {
      const uint16_t* s = static_cast<const uint16_t*>(src) + first;
      for (int64_t i = 0; i < count; ++i) dst[i] = s[i];
      break;
    }
    default: {
      const uint8_t* s = static_cast<const uint8_t*>(src) + first;
      for (int64_t i = 0; i < count; ++i) dst[i] = s[i];
    }
  }
}

// Values a plane converts at a time: four planes' worth stay in L1.
constexpr int64_t kChunk = 1024;

// One sub-word input's segment; returns the segment's end.
template <int BITS>
uint32_t* assemble_planes(const void* const* ptrs, const uint8_t* kinds,
                          const int64_t* ns, int64_t num_parts, int64_t inner,
                          int64_t bucket, int64_t vocab, uint32_t* seg) {
  constexpr int P = planes_of(BITS);
  const int64_t stride = (bucket + P - 1) / P * inner;
  // The part, and the element inside it, a plane reads next; part ==
  // num_parts is the padding.
  struct Cursor { int64_t part, off; } cur[P];
  for (int p = 0; p < P; ++p) {
    cur[p] = {0, p * stride};
    while (cur[p].part < num_parts && cur[p].off >= ns[cur[p].part] * inner) {
      cur[p].off -= ns[cur[p].part++] * inner;
    }
  }
  uint32_t scratch[P * kChunk];
  for (int64_t i = 0; i < stride;) {
    if (cur[0].part >= num_parts) {  // and so is every later plane
      for (int g = 0; g < P * BITS / 32; ++g) {
        std::memset(seg + g * stride + i, 0,
                    static_cast<size_t>(stride - i) * 4);
      }
      break;
    }
    int64_t run = stride - i < kChunk ? stride - i : kChunk;
    for (int p = 0; p < P; ++p) {
      if (cur[p].part < num_parts) {
        const int64_t left = ns[cur[p].part] * inner - cur[p].off;
        if (left < run) run = left;
      }
    }
    for (int p = 0; p < P; ++p) {
      Cursor& c = cur[p];
      if (c.part >= num_parts) {
        std::memset(scratch + p * kChunk, 0, static_cast<size_t>(run) * 4);
        continue;
      }
      load_values(ptrs[c.part], kinds[c.part], c.off, run, vocab,
                  scratch + p * kChunk);
      c.off += run;
      while (c.part < num_parts && c.off >= ns[c.part] * inner) {
        c.off = 0;  // a run never crosses a part, so it ended exactly here
        ++c.part;
      }
    }
    pack_words<BITS>(
        [&](int p, int64_t k) { return scratch[p * kChunk + k]; }, run,
        seg + i, stride);
    i += run;
  }
  return seg + (P * BITS / 32) * stride;
}

}  // namespace

extern "C" {

// 128-bit content digest (two independently-keyed 64-bit lanes, 32 bytes
// per iteration) for the batcher's device-input cache. Non-cryptographic
// but well-mixed: at the cache's scale (<=1e6 distinct batches) the
// 128-bit collision probability is ~1e-27. ~5x faster than blake2b, and
// ctypes releases the GIL for the call, so hashing a ~2 MB batch never
// stalls the request handlers.
void hash128(const uint8_t* p, int64_t n, uint64_t* out) {
  const uint64_t K0 = 0x9E3779B185EBCA87ull, K1 = 0xC2B2AE3D27D4EB4Full,
                 K2 = 0x165667B19E3779F9ull, K3 = 0x27D4EB2F165667C5ull;
  uint64_t h0 = K0 ^ static_cast<uint64_t>(n);
  uint64_t h1 = K1 + static_cast<uint64_t>(n);
  int64_t i = 0;
  for (; i + 32 <= n; i += 32) {
    uint64_t a, b, c, d;
    std::memcpy(&a, p + i, 8);
    std::memcpy(&b, p + i + 8, 8);
    std::memcpy(&c, p + i + 16, 8);
    std::memcpy(&d, p + i + 24, 8);
    h0 = mix64(a ^ h0, K2 ^ b);
    h1 = mix64(c ^ h1, K3 ^ d);
  }
  if (i < n) {
    uint8_t tail[32] = {0};
    std::memcpy(tail, p + i, static_cast<size_t>(n - i));
    uint64_t a, b, c, d;
    std::memcpy(&a, tail, 8);
    std::memcpy(&b, tail + 8, 8);
    std::memcpy(&c, tail + 16, 8);
    std::memcpy(&d, tail + 24, 8);
    h0 = mix64(a ^ h0, K2 ^ b);
    h1 = mix64(c ^ h1, K3 ^ d);
  }
  out[0] = mix64(h0 ^ K1, h1 ^ K0);  // cross-mix: each output depends on
  out[1] = mix64(h1 ^ K3, h0 ^ K2);  // both lanes
}

// Batched per-row blake2b-128 (ISSUE 15 satellite): N rows of a
// contiguous [n_rows, row_bytes] uint8 matrix -> N 16-byte digests, each
// blake2b(header || row, digest_size=16) — byte-identical to the
// hashlib.blake2b python fallback in cache/row_cache.py digest_rows and
// cache/digest.py row_label_keys (header empty there). ONE ctypes call
// releases the GIL for the whole batch, replacing the per-row python
// hash loop the row-cache plane otherwise pays on every armed batch.
void hash128_rows(const uint8_t* header, int64_t header_len,
                  const uint8_t* rows, int64_t n_rows, int64_t row_bytes,
                  uint8_t* out) {
  for (int64_t r = 0; r < n_rows; ++r) {
    blake2b16_2seg(header, header_len, rows + r * row_bytes, row_bytes,
                   out + r * 16);
  }
}

// ids[i] -> int32(ids[i] mod vocab) — the uncompressed fold. Power-of-two
// vocabs (the common config) take the mask path: two's-complement AND equals
// the mathematical mod, and skips the 64-bit division.
void fold_i32(const int64_t* ids, int64_t n, int64_t vocab, int32_t* out) {
  const bool pow2 = (vocab & (vocab - 1)) == 0;
  const int64_t mask = vocab - 1;
  for (int64_t i = 0; i < n; ++i) {
    out[i] = static_cast<int32_t>(fold1(ids[i], vocab, pow2, mask));
  }
}

// Already-folded int32 ids -> 3 little-endian bytes each (the u24 packing
// of ops/transfer.py's PER-KEY path, pack_host: one pass, no intermediate
// view/copy; the combined upload takes pack_planes_u24_i32 below).
// Requires 0 <= ids[i] < 2^24.
void pack_u24_i32(const int32_t* ids, int64_t n, uint8_t* out) {
  for (int64_t i = 0; i < n; ++i) {
    uint32_t v = static_cast<uint32_t>(ids[i]);
    out[3 * i + 0] = static_cast<uint8_t>(v);
    out[3 * i + 1] = static_cast<uint8_t>(v >> 8);
    out[3 * i + 2] = static_cast<uint8_t>(v >> 16);
  }
}

// f32 -> bf16 with round-to-nearest-even (numpy/ml_dtypes-compatible,
// including NaN quieting).
void f32_to_bf16(const float* in, int64_t n, uint16_t* out) {
  const uint32_t* bits = reinterpret_cast<const uint32_t*>(in);
  for (int64_t i = 0; i < n; ++i) {
    out[i] = bf16_bits(bits[i]);
  }
}

// The plane forms of the two packs above, and of 1- and 2-byte values as
// they are, for ops/transfer.py's combined upload (pack_host_combined):
// one padded [n_rows, inner] array in, its whole-word segment out, each
// read and written once.
void pack_planes_u24_i32(const int32_t* ids, int64_t n_rows, int64_t inner,
                         uint32_t* out) {
  pack_planes<24>([=](int64_t k) { return static_cast<uint32_t>(ids[k]); },
                  n_rows, inner, out);
}

void pack_planes_bf16_f32(const float* in, int64_t n_rows, int64_t inner,
                          uint32_t* out) {
  const uint32_t* bits = reinterpret_cast<const uint32_t*>(in);
  pack_planes<16>(
      [=](int64_t k) { return static_cast<uint32_t>(bf16_bits(bits[k])); },
      n_rows, inner, out);
}

void pack_planes_raw16(const uint16_t* in, int64_t n_rows, int64_t inner,
                       uint32_t* out) {
  pack_planes<16>([=](int64_t k) { return static_cast<uint32_t>(in[k]); },
                  n_rows, inner, out);
}

void pack_planes_raw8(const uint8_t* in, int64_t n_rows, int64_t inner,
                      uint32_t* out) {
  pack_planes<8>([=](int64_t k) { return static_cast<uint32_t>(in[k]); },
                 n_rows, inner, out);
}

// Batch assembly for ANY combined layout (ops/transfer.py combined_layout):
// reads each request's arrays ONCE and writes the final padded upload buffer
// directly, in the word format above, replacing the python path's pad copy +
// fold pass + pack pass (3 full passes and 2 temporaries a batch,
// serving/batcher.py _dispatch + ops/transfer.py). The buffer is, bit for
// bit, pack_host_combined over the padded, folded batch. Input k (the
// layout's entries, key-sorted) has bits[k] in {32, 24, 16, 8}, inner[k]
// values a row and, where its int64 parts are ids to fold, vocab[k] > 0; its
// segment follows input k-1's. Part p of input k is ptrs[k * num_parts + p],
// ns[p] rows of inner[k] values, read as kinds[k * num_parts + p] says:
//   kRaw32   4-byte values as they are (32 bits: any dtype; 24: int32 ids,
//            pre-folded by a compact-wire client, low 3 bytes taken)
//   kFold64  int64 ids, folded mod vocab[k] here (32 or 24 bits)
//   kBf16F32 float32, cast to bf16 here, RNE (16 bits)
//   kRaw16 / kRaw8  2- and 1-byte values as they are
// (for OUT-of-contract int32 ids in a group MIXED with int64 ones the python
// path widens to int64 and folds while this path truncates: an intentional,
// documented divergence reachable only by direct submit() callers violating
// the compact contract). A word of a sub-word segment takes one value of
// every plane, and those lie in different requests, so each plane walks the
// parts with a cursor of its own; the padded batch is flat here (rows are
// the caller's notion): plane p is elements [p * stride, (p + 1) * stride),
// zero past the last part. Thread-safe; ctypes releases the GIL for the call.
// Returns the nanoseconds the pass took, first line to last, on a clock that
// asks nothing of the interpreter: what the caller's own clock reads around
// the call beyond this is ctypes and the wait to take the GIL back.
int64_t assemble_batch(int64_t num_inputs, const int32_t* bits,
                       const int64_t* inner, const int64_t* vocab,
                       const void* const* ptrs, const uint8_t* kinds,
                       const int64_t* ns, int64_t num_parts, int64_t bucket,
                       uint32_t* out) {
  const auto started = std::chrono::steady_clock::now();
  for (int64_t k = 0; k < num_inputs; ++k) {
    const void* const* kp = ptrs + k * num_parts;
    const uint8_t* kk = kinds + k * num_parts;
    if (bits[k] == 32) {  // one plane: the padded array itself
      int64_t off = 0;
      for (int64_t p = 0; p < num_parts; ++p) {
        load_values(kp[p], kk[p], 0, ns[p] * inner[k], vocab[k], out + off);
        off += ns[p] * inner[k];
      }
      std::memset(out + off, 0,
                  static_cast<size_t>(bucket * inner[k] - off) * 4);
      out += bucket * inner[k];
    } else if (bits[k] == 24) {
      out = assemble_planes<24>(kp, kk, ns, num_parts, inner[k], bucket,
                                vocab[k], out);
    } else if (bits[k] == 16) {
      out = assemble_planes<16>(kp, kk, ns, num_parts, inner[k], bucket,
                                vocab[k], out);
    } else {
      out = assemble_planes<8>(kp, kk, ns, num_parts, inner[k], bucket,
                               vocab[k], out);
    }
  }
  return std::chrono::duration_cast<std::chrono::nanoseconds>(
             std::chrono::steady_clock::now() - started)
      .count();
}

}  // extern "C"
