// grandine-tpu native runtime kernels: SHA-256 merkleization hot loop.
//
// Equivalent of the reference's `hashing` crate (hashing/src/lib.rs:10-60 —
// sha2 crate with SIMD asm + ZERO_HASHES table) re-implemented for this
// framework: the per-node hash loop of SSZ hash-tree-root lives here so the
// Python/JAX host layer never pays per-hash interpreter overhead.
//
// Two SHA-256 compression backends, selected once at init by CPUID:
//   * x86 SHA-NI intrinsics (one 64-byte block ≈ tens of cycles)
//   * portable C++ fallback
//
// Exported C ABI (consumed via ctypes from grandine_tpu.native):
//   gt_init()                      -> 1 if SHA-NI active, 0 if portable
//   gt_sha256(data, len, out32)
//   gt_hash_pairs(in, n, out)      -- n 64-byte concatenated pairs -> n roots
//   gt_merkleize(chunks, n, depth, out32)
//   gt_merkleize_many(chunks, n_items, cpi, depth, out)
//   gt_zero_hash(level, out32)
//   gt_mix_in_length(root32, value, out32)
//   gt_crc32c(data, len)
//   gt_g2_decompress_batch(in, n, out, status)  -- n 96-byte compressed
//                                  G2 points -> n affine (x.c0 x.c1 y.c0 y.c1)

#include <cstdint>
#include <cstring>
#include <cstdlib>

#if defined(__x86_64__)
#include <immintrin.h>
#include <cpuid.h>
#define GT_X86 1
#endif

namespace {

// ---------------------------------------------------------------- portable
const uint32_t K[64] = {
    0x428a2f98, 0x71374491, 0xb5c0fbcf, 0xe9b5dba5, 0x3956c25b, 0x59f111f1,
    0x923f82a4, 0xab1c5ed5, 0xd807aa98, 0x12835b01, 0x243185be, 0x550c7dc3,
    0x72be5d74, 0x80deb1fe, 0x9bdc06a7, 0xc19bf174, 0xe49b69c1, 0xefbe4786,
    0x0fc19dc6, 0x240ca1cc, 0x2de92c6f, 0x4a7484aa, 0x5cb0a9dc, 0x76f988da,
    0x983e5152, 0xa831c66d, 0xb00327c8, 0xbf597fc7, 0xc6e00bf3, 0xd5a79147,
    0x06ca6351, 0x14292967, 0x27b70a85, 0x2e1b2138, 0x4d2c6dfc, 0x53380d13,
    0x650a7354, 0x766a0abb, 0x81c2c92e, 0x92722c85, 0xa2bfe8a1, 0xa81a664b,
    0xc24b8b70, 0xc76c51a3, 0xd192e819, 0xd6990624, 0xf40e3585, 0x106aa070,
    0x19a4c116, 0x1e376c08, 0x2748774c, 0x34b0bcb5, 0x391c0cb3, 0x4ed8aa4a,
    0x5b9cca4f, 0x682e6ff3, 0x748f82ee, 0x78a5636f, 0x84c87814, 0x8cc70208,
    0x90befffa, 0xa4506ceb, 0xbef9a3f7, 0xc67178f2};

inline uint32_t rotr(uint32_t x, int n) { return (x >> n) | (x << (32 - n)); }
inline uint32_t rd32(const uint8_t* p) {
  return (uint32_t(p[0]) << 24) | (uint32_t(p[1]) << 16) |
         (uint32_t(p[2]) << 8) | uint32_t(p[3]);
}
inline void wr32(uint8_t* p, uint32_t v) {
  p[0] = uint8_t(v >> 24);
  p[1] = uint8_t(v >> 16);
  p[2] = uint8_t(v >> 8);
  p[3] = uint8_t(v);
}

void compress_portable(uint32_t st[8], const uint8_t* block) {
  uint32_t w[64];
  for (int i = 0; i < 16; i++) w[i] = rd32(block + 4 * i);
  for (int i = 16; i < 64; i++) {
    uint32_t s0 = rotr(w[i - 15], 7) ^ rotr(w[i - 15], 18) ^ (w[i - 15] >> 3);
    uint32_t s1 = rotr(w[i - 2], 17) ^ rotr(w[i - 2], 19) ^ (w[i - 2] >> 10);
    w[i] = w[i - 16] + s0 + w[i - 7] + s1;
  }
  uint32_t a = st[0], b = st[1], c = st[2], d = st[3];
  uint32_t e = st[4], f = st[5], g = st[6], h = st[7];
  for (int i = 0; i < 64; i++) {
    uint32_t S1 = rotr(e, 6) ^ rotr(e, 11) ^ rotr(e, 25);
    uint32_t ch = (e & f) ^ (~e & g);
    uint32_t t1 = h + S1 + ch + K[i] + w[i];
    uint32_t S0 = rotr(a, 2) ^ rotr(a, 13) ^ rotr(a, 22);
    uint32_t mj = (a & b) ^ (a & c) ^ (b & c);
    uint32_t t2 = S0 + mj;
    h = g; g = f; f = e; e = d + t1;
    d = c; c = b; b = a; a = t1 + t2;
  }
  st[0] += a; st[1] += b; st[2] += c; st[3] += d;
  st[4] += e; st[5] += f; st[6] += g; st[7] += h;
}

// ---------------------------------------------------------------- SHA-NI
#ifdef GT_X86
__attribute__((target("sha,sse4.1")))
void compress_shani(uint32_t st[8], const uint8_t* block) {
  const __m128i MASK =
      _mm_set_epi64x(0x0c0d0e0f08090a0bULL, 0x0405060700010203ULL);
  __m128i tmp = _mm_loadu_si128((const __m128i*)&st[0]);
  __m128i s1 = _mm_loadu_si128((const __m128i*)&st[4]);
  tmp = _mm_shuffle_epi32(tmp, 0xB1);
  s1 = _mm_shuffle_epi32(s1, 0x1B);
  __m128i s0 = _mm_alignr_epi8(tmp, s1, 8);
  s1 = _mm_blend_epi16(s1, tmp, 0xF0);
  const __m128i abef_save = s0, cdgh_save = s1;

  __m128i msg, msg0, msg1, msg2, msg3;

  msg0 = _mm_shuffle_epi8(_mm_loadu_si128((const __m128i*)(block + 0)), MASK);
  msg = _mm_add_epi32(msg0, _mm_set_epi64x(0xE9B5DBA5B5C0FBCFULL, 0x71374491428A2F98ULL));
  s1 = _mm_sha256rnds2_epu32(s1, s0, msg);
  msg = _mm_shuffle_epi32(msg, 0x0E);
  s0 = _mm_sha256rnds2_epu32(s0, s1, msg);

  msg1 = _mm_shuffle_epi8(_mm_loadu_si128((const __m128i*)(block + 16)), MASK);
  msg = _mm_add_epi32(msg1, _mm_set_epi64x(0xAB1C5ED5923F82A4ULL, 0x59F111F13956C25BULL));
  s1 = _mm_sha256rnds2_epu32(s1, s0, msg);
  msg = _mm_shuffle_epi32(msg, 0x0E);
  s0 = _mm_sha256rnds2_epu32(s0, s1, msg);
  msg0 = _mm_sha256msg1_epu32(msg0, msg1);

  msg2 = _mm_shuffle_epi8(_mm_loadu_si128((const __m128i*)(block + 32)), MASK);
  msg = _mm_add_epi32(msg2, _mm_set_epi64x(0x550C7DC3243185BEULL, 0x12835B01D807AA98ULL));
  s1 = _mm_sha256rnds2_epu32(s1, s0, msg);
  msg = _mm_shuffle_epi32(msg, 0x0E);
  s0 = _mm_sha256rnds2_epu32(s0, s1, msg);
  msg1 = _mm_sha256msg1_epu32(msg1, msg2);

  msg3 = _mm_shuffle_epi8(_mm_loadu_si128((const __m128i*)(block + 48)), MASK);
  msg = _mm_add_epi32(msg3, _mm_set_epi64x(0xC19BF1749BDC06A7ULL, 0x80DEB1FE72BE5D74ULL));
  s1 = _mm_sha256rnds2_epu32(s1, s0, msg);
  tmp = _mm_alignr_epi8(msg3, msg2, 4);
  msg0 = _mm_add_epi32(msg0, tmp);
  msg0 = _mm_sha256msg2_epu32(msg0, msg3);
  msg = _mm_shuffle_epi32(msg, 0x0E);
  s0 = _mm_sha256rnds2_epu32(s0, s1, msg);
  msg2 = _mm_sha256msg1_epu32(msg2, msg3);

  const uint64_t k2[12][2] = {
      {0xEFBE4786E49B69C1ULL, 0x240CA1CC0FC19DC6ULL},
      {0x4A7484AA2DE92C6FULL, 0x76F988DA5CB0A9DCULL},
      {0xA831C66D983E5152ULL, 0xBF597FC7B00327C8ULL},
      {0xD5A79147C6E00BF3ULL, 0x1429296706CA6351ULL},
      {0x2E1B213827B70A85ULL, 0x53380D134D2C6DFCULL},
      {0x766A0ABB650A7354ULL, 0x92722C8581C2C92EULL},
      {0xA81A664BA2BFE8A1ULL, 0xC76C51A3C24B8B70ULL},
      {0xD6990624D192E819ULL, 0x106AA070F40E3585ULL},
      {0x1E376C0819A4C116ULL, 0x34B0BCB52748774CULL},
      {0x4ED8AA4A391C0CB3ULL, 0x682E6FF35B9CCA4FULL},
      {0x78A5636F748F82EEULL, 0x8CC7020884C87814ULL},
      {0xA4506CEB90BEFFFAULL, 0xC67178F2BEF9A3F7ULL}};
  // rounds 16..63, 4 at a time, msg registers rotating
  __m128i* m[4] = {&msg0, &msg1, &msg2, &msg3};
  for (int r = 0; r < 12; r++) {
    __m128i& cur = *m[r & 3];
    __m128i& nxt = *m[(r + 1) & 3];
    __m128i& prv = *m[(r + 3) & 3];
    msg = _mm_add_epi32(cur, _mm_set_epi64x((long long)k2[r][1], (long long)k2[r][0]));
    s1 = _mm_sha256rnds2_epu32(s1, s0, msg);
    tmp = _mm_alignr_epi8(cur, prv, 4);
    nxt = _mm_add_epi32(nxt, tmp);
    nxt = _mm_sha256msg2_epu32(nxt, cur);
    msg = _mm_shuffle_epi32(msg, 0x0E);
    s0 = _mm_sha256rnds2_epu32(s0, s1, msg);
    if (r < 11) prv = _mm_sha256msg1_epu32(prv, cur);
  }

  s0 = _mm_add_epi32(s0, abef_save);
  s1 = _mm_add_epi32(s1, cdgh_save);
  tmp = _mm_shuffle_epi32(s0, 0x1B);
  s1 = _mm_shuffle_epi32(s1, 0xB1);
  s0 = _mm_blend_epi16(tmp, s1, 0xF0);
  s1 = _mm_alignr_epi8(s1, tmp, 8);
  _mm_storeu_si128((__m128i*)&st[0], s0);
  _mm_storeu_si128((__m128i*)&st[4], s1);
}
#endif  // GT_X86

typedef void (*compress_fn)(uint32_t[8], const uint8_t*);
compress_fn g_compress = compress_portable;

const uint32_t IV[8] = {0x6a09e667, 0xbb67ae85, 0x3c6ef372, 0xa54ff53a,
                        0x510e527f, 0x9b05688c, 0x1f83d9ab, 0x5be0cd19};

// Constant second block for a 64-byte message: 0x80, zeros, bit length 512.
uint8_t PAD64[64];

// hash of a 64-byte input (the merkle node op): 2 compressions.
inline void hash64(const uint8_t* in, uint8_t* out) {
  uint32_t st[8];
  std::memcpy(st, IV, sizeof(IV));
  g_compress(st, in);
  g_compress(st, PAD64);
  for (int i = 0; i < 8; i++) wr32(out + 4 * i, st[i]);
}

const int MAX_DEPTH = 64;
uint8_t ZERO_HASH[MAX_DEPTH + 1][32];
bool g_inited = false;

}  // namespace

extern "C" {

int gt_init(void) {
  if (g_inited) {
#ifdef GT_X86
    return g_compress == compress_shani ? 1 : 0;
#else
    return 0;
#endif
  }
  std::memset(PAD64, 0, sizeof(PAD64));
  PAD64[0] = 0x80;
  PAD64[62] = 0x02;  // 512 bits big-endian = 0x0200
#ifdef GT_X86
  unsigned a, b, c, d;
  if (__get_cpuid_count(7, 0, &a, &b, &c, &d) && (b & (1u << 29))) {
    g_compress = compress_shani;
  }
#endif
  std::memset(ZERO_HASH[0], 0, 32);
  uint8_t buf[64];
  for (int i = 1; i <= MAX_DEPTH; i++) {
    std::memcpy(buf, ZERO_HASH[i - 1], 32);
    std::memcpy(buf + 32, ZERO_HASH[i - 1], 32);
    hash64(buf, ZERO_HASH[i]);
  }
  g_inited = true;
#ifdef GT_X86
  return g_compress == compress_shani ? 1 : 0;
#else
  return 0;
#endif
}

void gt_zero_hash(int level, uint8_t* out32) {
  std::memcpy(out32, ZERO_HASH[level <= MAX_DEPTH ? level : MAX_DEPTH], 32);
}

void gt_sha256(const uint8_t* data, uint64_t len, uint8_t* out32) {
  uint32_t st[8];
  std::memcpy(st, IV, sizeof(IV));
  uint64_t full = len / 64;
  for (uint64_t i = 0; i < full; i++) g_compress(st, data + 64 * i);
  uint8_t tail[128];
  uint64_t rem = len - 64 * full;
  std::memcpy(tail, data + 64 * full, rem);
  tail[rem] = 0x80;
  uint64_t tlen = (rem + 9 <= 64) ? 64 : 128;
  std::memset(tail + rem + 1, 0, tlen - rem - 1 - 8);
  uint64_t bits = len * 8;
  for (int i = 0; i < 8; i++) tail[tlen - 1 - i] = uint8_t(bits >> (8 * i));
  g_compress(st, tail);
  if (tlen == 128) g_compress(st, tail + 64);
  for (int i = 0; i < 8; i++) wr32(out32 + 4 * i, st[i]);
}

// n concatenated 64-byte pairs -> n 32-byte parent nodes. in != out allowed
// to alias only if out <= in (in-place tree reduction writes forward).
void gt_hash_pairs(const uint8_t* in, uint64_t n, uint8_t* out) {
  for (uint64_t i = 0; i < n; i++) hash64(in + 64 * i, out + 32 * i);
}

// Merkleize `n_chunks` 32-byte chunks into a subtree of height `depth`
// (2^depth leaf slots, zero-padded virtually). Scratch is O(n).
static void merkleize_into(const uint8_t* chunks, uint64_t n_chunks, int depth,
                           uint8_t* out32, uint8_t* scratch) {
  if (n_chunks == 0) {
    std::memcpy(out32, ZERO_HASH[depth], 32);
    return;
  }
  if (depth == 0) {
    std::memcpy(out32, chunks, 32);
    return;
  }
  // copy level 0 into scratch
  uint64_t n = n_chunks;
  std::memcpy(scratch, chunks, n * 32);
  uint8_t buf[64];
  for (int level = 0; level < depth; level++) {
    uint64_t pairs = n / 2;
    for (uint64_t i = 0; i < pairs; i++)
      hash64(scratch + 64 * i, scratch + 32 * i);
    if (n & 1) {
      std::memcpy(buf, scratch + 32 * (n - 1), 32);
      std::memcpy(buf + 32, ZERO_HASH[level], 32);
      hash64(buf, scratch + 32 * pairs);
      n = pairs + 1;
    } else {
      n = pairs;
    }
    if (n == 1 && level + 1 < depth) {
      // remaining right siblings are all zero subtrees
      for (int l = level + 1; l < depth; l++) {
        std::memcpy(buf, scratch, 32);
        std::memcpy(buf + 32, ZERO_HASH[l], 32);
        hash64(buf, scratch);
      }
      break;
    }
  }
  std::memcpy(out32, scratch, 32);
}

// Returns 1 on success, 0 on allocation failure (caller falls back to the
// hashlib path).
int gt_merkleize(const uint8_t* chunks, uint64_t n_chunks, int depth,
                 uint8_t* out32) {
  uint8_t* scratch =
      (uint8_t*)std::malloc((n_chunks ? n_chunks : 1) * 32 + 32);
  if (!scratch) return 0;
  merkleize_into(chunks, n_chunks, depth, out32, scratch);
  std::free(scratch);
  return 1;
}

// Batch: n_items independent subtrees, each `cpi` chunks wide, each
// merkleized to height `depth`. The 50k-validator registry path: one call
// hashes every validator's 8-field subtree. Returns 1 on success, 0 on
// allocation failure.
int gt_merkleize_many(const uint8_t* chunks, uint64_t n_items, uint64_t cpi,
                      int depth, uint8_t* out) {
  uint8_t* scratch = (uint8_t*)std::malloc((cpi ? cpi : 1) * 32 + 32);
  if (!scratch) return 0;
  for (uint64_t i = 0; i < n_items; i++)
    merkleize_into(chunks + i * cpi * 32, cpi, depth, out + 32 * i, scratch);
  std::free(scratch);
  return 1;
}

// mix_in_length / mix_in_selector: hash(root ++ le64(value) ++ zeros24)
void gt_mix_in_length(const uint8_t* root, uint64_t value, uint8_t* out32) {
  uint8_t buf[64];
  std::memcpy(buf, root, 32);
  std::memset(buf + 32, 0, 32);
  for (int i = 0; i < 8; i++) buf[32 + i] = uint8_t(value >> (8 * i));
  hash64(buf, out32);
}

// ------------------------------------------------------------------ crc32c
// CRC-32C (Castagnoli) for the snappy framing layer: every database put
// checksums its value, so the byte-at-a-time Python loop was a systemic
// tax on storage. SSE4.2 has the polynomial in hardware (crc32 instr);
// the portable path is a table-driven fallback built at init.

namespace {
uint32_t CRC_TABLE[256];
bool g_crc_table_built = false;

void build_crc_table() {
  const uint32_t poly = 0x82F63B78u;
  for (uint32_t i = 0; i < 256; i++) {
    uint32_t crc = i;
    for (int j = 0; j < 8; j++)
      crc = (crc & 1) ? (crc >> 1) ^ poly : crc >> 1;
    CRC_TABLE[i] = crc;
  }
  g_crc_table_built = true;
}

uint32_t crc32c_portable(uint32_t crc, const uint8_t* p, uint64_t len) {
  for (uint64_t i = 0; i < len; i++)
    crc = CRC_TABLE[(crc ^ p[i]) & 0xFF] ^ (crc >> 8);
  return crc;
}

#ifdef GT_X86
__attribute__((target("sse4.2")))
uint32_t crc32c_hw(uint32_t crc, const uint8_t* p, uint64_t len) {
  uint64_t c = crc;
  while (len >= 8) {
    uint64_t v;
    std::memcpy(&v, p, 8);
    c = _mm_crc32_u64(c, v);
    p += 8;
    len -= 8;
  }
  uint32_t c32 = (uint32_t)c;
  while (len--) c32 = _mm_crc32_u8(c32, *p++);
  return c32;
}

bool have_sse42() {
  unsigned a, b, c, d;
  return __get_cpuid(1, &a, &b, &c, &d) && (c & (1u << 20));
}
#endif
}  // namespace

uint32_t gt_crc32c(const uint8_t* data, uint64_t len) {
  uint32_t crc = 0xFFFFFFFFu;
#ifdef GT_X86
  static const bool hw = have_sse42();
  if (hw) return crc32c_hw(crc, data, len) ^ 0xFFFFFFFFu;
#endif
  if (!g_crc_table_built) build_crc_table();
  return crc32c_portable(crc, data, len) ^ 0xFFFFFFFFu;
}

}  // extern "C"

// -------------------------------------------------------- G2 decompression
// The firehose's `host_prep op=g2_decompress` stage: one call decodes a
// whole batch of compressed signatures (the reference does this in blst).
// Item for item it is crypto/bls.py `g2_from_bytes(data,
// subgroup_check=False)` — same flag rules, same range checks, the same
// y of the two roots — and that function stays the differential reference
// (tests/test_native_g2_decompress.py). Called through ctypes it holds no
// GIL, which a Python `pow(n, e, P)` does for its whole length. The data
// is public: nothing here is constant time.
//
// Fp is 6 x 64-bit limbs, little-endian, in Montgomery form (R = 2^384);
// Fp2 = Fp[u] / (u^2 + 1). Every constant but P itself is derived from P
// once, so there is no second table to keep in step with it.

namespace {

typedef unsigned __int128 u128;

struct Fp {
  uint64_t l[6];
};
struct Fp2 {
  Fp c0, c1;
};

const Fp FP_P = {{0xb9feffffffffaaabULL, 0x1eabfffeb153ffffULL,
                  0x6730d2a0f6b0f624ULL, 0x64774b84f38512bfULL,
                  0x4b1ba7b6434bacd7ULL, 0x1a0111ea397fe69aULL}};

inline bool fp_is_zero(const Fp& a) {
  return (a.l[0] | a.l[1] | a.l[2] | a.l[3] | a.l[4] | a.l[5]) == 0;
}

inline bool fp_eq(const Fp& a, const Fp& b) {
  uint64_t d = 0;
  for (int i = 0; i < 6; i++) d |= a.l[i] ^ b.l[i];
  return d == 0;
}

// a > b, a == b or a < b as plain 384-bit integers: 1, 0, -1
inline int fp_cmp(const Fp& a, const Fp& b) {
  for (int i = 5; i >= 0; i--) {
    if (a.l[i] != b.l[i]) return a.l[i] > b.l[i] ? 1 : -1;
  }
  return 0;
}

// r = a - b as integers; returns the borrow
inline uint64_t raw_sub(Fp& r, const Fp& a, const Fp& b) {
  uint64_t borrow = 0;
  for (int i = 0; i < 6; i++) {
    u128 d = (u128)a.l[i] - b.l[i] - borrow;
    r.l[i] = (uint64_t)d;
    borrow = (uint64_t)(d >> 64) & 1;
  }
  return borrow;
}

// r = a + b as integers (operands below 2^383: no carry out)
inline void raw_add(Fp& r, const Fp& a, const Fp& b) {
  uint64_t carry = 0;
  for (int i = 0; i < 6; i++) {
    u128 s = (u128)a.l[i] + b.l[i] + carry;
    r.l[i] = (uint64_t)s;
    carry = (uint64_t)(s >> 64);
  }
}

inline Fp fp_add(const Fp& a, const Fp& b) {
  Fp r;
  raw_add(r, a, b);
  if (fp_cmp(r, FP_P) >= 0) raw_sub(r, r, FP_P);
  return r;
}

inline Fp fp_sub(const Fp& a, const Fp& b) {
  Fp r;
  if (raw_sub(r, a, b)) raw_add(r, r, FP_P);  // wraps back into [0, P)
  return r;
}

inline Fp fp_neg(const Fp& a) {
  Fp r = a;
  if (!fp_is_zero(a)) raw_sub(r, FP_P, a);
  return r;
}

uint64_t g_fp_n0;  // -P^-1 mod 2^64

// Montgomery product a * b / R mod P (CIOS); operands and result in [0, P)
Fp fp_mul(const Fp& a, const Fp& b) {
  uint64_t t[8] = {0, 0, 0, 0, 0, 0, 0, 0};
#pragma GCC unroll 6
  for (int i = 0; i < 6; i++) {
    uint64_t carry = 0;
#pragma GCC unroll 6
    for (int j = 0; j < 6; j++) {
      u128 x = (u128)a.l[j] * b.l[i] + t[j] + carry;
      t[j] = (uint64_t)x;
      carry = (uint64_t)(x >> 64);
    }
    u128 x = (u128)t[6] + carry;
    t[6] = (uint64_t)x;
    t[7] = (uint64_t)(x >> 64);
    uint64_t m = t[0] * g_fp_n0;
    x = (u128)m * FP_P.l[0] + t[0];
    carry = (uint64_t)(x >> 64);
#pragma GCC unroll 6
    for (int j = 1; j < 6; j++) {
      x = (u128)m * FP_P.l[j] + t[j] + carry;
      t[j - 1] = (uint64_t)x;
      carry = (uint64_t)(x >> 64);
    }
    x = (u128)t[6] + carry;
    t[5] = (uint64_t)x;
    t[6] = t[7] + (uint64_t)(x >> 64);
  }
  Fp r;  // below 2P < 2^382: t[6] is 0
  for (int i = 0; i < 6; i++) r.l[i] = t[i];
  if (fp_cmp(r, FP_P) >= 0) raw_sub(r, r, FP_P);
  return r;
}

inline Fp fp_sqr(const Fp& a) { return fp_mul(a, a); }

// a^e for a 384-bit exponent of plain limbs
Fp fp_pow(const Fp& a, const Fp& e, const Fp& one) {
  Fp r = one;
  for (int i = 383; i >= 0; i--) {
    r = fp_sqr(r);
    if ((e.l[i >> 6] >> (i & 63)) & 1) r = fp_mul(r, a);
  }
  return r;
}

struct FpConsts {
  Fp r2;         // R^2 mod P: into Montgomery form
  Fp one;        // R mod P
  Fp one_plain;  // the integer 1: out of Montgomery form
  Fp half;       // 1/2
  Fp four;       // 4: the curve's b is 4 + 4u
  Fp exp_sqrt;   // (P + 1) / 4: a root of a residue, as P = 3 mod 4
  Fp exp_isqrt;  // (P - 3) / 4: the inverse of that root

  FpConsts() {
    // Newton: each step doubles the correct low bits of P^-1 mod 2^64
    uint64_t inv = 1;
    for (int i = 0; i < 6; i++) inv *= 2 - FP_P.l[0] * inv;
    g_fp_n0 = 0 - inv;
    // 2^k mod P by doubling: k = 384 is R, k = 768 is R^2
    Fp x = {{1, 0, 0, 0, 0, 0}};
    one_plain = x;
    for (int k = 1; k <= 768; k++) {
      x = fp_add(x, x);
      if (k == 384) one = x;
    }
    r2 = x;
    four = fp_add(fp_add(one, one), fp_add(one, one));
    Fp three = {{3, 0, 0, 0, 0, 0}};
    raw_add(exp_sqrt, FP_P, one_plain);
    raw_sub(exp_isqrt, FP_P, three);
    Fp half_plain = exp_sqrt;  // (P + 1) / 2, before the second shift
    shr(half_plain, 1);
    half = fp_mul(half_plain, r2);
    shr(exp_sqrt, 2);
    shr(exp_isqrt, 2);
  }

  static void shr(Fp& a, int bits) {
    for (int i = 0; i < 6; i++) {
      uint64_t hi = i < 5 ? a.l[i + 1] : 0;
      a.l[i] = (a.l[i] >> bits) | (hi << (64 - bits));
    }
  }
};

const FpConsts& fp_consts() {
  static const FpConsts c;  // built once, by whichever thread is first
  return c;
}

inline Fp fp_from_be48(const uint8_t* p) {
  Fp r;
  for (int i = 0; i < 6; i++) {
    uint64_t v = 0;
    for (int j = 0; j < 8; j++) v = (v << 8) | p[(5 - i) * 8 + j];
    r.l[i] = v;
  }
  return r;
}

inline void fp_to_be48(const Fp& a, uint8_t* p) {
  for (int i = 0; i < 6; i++)
    for (int j = 0; j < 8; j++)
      p[(5 - i) * 8 + j] = uint8_t(a.l[i] >> (8 * (7 - j)));
}

inline Fp2 fp2_sqr(const Fp2& a) {
  Fp t = fp_mul(a.c0, a.c1);
  return {fp_mul(fp_add(a.c0, a.c1), fp_sub(a.c0, a.c1)), fp_add(t, t)};
}

inline Fp2 fp2_mul(const Fp2& a, const Fp2& b) {
  Fp v0 = fp_mul(a.c0, b.c0), v1 = fp_mul(a.c1, b.c1);
  Fp cross = fp_mul(fp_add(a.c0, a.c1), fp_add(b.c0, b.c1));
  return {fp_sub(v0, v1), fp_sub(fp_sub(cross, v0), v1)};
}

// A square root of z = a + b*u, or false where z is no square. Two
// exponentiations: s = sqrt(a^2 + b^2), then w = t2^((P-3)/4) for
// t2 = (a + s) / 2 gives t = w * t2 with t^2 = +-t2 and t * w = +-1, so
// the root and the inverse the other coordinate needs come from one
// power: t^2 = t2 -> (t, b*w/2); t^2 = -t2 -> (-b*w/2, t). Which of the
// two roots comes out does not matter: the caller picks by the sign bit.
bool fp2_sqrt(const Fp2& z, Fp2& y, const FpConsts& k) {
  const Fp zero = {{0, 0, 0, 0, 0, 0}};
  if (fp_is_zero(z.c1)) {
    // a or -a is a residue (-1 is none): w^2 = +-a
    Fp w = fp_pow(z.c0, k.exp_sqrt, k.one);
    if (fp_eq(fp_sqr(w), z.c0)) {
      y = {w, zero};
    } else {
      y = {zero, w};
    }
    return true;
  }
  Fp norm = fp_add(fp_sqr(z.c0), fp_sqr(z.c1));
  Fp s = fp_pow(norm, k.exp_sqrt, k.one);
  if (!fp_eq(fp_sqr(s), norm)) return false;
  // t2 = 0 would need a = -s, that is b = 0: handled above
  Fp t2 = fp_mul(fp_add(z.c0, s), k.half);
  Fp w = fp_pow(t2, k.exp_isqrt, k.one);
  Fp t = fp_mul(w, t2);
  Fp other = fp_mul(fp_mul(z.c1, w), k.half);
  if (fp_eq(fp_sqr(t), t2)) {
    y = {t, other};
  } else {
    y = {fp_neg(other), t};
  }
  Fp2 check = fp2_sqr(y);
  return fp_eq(check.c0, z.c0) && fp_eq(check.c1, z.c1);
}

// crypto/bls.py `_fq2_lex_larger`: y above -y, ordered by (c1, c0) as
// plain integers
bool fp2_lex_larger(const Fp& c0_plain, const Fp& c1_plain) {
  const Fp& v = fp_is_zero(c1_plain) ? c0_plain : c1_plain;
  if (fp_is_zero(v)) return false;
  Fp neg;
  raw_sub(neg, FP_P, v);
  return fp_cmp(v, neg) > 0;
}

enum G2Status : uint8_t {
  G2_OK = 0,
  G2_INFINITY = 1,
  G2_NOT_COMPRESSED = 2,
  G2_BAD_INFINITY = 3,
  G2_X_OUT_OF_RANGE = 4,
  G2_NOT_ON_CURVE = 5,
};

uint8_t g2_decompress_one(const uint8_t* in, uint8_t* out,
                          const FpConsts& k) {
  uint8_t flags = in[0];
  if (!(flags & 0x80)) return G2_NOT_COMPRESSED;
  if (flags & 0x40) {
    uint8_t rest = flags & 0x3f;
    for (int i = 1; i < 96; i++) rest |= in[i];
    return rest ? G2_BAD_INFINITY : G2_INFINITY;
  }
  uint8_t head[48];
  std::memcpy(head, in, 48);
  head[0] &= 0x1f;
  Fp x1 = fp_from_be48(head), x0 = fp_from_be48(in + 48);
  if (fp_cmp(x0, FP_P) >= 0 || fp_cmp(x1, FP_P) >= 0)
    return G2_X_OUT_OF_RANGE;
  Fp2 x = {fp_mul(x0, k.r2), fp_mul(x1, k.r2)};
  Fp2 rhs = fp2_mul(fp2_sqr(x), x);
  rhs.c0 = fp_add(rhs.c0, k.four);
  rhs.c1 = fp_add(rhs.c1, k.four);
  Fp2 y;
  if (!fp2_sqrt(rhs, y, k)) return G2_NOT_ON_CURVE;
  Fp y0 = fp_mul(y.c0, k.one_plain), y1 = fp_mul(y.c1, k.one_plain);
  if (bool(flags & 0x20) != fp2_lex_larger(y0, y1)) {
    y0 = fp_neg(y0);
    y1 = fp_neg(y1);
  }
  fp_to_be48(x0, out);
  fp_to_be48(x1, out + 48);
  fp_to_be48(y0, out + 96);
  fp_to_be48(y1, out + 144);
  return G2_OK;
}

}  // namespace

extern "C" {

// n compressed G2 points of 96 bytes -> n affine points of 192 bytes
// (x.c0, x.c1, y.c0, y.c1, 48 bytes big-endian each) and one status byte
// an item: 0 decoded, 1 the point at infinity, 2.. the reason it is
// malformed (G2Status; crypto/bls.py maps them to its BlsError texts).
// Where the status is not 0 the item's 192 bytes are zero. No subgroup
// check, as in the Python decoder with subgroup_check=False.
void gt_g2_decompress_batch(const uint8_t* in, uint64_t n, uint8_t* out,
                            uint8_t* status) {
  const FpConsts& k = fp_consts();
  std::memset(out, 0, n * 192);
  for (uint64_t i = 0; i < n; i++)
    status[i] = g2_decompress_one(in + 96 * i, out + 192 * i, k);
}

}  // extern "C"
