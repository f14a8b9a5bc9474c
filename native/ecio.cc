// Fused erasure-IO kernels — the host data path's single-pass core.
//
// Role (SURVEY.md §2.5/§2.12, VERDICT r4 next-#1): the e2e PUT/GET gap
// vs the codec microbench was Python glue making 3-4 separate passes
// over every object byte (encode, hash, frame-copy, write).  These
// kernels do the whole shard-side transform in ONE cache-hot C pass per
// 1 MiB block, reading/writing mmap'd files directly so the only
// remaining copies are the ones the hardware requires:
//
//   ec_put_frame   (nb, K, S) data -> per-shard framed files
//                  [32B mxh256 digest | shard] per block, parity rows
//                  computed straight into the output frames (no staging
//                  buffer), every row hashed while still in cache.
//                  The reference does this as three goroutine stages
//                  (Encode -> bitrot writer -> disk, cmd/erasure-
//                  encode.go:36, cmd/bitrot-streaming.go:54).
//
//   ec_get_verify  K framed shard segments -> (nb, K, S) data rows,
//                  hash-verifying every frame and GF-reconstructing
//                  missing data rows in the same pass (the fused
//                  verify+decode of cmd/erasure-decode.go:101 +
//                  cmd/bitrot-streaming.go:142, host edition of
//                  north-star config #5).
//
//   ec_recv_exact  a connected stream socket -> the caller's buffer, the
//                  poll + recv loop of a request body's pull in ONE call
//                  (one GIL release a pull instead of two a recv).
//
//   ec_read_rows   candidate shard files -> K slots of one buffer: the
//                  open + pread + close of a GET segment's shard rows in
//                  ONE call (one GIL release a segment instead of ~8 a
//                  row and a pool hop each).
//
// The mxh256 tree hash and the vpshufb GF(2^8) row multiply are pulled
// in from their single sources of truth (mxh256.cc / rs_cpu.cc) so the
// bytes are provably identical to the spec paths.

#include <cerrno>
#include <cstdint>
#include <cstring>
#include <cstddef>
#include <ctime>

#include <fcntl.h>
#include <poll.h>
#include <sys/socket.h>
#include <unistd.h>

#include "mxh256.cc"   // chunk_words/level + mxh256_rows (exported too)
#include "rs_cpu.cc"   // rs_encode + rs_isa

// GFNI: constant-multiply in GF(2^8)/0x11D as an 8x8 bit-matrix affine
// transform — ONE vgf2p8affineqb per 64 bytes per coefficient vs the
// six-op vpshufb nibble sequence.  The matrix qword layout (byte 7-r =
// row r, direct bit order) is calibrated against the field in
// native/ecio_native.py:affine_qwords and self-checked at load.
#if defined(__GFNI__) && defined(__AVX512BW__)
#define EC_GFNI 1
#endif

extern "C" {

const char* ec_isa() {
#if defined(EC_GFNI)
  return "gfni-avx512";
#elif defined(__AVX512BW__)
  return "avx512bw";
#elif defined(__AVX2__)
  return "avx2";
#else
  return "scalar";
#endif
}

// One-row mxh256: row (len bytes) -> out32.  scratch >= 2*ceil(len/256)*32.
static void mxh_row(const uint8_t* row, size_t len, const int8_t* at,
                    const int32_t* corr, const uint8_t* tag,
                    uint8_t* out32, uint8_t* scratch) {
  size_t max_lvl = len ? (len + 255) / 256 * 32 : 32;
  uint8_t* bufa = scratch;
  uint8_t* bufb = scratch + max_lvl;
  size_t cur_len = level(row, len, at, corr, bufa);
  uint8_t* cur = bufa;
  uint8_t* nxt = bufb;
  while (cur_len != 32) {
    size_t nl = level(cur, cur_len, at, corr, nxt);
    uint8_t* t = cur; cur = nxt; nxt = t;
    cur_len = nl;
  }
  for (int i = 0; i < 32; ++i) out32[i] = cur[i] ^ tag[i];
}

// GF row multiply-accumulate with per-source POINTERS (sources live in
// separate frame buffers): dst = XOR_c coeff_c * src_c over `len` bytes.
// tables: (nsrc, 32) nibble tables; mats: (nsrc) affine qwords — the
// GFNI build uses mats, others use tables (callers pass both).
static void rs_row_ptrs(const uint8_t* tables, const uint64_t* mats,
                        const uint8_t* const* srcs,
                        int nsrc, uint8_t* dst, size_t len) {
  size_t i = 0;
#if defined(EC_GFNI)
  for (; i + 64 <= len; i += 64) {
    __m512i acc = _mm512_setzero_si512();
    for (int c = 0; c < nsrc; ++c) {
      const __m512i A = _mm512_set1_epi64((long long)mats[c]);
      __m512i x = _mm512_loadu_si512((const void*)(srcs[c] + i));
      acc = _mm512_xor_si512(acc, _mm512_gf2p8affine_epi64_epi8(x, A, 0));
    }
    _mm512_storeu_si512((void*)(dst + i), acc);
  }
  (void)tables;
#elif defined(__AVX512BW__)
  const __m512i mask = _mm512_set1_epi8(0x0F);
  for (; i + 64 <= len; i += 64) {
    __m512i acc = _mm512_setzero_si512();
    for (int c = 0; c < nsrc; ++c) {
      const uint8_t* tab = tables + (size_t)c * 32;
      const __m512i lo = _mm512_broadcast_i32x4(
          _mm_loadu_si128((const __m128i*)tab));
      const __m512i hi = _mm512_broadcast_i32x4(
          _mm_loadu_si128((const __m128i*)(tab + 16)));
      __m512i x = _mm512_loadu_si512((const void*)(srcs[c] + i));
      __m512i xl = _mm512_and_si512(x, mask);
      __m512i xh = _mm512_and_si512(_mm512_srli_epi16(x, 4), mask);
      acc = _mm512_xor_si512(acc, _mm512_shuffle_epi8(lo, xl));
      acc = _mm512_xor_si512(acc, _mm512_shuffle_epi8(hi, xh));
    }
    _mm512_storeu_si512((void*)(dst + i), acc);
  }
#elif defined(__AVX2__)
  const __m256i mask = _mm256_set1_epi8(0x0F);
  for (; i + 32 <= len; i += 32) {
    __m256i acc = _mm256_setzero_si256();
    for (int c = 0; c < nsrc; ++c) {
      const uint8_t* tab = tables + (size_t)c * 32;
      const __m256i lo = _mm256_broadcastsi128_si256(
          _mm_loadu_si128((const __m128i*)tab));
      const __m256i hi = _mm256_broadcastsi128_si256(
          _mm_loadu_si128((const __m128i*)(tab + 16)));
      __m256i x = _mm256_loadu_si256((const __m256i*)(srcs[c] + i));
      __m256i xl = _mm256_and_si256(x, mask);
      __m256i xh = _mm256_and_si256(_mm256_srli_epi16(x, 4), mask);
      acc = _mm256_xor_si256(acc, _mm256_shuffle_epi8(lo, xl));
      acc = _mm256_xor_si256(acc, _mm256_shuffle_epi8(hi, xh));
    }
    _mm256_storeu_si256((__m256i*)(dst + i), acc);
  }
#endif
  for (; i < len; ++i) {
    uint8_t acc = 0;
    for (int c = 0; c < nsrc; ++c) {
      const uint8_t* tab = tables + (size_t)c * 32;
      uint8_t x = srcs[c][i];
      acc ^= tab[x & 15] ^ tab[16 + (x >> 4)];
    }
    dst[i] = acc;
  }
}

// PUT: data (nb, k, S) contiguous -> outs[k+m] framed shard streams,
// outs[s] receiving nb frames of (32 | S) bytes (may be an mmap'd file).
// rs_tables: (m, k, 32) parity nibble tables; rs_mats: (m, k) affine
// qwords (GFNI builds); at/corr: mxh matrix; tag: 32-byte mxh length
// tag for S.  scratch >= 2*ceil(S/256)*32 + 64.
void ec_put_frame(const uint8_t* data, int nb, int k, int m, size_t S,
                  const uint8_t* rs_tables, const uint64_t* rs_mats,
                  const int8_t* at,
                  const int32_t* corr, const uint8_t* tag,
                  uint8_t* const* outs, uint8_t* scratch) {
  const size_t frame = 32 + S;
  const uint8_t* srcs[64];
  for (int b = 0; b < nb; ++b) {
    const uint8_t* blk = data + (size_t)b * k * S;
    for (int i = 0; i < k; ++i) srcs[i] = blk + (size_t)i * S;
    // Parity rows straight into their output frames (no staging).
    for (int r = 0; r < m; ++r) {
      uint8_t* dst = outs[k + r] + (size_t)b * frame;
      rs_row_ptrs(rs_tables + (size_t)r * k * 32, rs_mats + (size_t)r * k,
                  srcs, k, dst + 32, S);
      mxh_row(dst + 32, S, at, corr, tag, dst, scratch);
    }
    // Data rows: copy + hash while the block is cache-hot.
    for (int i = 0; i < k; ++i) {
      uint8_t* dst = outs[i] + (size_t)b * frame;
      std::memcpy(dst + 32, blk + (size_t)i * S, S);
      mxh_row(dst + 32, S, at, corr, tag, dst, scratch);
    }
  }
}

// GET: frames[j] = the j-th SELECTED shard's segment (nb frames of
// (32 | S), e.g. an mmap of the file range); sel[j] = its shard index in
// [0, k+m).  Verifies every frame's digest; copies data rows (sel[j] <
// k) into y (nb, k, S); reconstructs `tgts` (missing data rows) via
// dec_tables ((ntgt, ksel, 32), columns in sel order).  ok[j] (init 1)
// is cleared on the first digest mismatch of row j; returns the number
// of bad rows (caller re-reads spares and retries — bitrot is rare).
int ec_get_verify(const uint8_t* const* frames, const int32_t* sel,
                  int ksel, int nb, size_t S, int k,
                  const uint8_t* dec_tables, const uint64_t* dec_mats,
                  const int32_t* tgts, int ntgt,
                  const int8_t* at, const int32_t* corr, const uint8_t* tag,
                  uint8_t* y, uint8_t* ok, uint8_t* scratch) {
  const size_t frame = 32 + S;
  uint8_t digest[32];
  int nbad = 0;
  const uint8_t* srcs[64];
  for (int b = 0; b < nb; ++b) {
    for (int j = 0; j < ksel; ++j) {
      if (!ok[j]) continue;
      const uint8_t* f = frames[j] + (size_t)b * frame;
      mxh_row(f + 32, S, at, corr, tag, digest, scratch);
      if (std::memcmp(digest, f, 32) != 0) { ok[j] = 0; ++nbad; continue; }
      if (sel[j] < k)
        std::memcpy(y + ((size_t)b * k + sel[j]) * S, f + 32, S);
    }
    if (nbad) continue;              // result is void; skip the GF work
    for (int t = 0; t < ntgt; ++t) {
      for (int j = 0; j < ksel; ++j)
        srcs[j] = frames[j] + (size_t)b * frame + 32;
      rs_row_ptrs(dec_tables + (size_t)t * ksel * 32,
                  dec_mats + (size_t)t * ksel, srcs, ksel,
                  y + ((size_t)b * k + tgts[t]) * S, S);
    }
  }
  return nbad;
}

// Healthy-GET verdict-only pass: hash-verify every frame of every
// selected row, touch nothing else.  No gather, no GF — the fast path
// asks "are all k data shards intact?" and, on yes, assembles the
// object from systematic slices (they ARE the plaintext).  ok[j]
// (init 1) is cleared on row j's first mismatch; returns bad rows.
int ec_verify_frames(const uint8_t* const* frames, int ksel, int nb,
                     size_t S, const int8_t* at, const int32_t* corr,
                     const uint8_t* tag, uint8_t* ok, uint8_t* scratch) {
  const size_t frame = 32 + S;
  uint8_t digest[32];
  int nbad = 0;
  for (int j = 0; j < ksel; ++j) {
    for (int b = 0; b < nb; ++b) {
      const uint8_t* f = frames[j] + (size_t)b * frame;
      mxh_row(f + 32, S, at, corr, tag, digest, scratch);
      if (std::memcmp(digest, f, 32) != 0) { ok[j] = 0; ++nbad; break; }
    }
  }
  return nbad;
}

// Whole-row GF transform with per-row pointers: dsts[t] = sum_c
// M[t][c] * srcs[c] over len bytes — the heal path reconstructs full
// logical shard rows without ever stacking them into a batch matrix.
void ec_gf_rows(const uint8_t* tables, const uint64_t* mats,
                const uint8_t* const* srcs, int nsrc,
                uint8_t* const* dsts, int ntgt, size_t len) {
  for (int t = 0; t < ntgt; ++t) {
    rs_row_ptrs(tables + (size_t)t * nsrc * 32,
                mats + (size_t)t * nsrc, srcs, nsrc, dsts[t], len);
  }
}

static int64_t mono_ms() {
  struct timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return (int64_t)ts.tv_sec * 1000 + ts.tv_nsec / 1000000;
}

// Fill buf[0..n) from the connected stream socket `fd`: recv until the
// buffer is full or the peer closes, waiting in poll whenever the
// socket is dry.  Returns the bytes filled (< n only at the peer's
// close) or -errno; -ETIMEDOUT when `timeout_ms` passed without a byte
// (an idle limit per wait, not a limit on the call; < 0 waits for
// ever).  EINTR and EAGAIN are retried here.  *recvs gets the recv
// calls that returned bytes.  MSG_DONTWAIT: the call never depends on
// the descriptor's own blocking mode (Python keeps a socket with a
// timeout non-blocking).
ssize_t ec_recv_exact(int fd, uint8_t* buf, size_t n, int timeout_ms,
                      int* recvs) {
  size_t got = 0;
  int calls = 0;
  ssize_t rc = 0;
  while (got < n) {
    ssize_t r = recv(fd, buf + got, n - got, MSG_DONTWAIT);
    if (r > 0) { got += (size_t)r; ++calls; continue; }
    if (r == 0) break;                         // peer closed
    if (errno == EINTR) continue;
    if (errno != EAGAIN && errno != EWOULDBLOCK) { rc = -errno; break; }
    struct pollfd p = {fd, POLLIN, 0};
    int64_t until = mono_ms() + timeout_ms;
    int left = timeout_ms, pr;
    while ((pr = poll(&p, 1, left)) < 0 && errno == EINTR) {
      if (timeout_ms >= 0) {
        int64_t l = until - mono_ms();
        left = l > 0 ? (int)l : 0;
      }
    }
    if (pr == 0) { rc = -ETIMEDOUT; break; }
    if (pr < 0) { rc = -errno; break; }
    // POLLIN, POLLHUP or POLLERR: the next recv says which.
  }
  if (recvs) *recvs = calls;
  return rc < 0 ? rc : (ssize_t)got;
}

static int64_t mono_ns() {
  struct timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return (int64_t)ts.tv_sec * 1000000000 + ts.tv_nsec;
}

// ec_read_rows' per-candidate outcomes besides 0 (read) and an errno.
#define EC_ROW_SIZE (-1)      // the file ends before offset + length
                              // (or, with exact_end, after it)
#define EC_ROW_UNTRIED (-2)   // k slots were filled before its turn

// Read [offset, offset + length) of paths[0..n), one file after another
// in that order, each into the next free `length`-byte slot of `out`,
// until k slots are filled.  A file is opened, pread and closed;
// `exact_end`: it must end at offset + length (the range holds the
// shard's tail fragment); `drop`: POSIX_FADV_DONTNEED once read (the
// page-cache policy of storage/diskio.py).  For each candidate i:
// err[i] 0, the errno of a failed open or read, EC_ROW_SIZE or
// EC_ROW_UNTRIED; slot[i] the slot it filled or -1; ns[i] its
// nanoseconds.  Returns the slots filled.
int ec_read_rows(const char* const* paths, int n, int k, int64_t offset,
                 size_t length, int exact_end, int drop, uint8_t* out,
                 int32_t* err, int32_t* slot, int64_t* ns) {
  int filled = 0;
  for (int i = 0; i < n; ++i) {
    slot[i] = -1;
    ns[i] = 0;
    if (filled >= k) { err[i] = EC_ROW_UNTRIED; continue; }
    const int64_t t0 = mono_ns();
    int e = 0;
    int fd = open(paths[i], O_RDONLY | O_CLOEXEC);
    if (fd < 0) {
      e = errno;
    } else {
      uint8_t* dst = out + (size_t)filled * length;
      size_t got = 0;
      while (got < length) {
        ssize_t r = pread(fd, dst + got, length - got, offset + (off_t)got);
        if (r > 0) { got += (size_t)r; continue; }
        if (r == 0) break;                       // EOF
        if (errno == EINTR) continue;
        e = errno;
        break;
      }
      if (!e && got < length) e = EC_ROW_SIZE;
      if (!e && exact_end) {
        uint8_t b;
        ssize_t r;
        while ((r = pread(fd, &b, 1, offset + (off_t)length)) < 0
               && errno == EINTR) {}
        if (r > 0) e = EC_ROW_SIZE;
        else if (r < 0) e = errno;
      }
      if (drop) posix_fadvise(fd, 0, 0, POSIX_FADV_DONTNEED);
      close(fd);
    }
    err[i] = e;
    ns[i] = mono_ns() - t0;
    if (!e) slot[i] = filled++;
  }
  return filled;
}

// GFNI<->field self-check material: y = c * x in GF(2^8)/0x11D for the
// loader to validate the affine-matrix layout at import time.
int ec_selftest_mul(const uint64_t* mat, int x) {
#if defined(EC_GFNI)
  __m128i X = _mm_set1_epi8((char)x);
  __m128i A = _mm_set1_epi64x((long long)mat[0]);
  __m128i Y = _mm_gf2p8affine_epi64_epi8(X, A, 0);
  return (uint8_t)_mm_extract_epi8(Y, 0);
#else
  (void)mat; (void)x;
  return -1;
#endif
}

}  // extern "C"
