// Native replay core — sum-tree inner loops (SURVEY.md §7.3 item 2), the
// columnar staged append, and the wire's CRC-32C.
//
// The reference keeps all native compute in external deps (Caffe/ALE,
// SURVEY §2.1); its replay is pure Python. The rebuild's host-side PER
// sampling is the one genuinely pointer-chasing hot loop left outside XLA
// (root→leaf descent per sample lane), so it gets a C++ core: the numpy
// implementation in replay/prioritized.py stays as the portable fallback
// and the reference semantics; this file must match it bit-for-bit on the
// float64 tree (tests/test_native.py asserts equivalence).
//
// Exposed via plain C ABI for ctypes (no pybind11 in the image). All
// buffers are caller-owned numpy arrays; nothing here allocates.

#include <cstdint>
#include <cstring>

#if defined(__x86_64__)
#include <nmmintrin.h>
#elif defined(__aarch64__)
#include <arm_acle.h>
#include <asm/hwcap.h>
#include <sys/auxv.h>
#endif

namespace {

// CRC-32C (Castagnoli, reflected 0x82F63B78) slicing-by-8 tables, built
// by the compiler: t[0] is the classic byte table, t[k][b] advances
// t[k-1][b] past one more zero byte.
struct Crc32cTables {
  uint32_t t[8][256];
  constexpr Crc32cTables() : t() {
    for (uint32_t i = 0; i < 256; ++i) {
      uint32_t c = i;
      for (int k = 0; k < 8; ++k) c = (c & 1) ? (c >> 1) ^ 0x82F63B78u : c >> 1;
      t[0][i] = c;
    }
    for (int k = 1; k < 8; ++k) {
      for (uint32_t i = 0; i < 256; ++i) {
        t[k][i] = (t[k - 1][i] >> 8) ^ t[0][t[k - 1][i] & 0xFF];
      }
    }
  }
};
constexpr Crc32cTables kCrc{};

// Portable path. Works on the raw (pre-inverted) state, like the
// hardware paths below; the 8-byte word is assembled bytewise, so the
// result does not depend on the host's byte order or on alignment.
uint32_t crc32c_table(uint32_t c, const unsigned char* p, int64_t n) {
  for (; n >= 8; p += 8, n -= 8) {
    const uint32_t lo = c ^ (static_cast<uint32_t>(p[0]) |
                             static_cast<uint32_t>(p[1]) << 8 |
                             static_cast<uint32_t>(p[2]) << 16 |
                             static_cast<uint32_t>(p[3]) << 24);
    c = kCrc.t[7][lo & 0xFF] ^ kCrc.t[6][(lo >> 8) & 0xFF] ^
        kCrc.t[5][(lo >> 16) & 0xFF] ^ kCrc.t[4][lo >> 24] ^
        kCrc.t[3][p[4]] ^ kCrc.t[2][p[5]] ^ kCrc.t[1][p[6]] ^
        kCrc.t[0][p[7]];
  }
  for (; n > 0; ++p, --n) c = kCrc.t[0][(c ^ *p) & 0xFF] ^ (c >> 8);
  return c;
}

// Hardware paths: the CPU's own CRC-32C instruction, eight bytes a step.
// The build line carries no -march, so each is compiled for its feature
// by a target attribute and chosen at run time in crc32c_update.
#if defined(__x86_64__)
#define DDQ_CRC32C_HW 1
__attribute__((target("sse4.2")))
uint32_t crc32c_hw(uint32_t c, const unsigned char* p, int64_t n) {
  uint64_t c64 = c;
  for (; n >= 8; p += 8, n -= 8) {
    uint64_t w;
    std::memcpy(&w, p, 8);  // unaligned-safe; one mov
    c64 = _mm_crc32_u64(c64, w);
  }
  c = static_cast<uint32_t>(c64);
  for (; n > 0; ++p, --n) c = _mm_crc32_u8(c, *p);
  return c;
}
bool crc32c_hw_ok() { return __builtin_cpu_supports("sse4.2"); }
#elif defined(__aarch64__)
#define DDQ_CRC32C_HW 1
__attribute__((target("+crc")))
uint32_t crc32c_hw(uint32_t c, const unsigned char* p, int64_t n) {
  for (; n >= 8; p += 8, n -= 8) {
    uint64_t w;
    std::memcpy(&w, p, 8);
    c = __crc32cd(c, w);
  }
  for (; n > 0; ++p, --n) c = __crc32cb(c, *p);
  return c;
}
bool crc32c_hw_ok() { return (getauxval(AT_HWCAP) & HWCAP_CRC32) != 0; }
#endif

}  // namespace

extern "C" {

// CRC-32C of buf[0:n], continuing `state` (a previous result; 0 to
// start) — zlib.crc32's contract with the Castagnoli polynomial. Must
// stay bit-identical to utils/durability.py's numpy implementation,
// which remains the reference semantics and the fallback
// (tests/test_durability.py asserts equivalence on both paths). One
// call, no Python: ctypes.CDLL gives the interpreter lock up around it,
// which is why the wire's checksum lives here (ISSUE 30).
uint32_t crc32c_update(uint32_t state, const unsigned char* buf, int64_t n) {
#ifdef DDQ_CRC32C_HW
  static const bool hw = crc32c_hw_ok();
  if (hw) return ~crc32c_hw(~state, buf, n);
#endif
  return ~crc32c_table(~state, buf, n);
}

// The portable table loop alone, whatever the CPU offers: lets the tests
// hold BOTH native paths to the reference on a host that would always
// take the instruction.
uint32_t crc32c_update_portable(uint32_t state, const unsigned char* buf,
                                int64_t n) {
  return ~crc32c_table(~state, buf, n);
}

// Columnar staged append (ISSUE 8 ingest path): copy n rows of each of
// ncols columns into its caller-owned staging buffer at row `cursor`.
// dst[c] is the base of column c's staging buffer, src[c] the incoming
// contiguous segment, row_bytes[c] the column's row stride. One memcpy
// per COLUMN (not per row) — the whole point: the Python hot path pays
// O(columns) of call overhead per staged segment and zero per-row work.
// Returns the advanced cursor. Must stay bit-identical to the numpy
// fallback (`buf[cursor:cursor+n] = seg`), which remains the reference
// semantics (tests/test_columnar_ingest.py asserts equivalence).
int64_t staged_append(unsigned char* const* dst,
                      const unsigned char* const* src,
                      const int64_t* row_bytes, int64_t ncols,
                      int64_t cursor, int64_t n) {
  for (int64_t c = 0; c < ncols; ++c) {
    std::memcpy(dst[c] + cursor * row_bytes[c], src[c],
                static_cast<size_t>(n * row_bytes[c]));
  }
  return cursor + n;
}

// Set leaves tree[size + idx[k]] = p[k] (duplicates: last write wins, same
// as numpy fancy assignment), then repair ancestors bottom-up.
void st_set(double* tree, int64_t size, const int64_t* idx, const double* p,
            int64_t n) {
  for (int64_t k = 0; k < n; ++k) {
    tree[size + idx[k]] = p[k];
  }
  for (int64_t k = 0; k < n; ++k) {
    for (int64_t node = (size + idx[k]) >> 1; node >= 1; node >>= 1) {
      tree[node] = tree[2 * node] + tree[2 * node + 1];
    }
  }
}

// Stratified proportional sampling: lane k draws target
// (k + urand[k]) * total / n and descends root→leaf.
// Matches SumTree.sample_stratified (replay/prioritized.py).
void st_sample_stratified(const double* tree, int64_t size,
                          const double* urand, int64_t* out, int64_t n) {
  const double total = tree[1];
  const double stride = total / static_cast<double>(n);
  for (int64_t k = 0; k < n; ++k) {
    double target = (static_cast<double>(k) + urand[k]) * stride;
    int64_t node = 1;
    while (node < size) {
      const int64_t left = 2 * node;
      const double left_sum = tree[left];
      // strict '>' to match the numpy descent (targets > left_sum)
      if (target > left_sum) {
        target -= left_sum;
        node = left + 1;
      } else {
        node = left;
      }
    }
    out[k] = node - size;
  }
}

}  // extern "C"
