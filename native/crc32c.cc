// CRC32C (Castagnoli) — native kernel for data checksumming.
//
// Native-performance equivalent of the reference's crc32c
// (reference src/common/crc32c.cc dispatching to
// crc32c_intel_fast.c / crc32c_aarch64.c; polynomial 0x1EDC6F41,
// the one BlueStore/deep-scrub checksums use).  Software
// slicing-by-8 with the SSE4.2 hardware instruction when the build
// host has it (-march=native); exposed via ctypes
// (ceph_tpu/utils/crc.py).
#include <cstddef>
#include <cstdint>

#if defined(__SSE4_2__)
#include <nmmintrin.h>
#endif

static uint32_t table[8][256];
static bool initialized = false;

extern "C" void crc32c_init() {
  if (initialized) return;
  const uint32_t poly = 0x82F63B78u;  // reflected 0x1EDC6F41
  for (uint32_t i = 0; i < 256; i++) {
    uint32_t c = i;
    for (int k = 0; k < 8; k++)
      c = (c & 1) ? (poly ^ (c >> 1)) : (c >> 1);
    table[0][i] = c;
  }
  for (uint32_t i = 0; i < 256; i++) {
    uint32_t c = table[0][i];
    for (int s = 1; s < 8; s++) {
      c = table[0][c & 0xff] ^ (c >> 8);
      table[s][i] = c;
    }
  }
  initialized = true;
}

static inline uint32_t crc_body(uint32_t crc, const uint8_t* data,
                                size_t len) {
  crc = ~crc;
#if defined(__SSE4_2__)
  while (len >= 8) {
    uint64_t v;
    __builtin_memcpy(&v, data, 8);
    crc = (uint32_t)_mm_crc32_u64(crc, v);
    data += 8;
    len -= 8;
  }
  while (len--) crc = _mm_crc32_u8(crc, *data++);
#else
  while (len >= 8) {
    uint32_t lo, hi;
    __builtin_memcpy(&lo, data, 4);
    __builtin_memcpy(&hi, data + 4, 4);
    lo ^= crc;
    crc = table[7][lo & 0xff] ^ table[6][(lo >> 8) & 0xff] ^
          table[5][(lo >> 16) & 0xff] ^ table[4][lo >> 24] ^
          table[3][hi & 0xff] ^ table[2][(hi >> 8) & 0xff] ^
          table[1][(hi >> 16) & 0xff] ^ table[0][hi >> 24];
    data += 8;
    len -= 8;
  }
  while (len--)
    crc = table[0][(crc ^ *data++) & 0xff] ^ (crc >> 8);
#endif
  return ~crc;
}

extern "C" uint32_t crc32c(uint32_t crc, const uint8_t* data,
                           size_t len) {
  return crc_body(crc, data, len);
}

// n independent CRC32Cs, one per block_len bytes of a contiguous
// buffer: a store read verifies all the blocks it gathered in one
// call, so the caller drops and re-takes the interpreter lock once
// and not once a block.
extern "C" void crc32c_blocks(const uint8_t* data, size_t block_len,
                              size_t n, uint32_t* out) {
  for (size_t i = 0; i < n; i++)
    out[i] = crc_body(0, data + i * block_len, block_len);
}
