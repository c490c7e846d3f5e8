/* ceph_crc32c — CRC-32C (Castagnoli) with the reference's conventions:
 * reflected, NO final inversion, caller seeds (-1 for bufferhash), and the
 * return value is the raw register, so crc(AB) == crc(crc(A), B).
 *
 * Two implementations give the same bits; one is chosen when the library
 * loads, on what the CPU reports (the role of ceph_choose_crc32() in the
 * reference's src/common/crc32c.cc):
 *
 *  - "sse4.2": the x86-64 CRC32 instruction, eight bytes per step. Buffers
 *    of three blocks or more run as three independent streams over
 *    adjacent blocks, so the instruction's latency overlaps; the streams'
 *    registers are joined with a table that appends one block of zero
 *    bytes to a register (the shape of crc32c_intel_fast.c, re-derived).
 *  - "sctp8": table slicing-by-8 (the semantics of sctp_crc32.c,
 *    re-derived, nothing copied), for any other CPU. It keeps its own
 *    exported name so tests can hold the two against each other.
 *
 * Built by ceph_tpu/native/build.py into libcrc32c.so with plain
 * `cc -O3 -shared -fPIC` (the instruction is enabled per function) and
 * loaded with ctypes (ceph_tpu/common/crc.py); numpy is the fallback.
 */

#include <stddef.h>
#include <stdint.h>
#include <string.h>

#if defined(__x86_64__)
#include <nmmintrin.h>
#endif

typedef uint32_t (*crc_fn)(uint32_t, const uint8_t *, size_t);

static uint32_t table[8][256];

static void init_tables(void) {
    const uint32_t poly = 0x82F63B78u; /* reflected 0x1EDC6F41 */
    for (int n = 0; n < 256; n++) {
        uint32_t c = (uint32_t)n;
        for (int i = 0; i < 8; i++)
            c = (c & 1) ? (c >> 1) ^ poly : c >> 1;
        table[0][n] = c;
    }
    for (int k = 1; k < 8; k++)
        for (int n = 0; n < 256; n++)
            table[k][n] = table[0][table[k - 1][n] & 0xFF]
                          ^ (table[k - 1][n] >> 8);
}

uint32_t ceph_crc32c_sctp8(uint32_t seed, const uint8_t *data, size_t len) {
    uint32_t crc = seed;
    while (len >= 8) {
        crc = table[7][(crc ^ data[0]) & 0xFF]
            ^ table[6][((crc >> 8) ^ data[1]) & 0xFF]
            ^ table[5][((crc >> 16) ^ data[2]) & 0xFF]
            ^ table[4][((crc >> 24) ^ data[3]) & 0xFF]
            ^ table[3][data[4]] ^ table[2][data[5]]
            ^ table[1][data[6]] ^ table[0][data[7]];
        data += 8;
        len -= 8;
    }
    while (len--) {
        crc = table[0][(crc ^ *data++) & 0xFF] ^ (crc >> 8);
    }
    return crc;
}

static crc_fn chosen = ceph_crc32c_sctp8;
static const char *chosen_name = "sctp8";

#if defined(__x86_64__)

/* Per-stream block lengths, longest first: 8 KiB blocks carry large
 * buffers, 256-byte blocks what is left of them and buffers of 768 bytes
 * up; anything shorter takes the single stream. */
#define N_BLOCKS 2
static const size_t block_len[N_BLOCKS] = {8192, 256};

/* zeros[b][k][v] = the register after appending block_len[b] zero bytes
 * to the register v << 8k. Appending zeros is linear over GF(2) in the
 * register, so the four byte lanes' entries XOR to the whole register's. */
static uint32_t zeros[N_BLOCKS][4][256];

static uint32_t append_zeros(const uint32_t t[4][256], uint32_t crc) {
    return t[0][crc & 0xFF] ^ t[1][(crc >> 8) & 0xFF]
         ^ t[2][(crc >> 16) & 0xFF] ^ t[3][crc >> 24];
}

static void init_zeros(void) {
    for (int b = 0; b < N_BLOCKS; b++) {
        uint32_t basis[32];
        for (int i = 0; i < 32; i++) {
            uint32_t c = 1u << i;
            for (size_t n = 0; n < block_len[b]; n++)
                c = table[0][c & 0xFF] ^ (c >> 8);
            basis[i] = c;
        }
        for (int k = 0; k < 4; k++)
            for (int v = 0; v < 256; v++) {
                uint32_t c = 0;
                for (int j = 0; j < 8; j++)
                    if (v >> j & 1)
                        c ^= basis[8 * k + j];
                zeros[b][k][v] = c;
            }
    }
}

static inline uint64_t load64(const uint8_t *p) {
    uint64_t v;
    memcpy(&v, p, 8);
    return v;
}

__attribute__((target("sse4.2")))
static uint32_t crc32c_sse42(uint32_t crc, const uint8_t *p, size_t len) {
    while (len && ((uintptr_t)p & 7)) {
        crc = _mm_crc32_u8(crc, *p++);
        len--;
    }
    for (int b = 0; b < N_BLOCKS; b++) {
        const size_t blk = block_len[b];
        while (len >= 3 * blk) {
            /* stream 0 continues the register; 1 and 2 start from zero
             * and are joined by crc(r, AB) = zeros_B(crc(r, A)) ^ crc(0, B) */
            uint64_t c0 = crc, c1 = 0, c2 = 0;
            for (size_t i = 0; i < blk; i += 8) {
                c0 = _mm_crc32_u64(c0, load64(p + i));
                c1 = _mm_crc32_u64(c1, load64(p + blk + i));
                c2 = _mm_crc32_u64(c2, load64(p + 2 * blk + i));
            }
            crc = append_zeros(zeros[b], (uint32_t)c0) ^ (uint32_t)c1;
            crc = append_zeros(zeros[b], crc) ^ (uint32_t)c2;
            p += 3 * blk;
            len -= 3 * blk;
        }
    }
    uint64_t c = crc;
    while (len >= 8) {
        c = _mm_crc32_u64(c, load64(p));
        p += 8;
        len -= 8;
    }
    crc = (uint32_t)c;
    while (len--)
        crc = _mm_crc32_u8(crc, *p++);
    return crc;
}

#endif /* __x86_64__ */

__attribute__((constructor))
static void choose_crc32c(void) {
    init_tables();
#if defined(__x86_64__)
    __builtin_cpu_init();
    if (__builtin_cpu_supports("sse4.2")) {
        init_zeros();
        chosen = crc32c_sse42;
        chosen_name = "sse4.2";
    }
#endif
}

uint32_t ceph_crc32c(uint32_t seed, const uint8_t *data, size_t len) {
    return chosen(seed, data, len);
}

/* which implementation the load-time choice took: "sse4.2" or "sctp8" */
const char *ceph_crc32c_impl(void) {
    return chosen_name;
}
