/* crc32c (Castagnoli) — hardware + software paths + combine.
 *
 * Native equivalent of the reference's checksum stack
 * (src/common/crc32c.cc dispatching to crc32c_intel_fast /
 * crc32c_aarch64 / sctp_crc32 software fallback, plus
 * ceph_crc32c_zeros-style combine helpers): same polynomial 0x1EDC6F41
 * (reflected 0x82F63B78), same init/xor conventions as
 * bufferlist::crc32c (src/include/buffer.h:1199).
 *
 * Build: cc -O3 -fPIC -shared (see Makefile); SSE4.2 path compiled in
 * when available and selected at runtime via cpuid.
 */

#include <stdint.h>
#include <stddef.h>

#if defined(__x86_64__) || defined(__i386__)
#include <cpuid.h>
#endif

#define POLY_REFLECTED 0x82F63B78u

/* ---------------- software: slice-by-8 ---------------- */

static uint32_t table[8][256];
static int table_ready = 0;

static void init_tables(void) {
    if (table_ready) return;
    for (uint32_t i = 0; i < 256; i++) {
        uint32_t c = i;
        for (int j = 0; j < 8; j++)
            c = (c & 1) ? (c >> 1) ^ POLY_REFLECTED : c >> 1;
        table[0][i] = c;
    }
    for (uint32_t i = 0; i < 256; i++) {
        uint32_t c = table[0][i];
        for (int s = 1; s < 8; s++) {
            c = (c >> 8) ^ table[0][c & 0xff];
            table[s][i] = c;
        }
    }
    table_ready = 1;
}

static uint32_t crc32c_sw(uint32_t crc, const uint8_t *buf, size_t len) {
    init_tables();
    while (len && ((uintptr_t)buf & 7)) {
        crc = (crc >> 8) ^ table[0][(crc ^ *buf++) & 0xff];
        len--;
    }
    while (len >= 8) {
        uint64_t v;
        __builtin_memcpy(&v, buf, 8);
        v ^= crc;
        crc = table[7][v & 0xff] ^ table[6][(v >> 8) & 0xff] ^
              table[5][(v >> 16) & 0xff] ^ table[4][(v >> 24) & 0xff] ^
              table[3][(v >> 32) & 0xff] ^ table[2][(v >> 40) & 0xff] ^
              table[1][(v >> 48) & 0xff] ^ table[0][(v >> 56) & 0xff];
        buf += 8;
        len -= 8;
    }
    while (len--)
        crc = (crc >> 8) ^ table[0][(crc ^ *buf++) & 0xff];
    return crc;
}

/* ---------------- hardware: SSE4.2 crc32 instruction ---------------- */

#if defined(__x86_64__)
__attribute__((target("sse4.2")))
static uint32_t crc32c_hw(uint32_t crc, const uint8_t *buf, size_t len) {
    while (len && ((uintptr_t)buf & 7)) {
        crc = __builtin_ia32_crc32qi(crc, *buf++);
        len--;
    }
    uint64_t c = crc;
    while (len >= 8) {
        uint64_t v;
        __builtin_memcpy(&v, buf, 8);
        c = __builtin_ia32_crc32di(c, v);
        buf += 8;
        len -= 8;
    }
    crc = (uint32_t)c;
    while (len--)
        crc = __builtin_ia32_crc32qi(crc, *buf++);
    return crc;
}

static int have_sse42(void) {
    static int cached = -1;
    if (cached < 0) {
        unsigned eax, ebx, ecx, edx;
        cached = __get_cpuid(1, &eax, &ebx, &ecx, &edx) && (ecx & bit_SSE4_2);
    }
    return cached;
}
#endif

uint32_t ceph_tpu_crc32c(uint32_t crc, const uint8_t *buf, size_t len) {
#if defined(__x86_64__)
    if (have_sse42())
        return crc32c_hw(crc, buf, len);
#endif
    return crc32c_sw(crc, buf, len);
}

/* ---------------- combine: crc(A||B) from crc(A), crc(B), len(B) -----
 *
 * Advancing a CRC over n zero bytes multiplies it, as a polynomial over
 * GF(2), by x^(8n) mod P (zlib's crc32_combine since 1.2.12; the matrix
 * squaring this replaces cost ~70 us a call, and a shard pays one call
 * per overwritten extent: ec_util.refresh_chunk_crcs); combine = shift
 * crc(A) over len(B) zeros then xor crc(B).  This is also exactly what
 * the reference's ceph_crc32c_zeros enables (extending a crc across
 * zero padding without touching memory).
 */

/* a(x) * b(x) mod P in the reflected representation (bit 31 is x^0):
 * 32 shift-and-xor steps at most (zlib's multmodp).  a must not be 0;
 * the callers pass powers of x, which are units mod P. */
static uint32_t gf2_mulmod(uint32_t a, uint32_t b) {
    uint32_t m = 1u << 31, p = 0;
    for (;;) {
        if (a & m) {
            p ^= b;
            if ((a & (m - 1)) == 0) break;
        }
        m >>= 1;
        b = (b & 1) ? (b >> 1) ^ POLY_REFLECTED : b >> 1;
    }
    return p;
}

/* x2n[k] = x^(2^k) mod P, k = 0..66: a zero BYTE is x^8, so bit k of a
 * 64-bit byte length is x2n[k + 3].  Filled by squaring; no period of
 * the polynomial is assumed. */
static uint32_t x2n[67];
static int x2n_ready = 0;

/* at load, before any thread can call in */
__attribute__((constructor)) static void init_x2n(void) {
    if (x2n_ready) return;
    uint32_t p = 1u << 30;                  /* x^1 */
    for (int k = 0; k < 67; k++) {
        x2n[k] = p;
        p = gf2_mulmod(p, p);
    }
    x2n_ready = 1;
}

/* crc * x^(8 len) mod P: one multiplication per set bit of len. */
uint32_t ceph_tpu_crc32c_zeros(uint32_t crc, uint64_t len) {
    init_x2n();
    for (int k = 3; len && crc; len >>= 1, k++)
        if (len & 1)
            crc = gf2_mulmod(x2n[k], crc);
    return crc;
}

uint32_t ceph_tpu_crc32c_combine(uint32_t crc_a, uint32_t crc_b,
                                 uint64_t len_b) {
    return ceph_tpu_crc32c_zeros(crc_a, len_b) ^ crc_b;
}
