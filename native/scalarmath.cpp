// Batch scalar preparation for the signature-verification kernels.
//
// The TPU device kernels (corda_tpu/ops/{weierstrass,ed25519}.py) consume
// pre-derived scalars/window indices/limb arrays; deriving them per item in
// Python bigints was the service path's ceiling (~0.9s per 32k secp256k1
// batch, ~1.9s for Ed25519, measured in an early round).  This module
// does the whole scalar layer in one C pass per batch:
//   - Barrett modular arithmetic over the fixed curve moduli
//   - Montgomery batch inversion (one Fermat modpow per BATCH)
//   - secp256k1 GLV decomposition (Babai rounding, exact quotients)
//   - window/digit extraction and u16 limb packing in the kernels' wire
//     layout (16 little-endian 16-bit limbs per 256-bit value)
//
// Reference seams covered: Crypto.kt:473-496 (per-signature doVerify host
// work), OutOfProcessTransactionVerifierService.kt:18-71 (the service
// batching path this feeds).  No reference code is used here: the reference
// delegates scalar math to BouncyCastle/i2p; this is a from-scratch
// implementation of SEC1 §4.1.4 / RFC 8032 host-side scalar derivation.
//
// All multi-word values are little-endian arrays of u64.  Build:
//   g++ -O2 -fPIC -std=c++17 -shared -o libscalarmath.so scalarmath.cpp
// Loaded via ctypes (corda_tpu/ops/scalarprep.py) with a pure-Python
// fallback when the .so is absent.

#include <cstdint>
#include <cstring>

typedef unsigned __int128 u128;
typedef uint64_t u64;
typedef uint32_t u32;
typedef uint16_t u16;
typedef uint8_t u8;

namespace {

// ---------------------------------------------------------------------------
// Generic little-endian multiword helpers
// ---------------------------------------------------------------------------

inline void mp_zero(u64* x, int n) { std::memset(x, 0, 8 * n); }

inline void mp_copy(u64* d, const u64* s, int n) { std::memcpy(d, s, 8 * n); }

inline int mp_cmp(const u64* a, const u64* b, int n) {
    for (int i = n - 1; i >= 0; --i) {
        if (a[i] != b[i]) return a[i] < b[i] ? -1 : 1;
    }
    return 0;
}

inline bool mp_is_zero(const u64* a, int n) {
    for (int i = 0; i < n; ++i) if (a[i]) return false;
    return true;
}

inline u64 mp_add(u64* out, const u64* a, const u64* b, int n) {
    u128 c = 0;
    for (int i = 0; i < n; ++i) {
        c += (u128)a[i] + b[i];
        out[i] = (u64)c;
        c >>= 64;
    }
    return (u64)c;
}

inline u64 mp_sub(u64* out, const u64* a, const u64* b, int n) {
    u128 borrow = 0;
    for (int i = 0; i < n; ++i) {
        u128 d = (u128)a[i] - b[i] - borrow;
        out[i] = (u64)d;
        borrow = (d >> 64) ? 1 : 0;
    }
    return (u64)borrow;
}

// out[na+nb] = a * b (schoolbook; out must not alias inputs)
inline void mp_mul(const u64* a, int na, const u64* b, int nb, u64* out) {
    mp_zero(out, na + nb);
    for (int i = 0; i < na; ++i) {
        u128 carry = 0;
        u64 ai = a[i];
        for (int j = 0; j < nb; ++j) {
            u128 t = (u128)ai * b[j] + out[i + j] + carry;
            out[i + j] = (u64)t;
            carry = t >> 64;
        }
        out[i + nb] = (u64)carry;
    }
}

// ---------------------------------------------------------------------------
// Barrett reduction context for a fixed 256-bit modulus (HAC 14.42, b=2^64,
// k=4).  mu = floor(2^512 / m) fits 5 words for every modulus here
// (all are >= 2^252 > 2^192).
// ---------------------------------------------------------------------------

struct Mod {
    u64 m[4];
    u64 m5[5];     // m zero-extended to 5 words (for the k+1-word compare)
    u64 mu[5];
    u64 half[4];   // floor(m / 2) (the GLV split's rounding bias)
};

// mu = floor(2^512 / m) by restoring bitwise division (one-time per modulus).
void mod_init(Mod* M, const u64 m[4]) {
    mp_copy(M->m, m, 4);
    mp_copy(M->m5, m, 4);
    M->m5[4] = 0;
    u64 rem[5] = {0, 0, 0, 0, 0};
    u64 q[5] = {0, 0, 0, 0, 0};
    for (int bit = 512; bit >= 0; --bit) {
        // rem = rem << 1 | (bit == 512)
        u64 carry = (bit == 512) ? 1 : 0;
        for (int i = 0; i < 5; ++i) {
            u64 nc = rem[i] >> 63;
            rem[i] = (rem[i] << 1) | carry;
            carry = nc;
        }
        if (mp_cmp(rem, M->m5, 5) >= 0) {
            mp_sub(rem, rem, M->m5, 5);
            if (bit < 320) q[bit / 64] |= 1ull << (bit % 64);
        }
    }
    mp_copy(M->mu, q, 5);
    for (int i = 3; i >= 0; --i) {
        M->half[i] = (m[i] >> 1) | (i < 3 ? (m[i + 1] & 1) << 63 : 0);
    }
}

// r = x mod m for x < 2^512 (8 words); optionally returns the exact
// quotient's low 4 words in q_out (caller guarantees quotient < 2^256).
void bar_divmod(const Mod* M, const u64 x[8], u64 r[4], u64 q_out[4]) {
    // q1 = floor(x / b^3): 5 words x[3..7]
    const u64* q1 = x + 3;
    u64 q2[10];
    mp_mul(q1, 5, M->mu, 5, q2);           // q1 * mu
    u64* q3 = q2 + 5;                       // floor(q2 / b^5): 5 words
    // r1 = x mod b^5
    u64 r1[5];
    mp_copy(r1, x, 5);
    // r2 = (q3 * m) mod b^5
    u64 r2full[9];
    mp_mul(q3, 5, M->m, 4, r2full);
    // r = (r1 - r2) mod b^5  (fixed-width wraparound is the HAC "+ b^{k+1}")
    u64 rr[5];
    mp_sub(rr, r1, r2full, 5);
    u64 extra = 0;
    while (mp_cmp(rr, M->m5, 5) >= 0) {
        mp_sub(rr, rr, M->m5, 5);
        ++extra;
    }
    mp_copy(r, rr, 4);
    if (q_out) {
        u64 ext[5] = {extra, 0, 0, 0, 0};
        u64 q5[5];
        mp_add(q5, q3, ext, 5);
        mp_copy(q_out, q5, 4);
    }
}

inline void mod_red(const Mod* M, const u64 x[8], u64 r[4]) {
    bar_divmod(M, x, r, nullptr);
}

inline void mod_mul(const Mod* M, const u64 a[4], const u64 b[4], u64 r[4]) {
    u64 t[8];
    mp_mul(a, 4, b, 4, t);
    mod_red(M, t, r);
}

// r = base^exp mod m (binary ladder over a 256-bit exponent; ~20us — used
// once per BATCH by the Montgomery inversion, never per item).
void mod_pow(const Mod* M, const u64 base[4], const u64 exp[4], u64 r[4]) {
    u64 acc[4] = {1, 0, 0, 0};
    u64 sq[4];
    mp_copy(sq, base, 4);
    for (int i = 0; i < 256; ++i) {
        if ((exp[i / 64] >> (i % 64)) & 1) mod_mul(M, acc, sq, acc);
        if (i < 255) mod_mul(M, sq, sq, sq);
    }
    mp_copy(r, acc, 4);
}

// In-place Montgomery batch inversion of n nonzero values mod M
// (exp = m - 2: Fermat).  scratch: n*4 words.
void batch_inv(const Mod* M, u64* vals, int64_t n, u64* scratch) {
    if (n == 0) return;
    u64 acc[4] = {1, 0, 0, 0};
    for (int64_t i = 0; i < n; ++i) {
        mod_mul(M, acc, vals + 4 * i, acc);
        mp_copy(scratch + 4 * i, acc, 4);
    }
    u64 exp[4], two[4] = {2, 0, 0, 0};
    mp_sub(exp, M->m, two, 4);
    u64 inv[4];
    mod_pow(M, acc, exp, inv);
    for (int64_t i = n - 1; i > 0; --i) {
        u64 vi[4];
        mp_copy(vi, vals + 4 * i, 4);
        mod_mul(M, inv, scratch + 4 * (i - 1), vals + 4 * i);
        mod_mul(M, inv, vi, inv);
    }
    mp_copy(vals, inv, 4);
}

// ---------------------------------------------------------------------------
// Curve constants
// ---------------------------------------------------------------------------

const u64 K1_P[4] = {0xFFFFFFFEFFFFFC2Full, 0xFFFFFFFFFFFFFFFFull,
                     0xFFFFFFFFFFFFFFFFull, 0xFFFFFFFFFFFFFFFFull};
const u64 K1_N[4] = {0xBFD25E8CD0364141ull, 0xBAAEDCE6AF48A03Bull,
                     0xFFFFFFFFFFFFFFFEull, 0xFFFFFFFFFFFFFFFFull};
const u64 K1_B[4] = {7, 0, 0, 0};
// GLV basis (ecmath.py:371-386): beta, a1, |b1|, a2, b2 = a1
const u64 K1_BETA[4] = {0xC1396C28719501EEull, 0x9CF0497512F58995ull,
                        0x6E64479EAC3434E9ull, 0x7AE96A2B657C0710ull};
const u64 GLV_A1[2] = {0xE86C90E49284EB15ull, 0x3086D221A7D46BCDull};
const u64 GLV_AB1[2] = {0x6F547FA90ABFE4C3ull, 0xE4437ED6010E8828ull};
const u64 GLV_A2[3] = {0x57C1108D9D44CFD8ull, 0x14CA50F7A8E2F3F6ull, 1};
// b2 = a1

const u64 R1_P[4] = {0xFFFFFFFFFFFFFFFFull, 0x00000000FFFFFFFFull,
                     0x0000000000000000ull, 0xFFFFFFFF00000001ull};
const u64 R1_N[4] = {0xF3B9CAC2FC632551ull, 0xBCE6FAADA7179E84ull,
                     0xFFFFFFFFFFFFFFFFull, 0xFFFFFFFF00000000ull};
const u64 R1_B[4] = {0x3BCE3C3E27D2604Bull, 0x651D06B0CC53B0F6ull,
                     0xB3EBBD55769886BCull, 0x5AC635D8AA3A93E7ull};

const u64 ED_P[4] = {0xFFFFFFFFFFFFFFEDull, 0xFFFFFFFFFFFFFFFFull,
                     0xFFFFFFFFFFFFFFFFull, 0x7FFFFFFFFFFFFFFFull};
const u64 ED_L[4] = {0x5812631A5CF5D3EDull, 0x14DEF9DEA2F79CD6ull,
                     0x0000000000000000ull, 0x1000000000000000ull};

struct Ctx {
    Mod k1n, k1p, r1n, r1p, edl, edp;
    Ctx() {
        mod_init(&k1n, K1_N);
        mod_init(&k1p, K1_P);
        mod_init(&r1n, R1_N);
        mod_init(&r1p, R1_P);
        mod_init(&edl, ED_L);
        mod_init(&edp, ED_P);
    }
};

// C++11 magic static: thread-safe one-time construction (the batcher and
// OOP verifier call in from worker threads concurrently).
Ctx& ctx() {
    static Ctx c;
    return c;
}

// ---------------------------------------------------------------------------
// Per-curve helpers
// ---------------------------------------------------------------------------

inline void mod_neg(const Mod* P, const u64 y[4], u64 out[4]) {
    if (mp_is_zero(y, 4)) { mp_zero(out, 4); return; }
    mp_sub(out, P->m, y, 4);
}

// y^2 == x^3 + a*x + b (mod p) with a = 0 (k1) or a = -3 (r1).  The sum
// x^3 + (-3x mod p) + b runs in 5-word arithmetic (it can exceed 2^256)
// with trailing conditional subtractions — no Barrett needed.
bool on_curve(const Mod* P, const u64 x[4], const u64 y[4], const u64 b[4],
              bool a_minus3) {
    if (mp_cmp(x, P->m, 4) >= 0 || mp_cmp(y, P->m, 4) >= 0) return false;
    u64 y2[4], x2[4], x3[4];
    mod_mul(P, y, y, y2);
    mod_mul(P, x, x, x2);
    mod_mul(P, x2, x, x3);
    u64 acc[5], t5[5];
    mp_copy(acc, x3, 4);
    acc[4] = 0;
    mp_copy(t5, b, 4);
    t5[4] = 0;
    mp_add(acc, acc, t5, 5);
    if (a_minus3) {
        // acc += (p - (3x mod p))
        u64 three = 3, tx[5];
        mp_mul(x, 4, &three, 1, tx);
        while (mp_cmp(tx, P->m5, 5) >= 0) mp_sub(tx, tx, P->m5, 5);
        u64 negt[4];
        mod_neg(P, tx, negt);
        mp_copy(t5, negt, 4);
        t5[4] = 0;
        mp_add(acc, acc, t5, 5);
    }
    while (mp_cmp(acc, P->m5, 5) >= 0) mp_sub(acc, acc, P->m5, 5);
    return mp_cmp(y2, acc, 4) == 0;
}

// Signed GLV decomposition of k (mod n): k = k1 + k2*lambda, |k1|,|k2|<2^128.
// Mirrors ecmath.glv_decompose exactly (Babai rounding with n/2 bias).
// Returns false if a half ever exceeds 128 bits (mathematically impossible
// for k < n — a false return means an arithmetic bug, not bad input).
bool glv_split(const Ctx& C, const u64 k[4],
               bool* neg1, u64 abs1[2], bool* neg2, u64 abs2[2]) {
    const Mod* N = &C.k1n;
    // c1 = floor((b2*k + n/2) / n); b2 = a1 (2 words)
    u64 t6[6], t8[8];
    mp_mul(GLV_A1, 2, k, 4, t6);
    mp_copy(t8, t6, 6);
    t8[6] = t8[7] = 0;
    u64 nh5[8];
    mp_copy(nh5, N->half, 4);
    nh5[4] = nh5[5] = nh5[6] = nh5[7] = 0;
    mp_add(t8, t8, nh5, 8);
    u64 c1[4], rdump[4];
    bar_divmod(N, t8, rdump, c1);
    // c2 = floor((|b1|*k + n/2) / n)
    mp_mul(GLV_AB1, 2, k, 4, t6);
    mp_copy(t8, t6, 6);
    t8[6] = t8[7] = 0;
    mp_add(t8, t8, nh5, 8);
    u64 c2[4];
    bar_divmod(N, t8, rdump, c2);
    // k1 = k - c1*a1 - c2*a2  (plain integers; |k1| < 2^128)
    u64 s1[6], s2[6], S[6];
    mp_mul(c1, 2, GLV_A1, 2, s1);            // 4 words
    s1[4] = s1[5] = 0;
    mp_mul(c2, 2, GLV_A2, 3, s2);            // 5 words
    s2[5] = 0;
    mp_add(S, s1, s2, 6);
    u64 k6[6];
    mp_copy(k6, k, 4);
    k6[4] = k6[5] = 0;
    u64 d[6];
    if (mp_cmp(k6, S, 6) >= 0) {
        mp_sub(d, k6, S, 6);
        *neg1 = false;
    } else {
        mp_sub(d, S, k6, 6);
        *neg1 = true;
    }
    abs1[0] = d[0];
    abs1[1] = d[1];
    bool fit = !(d[2] | d[3] | d[4] | d[5]);
    // k2 = c1*|b1| - c2*b2 ; b2 = a1
    u64 p1[4], p2[4];
    mp_mul(c1, 2, GLV_AB1, 2, p1);
    mp_mul(c2, 2, GLV_A1, 2, p2);
    u64 d2[4];
    if (mp_cmp(p1, p2, 4) >= 0) {
        mp_sub(d2, p1, p2, 4);
        *neg2 = false;
    } else {
        mp_sub(d2, p2, p1, 4);
        *neg2 = true;
    }
    abs2[0] = d2[0];
    abs2[1] = d2[1];
    return fit && !(d2[2] | d2[3]);
}

// u64[4] LE value -> 16 LE u16 limbs (the kernels' wire limb format).
inline void write_limbs(u16* out, const u64 v[4]) {
    std::memcpy(out, v, 32);      // little-endian host: exact reinterpret
}

// ---------------------------------------------------------------------------
// secp256r1 half-gcd split (Antipa et al., "Accelerated Verification of
// ECDSA Signatures", SAC 2005): extended Euclid on (n, k) stopped at the
// first remainder below 2^128, giving k = v1/v2 (mod n) with both legs
// under 128 bits.  P-256 has no GLV endomorphism, so this is its only
// route to a half-length ladder.
// ---------------------------------------------------------------------------

inline int mp_bits(const u64* a, int n) {
    for (int i = n - 1; i >= 0; --i) {
        if (a[i]) return 64 * i + 64 - __builtin_clzll(a[i]);
    }
    return 0;
}

// out[nw] = a[na] << sh (caller guarantees the result fits nw words)
inline void mp_shl(u64* out, int nw, const u64* a, int na, int sh) {
    mp_zero(out, nw);
    int w = sh / 64, b = sh % 64;
    for (int i = na - 1; i >= 0; --i) {
        if (i + w < nw) out[i + w] |= a[i] << b;
        if (b && i + w + 1 < nw) out[i + w + 1] |= a[i] >> (64 - b);
    }
}

inline void mp_shr1(u64* a, int n) {
    for (int i = 0; i < n; ++i) {
        a[i] = (a[i] >> 1) | (i + 1 < n ? a[i + 1] << 63 : 0);
    }
}

// k (0 < k < n) → (neg1, v1, v2) with k*v2 ≡ (neg1 ? -v1 : v1) (mod n),
// 0 <= v1 < 2^128 and 0 < v2 < 2^128.  Signs in the EEA t-sequence strictly
// alternate, so only magnitudes are tracked (m_new = m0 + q*m1) with one
// parity bit; the invariant |t_i| <= n / r_{i-1} and the stop condition
// r_{i-1} >= 2^128 bound every magnitude strictly below 2^128 (a leg of
// exactly 2^128 is impossible).  A false return means the split degenerated
// (k = 0 / k >= n, or a defensive overflow check fired) — the caller routes
// such items to the host-oracle fallback.
bool r1_halfgcd(const u64 k[4], bool* neg1, u64 v1[2], u64 v2[2]) {
    if (mp_is_zero(k, 4) || mp_cmp(k, R1_N, 4) >= 0) return false;
    u64 r0[4], r1v[4], m0[4] = {0, 0, 0, 0}, m1[4] = {1, 0, 0, 0};
    mp_copy(r0, R1_N, 4);
    mp_copy(r1v, k, 4);
    bool s_pos = true;               // sign of the t attached to r1v
    while (r1v[2] | r1v[3]) {        // r1 >= 2^128
        // q = r0 / r1v, rem = r0 % r1v by shift-subtract: EEA quotients are
        // log-distributed, so total shift work across the loop is O(256)
        int d = mp_bits(r0, 4) - mp_bits(r1v, 4);
        u64 q[4] = {0, 0, 0, 0};
        u64 sh[5], rem[5];
        mp_shl(sh, 5, r1v, 4, d);
        mp_copy(rem, r0, 4);
        rem[4] = 0;
        for (int b = d; b >= 0; --b) {
            if (mp_cmp(rem, sh, 5) >= 0) {
                mp_sub(rem, rem, sh, 5);
                q[b / 64] |= 1ull << (b % 64);
            }
            mp_shr1(sh, 5);
        }
        mp_copy(r0, r1v, 4);
        mp_copy(r1v, rem, 4);
        u64 t8[8], m_new[4];
        mp_mul(q, 4, m1, 4, t8);
        u64 carry = mp_add(m_new, m0, t8, 4);
        if (carry || t8[4] | t8[5] | t8[6] | t8[7]) return false;
        mp_copy(m0, m1, 4);
        mp_copy(m1, m_new, 4);
        s_pos = !s_pos;
    }
    if (mp_is_zero(r1v, 4) || mp_is_zero(m1, 4)) return false;
    if (m1[2] | m1[3]) return false;
    v1[0] = r1v[0];
    v1[1] = r1v[1];
    v2[0] = m1[0];
    v2[1] = m1[1];
    // normalize v2 > 0: when t1 < 0, negate both legs and push the sign
    // onto v1 (applied to Q's y host-side)
    *neg1 = !s_pos;
    return true;
}

// ---------------------------------------------------------------------------
// Fast P-256 field arithmetic (FIPS 186-4 D.2.3 Solinas reduction) for the
// host-side [v2]R Jacobian ladder: the half-gcd prep runs ~1600 field mults
// per item here, where Barrett would triple the cost.
// ---------------------------------------------------------------------------

// r = t mod p256 for t < p^2 (8 words viewed as 16 u32 digits c0..c15).
void r1p_red(const u64 t[8], u64 r[4]) {
    u32 c[16];
    for (int i = 0; i < 8; ++i) {
        c[2 * i] = (u32)t[i];
        c[2 * i + 1] = (u32)(t[i] >> 32);
    }
    int64_t d[8];
    d[0] = (int64_t)c[0] + c[8] + c[9] - c[11] - c[12] - c[13] - c[14];
    d[1] = (int64_t)c[1] + c[9] + c[10] - c[12] - c[13] - c[14] - c[15];
    d[2] = (int64_t)c[2] + c[10] + c[11] - c[13] - c[14] - c[15];
    d[3] = (int64_t)c[3] + 2 * (int64_t)c[11] + 2 * (int64_t)c[12] + c[13]
         - c[15] - c[8] - c[9];
    d[4] = (int64_t)c[4] + 2 * (int64_t)c[12] + 2 * (int64_t)c[13] + c[14]
         - c[9] - c[10];
    d[5] = (int64_t)c[5] + 2 * (int64_t)c[13] + 2 * (int64_t)c[14] + c[15]
         - c[10] - c[11];
    d[6] = (int64_t)c[6] + c[13] + 3 * (int64_t)c[14] + 2 * (int64_t)c[15]
         - c[8] - c[9];
    d[7] = (int64_t)c[7] + c[8] + 3 * (int64_t)c[15] - c[10] - c[11]
         - c[12] - c[13];
    int64_t carry = 0;
    u32 out[8];
    for (int i = 0; i < 8; ++i) {
        int64_t v = d[i] + carry;
        out[i] = (u32)(v & 0xFFFFFFFFll);
        carry = v >> 32;             // arithmetic shift: floor division
    }
    u64 lo[4];
    for (int i = 0; i < 4; ++i) {
        lo[i] = (u64)out[2 * i] | ((u64)out[2 * i + 1] << 32);
    }
    // fold the signed end carry: value = lo + carry*2^256 and
    // 2^256 ≡ D (mod p) with D = 2^256 - p = 2^224 - 2^192 - 2^96 + 1;
    // each step trades one unit of carry for one add/sub of D (the loop
    // terminates within a few steps — |carry| <= 8 and wraps feed back
    // at most one unit)
    static const u64 D[4] = {0x0000000000000001ull, 0xFFFFFFFF00000000ull,
                             0xFFFFFFFFFFFFFFFFull, 0x00000000FFFFFFFEull};
    int guard = 0;
    while (carry != 0 && ++guard < 64) {
        if (carry > 0) {
            u64 ovf = mp_add(lo, lo, D, 4);
            carry += (int64_t)ovf - 1;
        } else {
            u64 brw = mp_sub(lo, lo, D, 4);
            carry += 1 - (int64_t)brw;
        }
    }
    while (mp_cmp(lo, R1_P, 4) >= 0) mp_sub(lo, lo, R1_P, 4);
    mp_copy(r, lo, 4);
}

// alias-safe (r may be a or b): the full product lands in t first
inline void r1p_mul(const u64 a[4], const u64 b[4], u64 r[4]) {
    u64 t[8];
    mp_mul(a, 4, b, 4, t);
    r1p_red(t, r);
}

inline void r1p_add(const u64 a[4], const u64 b[4], u64 r[4]) {
    u64 c = mp_add(r, a, b, 4);
    if (c || mp_cmp(r, R1_P, 4) >= 0) mp_sub(r, r, R1_P, 4);
}

inline void r1p_sub(const u64 a[4], const u64 b[4], u64 r[4]) {
    if (mp_sub(r, a, b, 4)) mp_add(r, r, R1_P, 4);
}

struct Jac { u64 X[4], Y[4], Z[4]; };

// o ← 2a, a = -3 (dbl-2001-b, 3M+5S); a must not be the identity.
// Alias-safe for o == a (every a-field is consumed before o is written).
void r1_jdbl(Jac* o, const Jac* a) {
    u64 delta[4], gamma[4], beta[4], alpha[4], t1[4], t2[4], m[4], yz[4];
    r1p_mul(a->Z, a->Z, delta);
    r1p_mul(a->Y, a->Y, gamma);
    r1p_mul(a->X, gamma, beta);
    r1p_sub(a->X, delta, t1);
    r1p_add(a->X, delta, t2);
    r1p_mul(t1, t2, m);
    r1p_add(m, m, alpha);
    r1p_add(alpha, m, alpha);        // alpha = 3(X-delta)(X+delta)
    r1p_add(a->Y, a->Z, yz);
    r1p_mul(yz, yz, yz);
    r1p_sub(yz, gamma, o->Z);        // Z3 = (Y+Z)^2 - gamma - delta
    r1p_sub(o->Z, delta, o->Z);
    u64 b8[4];
    r1p_add(beta, beta, b8);
    r1p_add(b8, b8, b8);
    r1p_add(b8, b8, b8);
    r1p_mul(alpha, alpha, t1);
    r1p_sub(t1, b8, o->X);           // X3 = alpha^2 - 8 beta
    u64 b4[4], g2[4];
    r1p_add(beta, beta, b4);
    r1p_add(b4, b4, b4);
    r1p_sub(b4, o->X, t2);
    r1p_mul(alpha, t2, t1);
    r1p_mul(gamma, gamma, g2);
    r1p_add(g2, g2, g2);
    r1p_add(g2, g2, g2);
    r1p_add(g2, g2, g2);
    r1p_sub(t1, g2, o->Y);           // Y3 = alpha(4 beta - X3) - 8 gamma^2
}

// o ← a + b (add-2007-bl, 11M+5S); both non-identity and a != ±b — the
// [v2]R ladder proves this structurally (see r1_mul_point).  Alias-safe
// for o == a.
void r1_jadd(Jac* o, const Jac* a, const Jac* b) {
    u64 z1z1[4], z2z2[4], u1[4], u2[4], s1[4], s2[4], t[4];
    r1p_mul(a->Z, a->Z, z1z1);
    r1p_mul(b->Z, b->Z, z2z2);
    r1p_mul(a->X, z2z2, u1);
    r1p_mul(b->X, z1z1, u2);
    r1p_mul(a->Y, b->Z, t);
    r1p_mul(t, z2z2, s1);
    r1p_mul(b->Y, a->Z, t);
    r1p_mul(t, z1z1, s2);
    u64 h[4], i_[4], j[4], rr_[4], v[4], zs[4];
    r1p_sub(u2, u1, h);
    r1p_add(h, h, t);
    r1p_mul(t, t, i_);               // I = (2H)^2
    r1p_mul(h, i_, j);
    r1p_sub(s2, s1, t);
    r1p_add(t, t, rr_);              // r = 2(S2 - S1)
    r1p_mul(u1, i_, v);
    r1p_add(a->Z, b->Z, zs);
    r1p_mul(zs, zs, zs);
    r1p_sub(zs, z1z1, zs);
    r1p_sub(zs, z2z2, zs);
    r1p_mul(zs, h, o->Z);            // Z3 = ((Z1+Z2)^2 - Z1Z1 - Z2Z2) H
    u64 x3[4], sj[4];
    r1p_mul(rr_, rr_, x3);
    r1p_sub(x3, j, x3);
    r1p_sub(x3, v, x3);
    r1p_sub(x3, v, x3);              // X3 = r^2 - J - 2V
    r1p_sub(v, x3, t);
    r1p_mul(rr_, t, t);
    r1p_mul(s1, j, sj);
    r1p_add(sj, sj, sj);
    r1p_sub(t, sj, o->Y);            // Y3 = r(V - X3) - 2 S1 J
    mp_copy(o->X, x3, 4);
}

// D = [v2]R for affine R = (rx, ry), 0 < v2 < 2^128, via 4-bit fixed
// windows (124 dbl + ~29 add).  Writes Jacobian (X, Z) only — the caller
// does an x-only projective compare, so Y is never needed.  Exception-free:
// before every add the accumulator is [16·prefix]R with 0 < 16·prefix <
// 2^128 ≪ n and the table entry is [d]R with d <= 15 < 16·prefix, so the
// add operands can never be equal or inverse.
void r1_mul_point(const u64 rx[4], const u64 ry[4], const u64 v2[2],
                  u64 outX[4], u64 outZ[4]) {
    Jac T[16];
    mp_copy(T[1].X, rx, 4);
    mp_copy(T[1].Y, ry, 4);
    mp_zero(T[1].Z, 4);
    T[1].Z[0] = 1;
    r1_jdbl(&T[2], &T[1]);
    for (int i = 3; i < 16; ++i) {
        if (i & 1) r1_jadd(&T[i], &T[i - 1], &T[1]);
        else r1_jdbl(&T[i], &T[i / 2]);
    }
    Jac acc;
    bool started = false;
    for (int t = 0; t < 32; ++t) {
        int shift = 4 * (31 - t);
        int dig = (int)((v2[shift / 64] >> (shift % 64)) & 0xF);
        if (started) {
            r1_jdbl(&acc, &acc);
            r1_jdbl(&acc, &acc);
            r1_jdbl(&acc, &acc);
            r1_jdbl(&acc, &acc);
            if (dig) r1_jadd(&acc, &acc, &T[dig]);
        } else if (dig) {
            acc = T[dig];
            started = true;
        }
    }
    mp_copy(outX, acc.X, 4);         // v2 >= 1 ⇒ started
    mp_copy(outZ, acc.Z, 4);
}

// y = sqrt(z) mod p256 via z^((p+1)/4) (p ≡ 3 mod 4); false when z is a
// non-residue (r is then not a valid x-coordinate).
bool r1p_sqrt(const u64 z[4], u64 y[4]) {
    // (p+1)/4 = 2^254 - 2^222 + 2^190 + 2^94
    static const u64 EXP[4] = {0x0000000000000000ull, 0x0000000040000000ull,
                               0x4000000000000000ull, 0x3FFFFFFFC0000000ull};
    u64 acc[4] = {1, 0, 0, 0}, sq[4], chk[4];
    mp_copy(sq, z, 4);
    for (int i = 0; i < 256; ++i) {
        if ((EXP[i / 64] >> (i % 64)) & 1) r1p_mul(acc, sq, acc);
        if (i < 255) r1p_mul(sq, sq, sq);
    }
    r1p_mul(acc, acc, chk);
    if (mp_cmp(chk, z, 4) != 0) return false;
    mp_copy(y, acc, 4);
    return true;
}

// ---------------------------------------------------------------------------
// SHA-512 (FIPS 180-4), portable: the library links nothing.  Held to
// hashlib.sha512 through sm_sha512 (tests/test_scalarprep.py).
// ---------------------------------------------------------------------------

const u64 SHA512_K[80] = {
    0x428a2f98d728ae22ull, 0x7137449123ef65cdull, 0xb5c0fbcfec4d3b2full, 0xe9b5dba58189dbbcull,
    0x3956c25bf348b538ull, 0x59f111f1b605d019ull, 0x923f82a4af194f9bull, 0xab1c5ed5da6d8118ull,
    0xd807aa98a3030242ull, 0x12835b0145706fbeull, 0x243185be4ee4b28cull, 0x550c7dc3d5ffb4e2ull,
    0x72be5d74f27b896full, 0x80deb1fe3b1696b1ull, 0x9bdc06a725c71235ull, 0xc19bf174cf692694ull,
    0xe49b69c19ef14ad2ull, 0xefbe4786384f25e3ull, 0x0fc19dc68b8cd5b5ull, 0x240ca1cc77ac9c65ull,
    0x2de92c6f592b0275ull, 0x4a7484aa6ea6e483ull, 0x5cb0a9dcbd41fbd4ull, 0x76f988da831153b5ull,
    0x983e5152ee66dfabull, 0xa831c66d2db43210ull, 0xb00327c898fb213full, 0xbf597fc7beef0ee4ull,
    0xc6e00bf33da88fc2ull, 0xd5a79147930aa725ull, 0x06ca6351e003826full, 0x142929670a0e6e70ull,
    0x27b70a8546d22ffcull, 0x2e1b21385c26c926ull, 0x4d2c6dfc5ac42aedull, 0x53380d139d95b3dfull,
    0x650a73548baf63deull, 0x766a0abb3c77b2a8ull, 0x81c2c92e47edaee6ull, 0x92722c851482353bull,
    0xa2bfe8a14cf10364ull, 0xa81a664bbc423001ull, 0xc24b8b70d0f89791ull, 0xc76c51a30654be30ull,
    0xd192e819d6ef5218ull, 0xd69906245565a910ull, 0xf40e35855771202aull, 0x106aa07032bbd1b8ull,
    0x19a4c116b8d2d0c8ull, 0x1e376c085141ab53ull, 0x2748774cdf8eeb99ull, 0x34b0bcb5e19b48a8ull,
    0x391c0cb3c5c95a63ull, 0x4ed8aa4ae3418acbull, 0x5b9cca4f7763e373ull, 0x682e6ff3d6b2b8a3ull,
    0x748f82ee5defb2fcull, 0x78a5636f43172f60ull, 0x84c87814a1f0ab72ull, 0x8cc702081a6439ecull,
    0x90befffa23631e28ull, 0xa4506cebde82bde9ull, 0xbef9a3f7b2c67915ull, 0xc67178f2e372532bull,
    0xca273eceea26619cull, 0xd186b8c721c0c207ull, 0xeada7dd6cde0eb1eull, 0xf57d4f7fee6ed178ull,
    0x06f067aa72176fbaull, 0x0a637dc5a2c898a6ull, 0x113f9804bef90daeull, 0x1b710b35131c471bull,
    0x28db77f523047d84ull, 0x32caab7b40c72493ull, 0x3c9ebe0a15c9bebcull, 0x431d67c49c100d4cull,
    0x4cc5d4becb3e42b6ull, 0x597f299cfc657e2aull, 0x5fcb6fab3ad6faecull, 0x6c44198c4a475817ull,
};

inline u64 rotr64(u64 x, int n) { return (x >> n) | (x << (64 - n)); }

// One 128-byte block into the state (big-endian message words).
void sha512_block(u64 h[8], const u8* p) {
    u64 w[80];
    for (int t = 0; t < 16; ++t) {
        u64 v = 0;
        for (int j = 0; j < 8; ++j) v = (v << 8) | p[8 * t + j];
        w[t] = v;
    }
    for (int t = 16; t < 80; ++t) {
        u64 s0 = rotr64(w[t - 15], 1) ^ rotr64(w[t - 15], 8) ^ (w[t - 15] >> 7);
        u64 s1 = rotr64(w[t - 2], 19) ^ rotr64(w[t - 2], 61) ^ (w[t - 2] >> 6);
        w[t] = w[t - 16] + s0 + w[t - 7] + s1;
    }
    u64 a = h[0], b = h[1], c = h[2], d = h[3];
    u64 e = h[4], f = h[5], g = h[6], hh = h[7];
    for (int t = 0; t < 80; ++t) {
        u64 S1 = rotr64(e, 14) ^ rotr64(e, 18) ^ rotr64(e, 41);
        u64 t1 = hh + S1 + ((e & f) ^ (~e & g)) + SHA512_K[t] + w[t];
        u64 S0 = rotr64(a, 28) ^ rotr64(a, 34) ^ rotr64(a, 39);
        u64 t2 = S0 + ((a & b) ^ (a & c) ^ (b & c));
        hh = g; g = f; f = e; e = d + t1;
        d = c; c = b; b = a; a = t1 + t2;
    }
    h[0] += a; h[1] += b; h[2] += c; h[3] += d;
    h[4] += e; h[5] += f; h[6] += g; h[7] += hh;
}

struct Sha512 {
    u64 h[8];
    u8 buf[128];
    size_t fill;
    u64 total;      // bytes so far (a message here is far under 2^61 bytes)
};

void sha512_init(Sha512* S) {
    static const u64 H0[8] = {
        0x6a09e667f3bcc908ull, 0xbb67ae8584caa73bull, 0x3c6ef372fe94f82bull,
        0xa54ff53a5f1d36f1ull, 0x510e527fade682d1ull, 0x9b05688c2b3e6c1full,
        0x1f83d9abfb41bd6bull, 0x5be0cd19137e2179ull};
    std::memcpy(S->h, H0, sizeof H0);
    S->fill = 0;
    S->total = 0;
}

void sha512_update(Sha512* S, const u8* p, size_t n) {
    S->total += n;
    if (S->fill) {
        size_t take = 128 - S->fill < n ? 128 - S->fill : n;
        std::memcpy(S->buf + S->fill, p, take);
        S->fill += take;
        p += take;
        n -= take;
        if (S->fill < 128) return;
        sha512_block(S->h, S->buf);
        S->fill = 0;
    }
    for (; n >= 128; p += 128, n -= 128) sha512_block(S->h, p);
    if (n) {
        std::memcpy(S->buf, p, n);
        S->fill = n;
    }
}

// The digest as its 64 big-endian bytes (what hashlib's digest() gives).
void sha512_final(Sha512* S, u8 out[64]) {
    S->buf[S->fill++] = 0x80;
    if (S->fill > 112) {
        std::memset(S->buf + S->fill, 0, 128 - S->fill);
        sha512_block(S->h, S->buf);
        S->fill = 0;
    }
    std::memset(S->buf + S->fill, 0, 120 - S->fill);   // high length word too
    u64 bits = S->total << 3;
    for (int j = 0; j < 8; ++j) S->buf[120 + j] = (u8)(bits >> (8 * (7 - j)));
    S->buf[119] = (u8)(S->total >> 61);
    sha512_block(S->h, S->buf);
    for (int i = 0; i < 8; ++i)
        for (int j = 0; j < 8; ++j)
            out[8 * i + j] = (u8)(S->h[i] >> (8 * (7 - j)));
}

// The split ladder's scalar windows of one row: s = s_lo + 2^128 s_hi as
// w=16 windows MSB-first over each 128-bit half (b_lo / b_hi, (8, stride)),
// k as joint 2-bit digits klo | khi<<2 (a_packed, (64, stride)).
inline void ed_split_windows(const u64 s[4], const u64 k[4], int64_t stride,
                             int64_t i, int32_t* b_lo, int32_t* b_hi,
                             u8* a_packed) {
    for (int t = 0; t < 8; ++t) {
        int shift = 16 * (7 - t);        // within the 128-bit half
        b_lo[(int64_t)t * stride + i] =
            (int32_t)((s[shift / 64] >> (shift % 64)) & 0xFFFF);
        b_hi[(int64_t)t * stride + i] =
            (int32_t)((s[2 + shift / 64] >> (shift % 64)) & 0xFFFF);
    }
    for (int t = 0; t < 64; ++t) {
        int shift = 2 * (63 - t);
        u32 klo = (u32)((k[shift / 64] >> (shift % 64)) & 3);
        u32 khi = (u32)((k[2 + shift / 64] >> (shift % 64)) & 3);
        a_packed[(int64_t)t * stride + i] = (u8)(klo | (khi << 2));
    }
}

}  // namespace

// ---------------------------------------------------------------------------
// Exports
// ---------------------------------------------------------------------------

extern "C" {

int sm_version() { return 7; }

// Differential-test seam: r = a*b mod m for mod_id in
// {0: k1 n, 1: k1 p, 2: r1 n, 3: r1 p, 4: ed L, 5: ed P}.
int sm_mulmod(int mod_id, const u64* a, const u64* b, u64* r) {
    const Ctx& C = ctx();
    const Mod* tbl[6] = {&C.k1n, &C.k1p, &C.r1n, &C.r1p, &C.edl, &C.edp};
    if (mod_id < 0 || mod_id > 5) return -1;
    mod_mul(tbl[mod_id], a, b, r);
    return 0;
}

// Differential-test seam: r = x mod m for a 512-bit x (8 words).
int sm_mod512(int mod_id, const u64* x, u64* r) {
    const Ctx& C = ctx();
    const Mod* tbl[6] = {&C.k1n, &C.k1p, &C.r1n, &C.r1p, &C.edl, &C.edp};
    if (mod_id < 0 || mod_id > 5) return -1;
    mod_red(tbl[mod_id], x, r);
    return 0;
}

// Differential-test seam for the GLV split.
int sm_glv(const u64* k, u8* negs, u64* abs1, u64* abs2) {
    bool n1, n2;
    bool fit = glv_split(ctx(), k, &n1, abs1, &n2, abs2);
    negs[0] = n1;
    negs[1] = n2;
    return fit ? 0 : -2;
}

// Differential-test seam: out = SHA-512(buf[0 .. len)).
int sm_sha512(const u8* buf, int64_t len, u8* out) {
    if (len < 0) return -1;
    Sha512 S;
    sha512_init(&S);
    sha512_update(&S, buf, (size_t)len);
    sha512_final(&S, out);
    return 0;
}

// Strict-DER ECDSA signatures -> LE u64 word rows: the native body of
// scalarprep.ecdsa_sigs_to_words, whose Python loop is the oracle.  Row i is
// buf[offsets[i] .. offsets[i+1]).  The acceptance set is that loop's, byte
// for byte: 0x30, ONE plain length byte equal to len - 2 (no long form), two
// 0x02 integers with a length of 1..(what is left), no set high bit, a
// leading zero only before a byte whose high bit is set, at most 32 bytes
// after that sign byte (>= 2^256: clamp-to-reject), nothing trailing.  A
// refused row is all zeros with ok = 0.
int sm_ecdsa_der_words(int64_t n, const u8* buf, const int64_t* offsets,
                       u64* r_words, u64* s_words, u8* ok)
{
    std::memset(r_words, 0, 32 * (size_t)n);
    std::memset(s_words, 0, 32 * (size_t)n);
    for (int64_t i = 0; i < n; ++i) {
        const u8* der = buf + offsets[i];
        const int64_t len = offsets[i + 1] - offsets[i];
        u8* rows[2] = {reinterpret_cast<u8*>(r_words + 4 * i),
                       reinterpret_cast<u8*>(s_words + 4 * i)};
        bool good = len >= 8 && der[0] == 0x30 && (int64_t)der[1] == len - 2;
        int64_t idx = 2;
        for (int k = 0; good && k < 2; ++k) {
            if (idx + 2 > len || der[idx] != 0x02) { good = false; break; }
            const int64_t ln = der[idx + 1];
            const u8* body = der + idx + 2;
            if (ln == 0 || idx + 2 + ln > len || (body[0] & 0x80)
                    || (ln > 1 && body[0] == 0 && !(body[1] & 0x80))) {
                good = false;
                break;
            }
            int64_t m = ln;
            if (body[0] == 0) { ++body; --m; }   // the sign byte
            if (m > 32) { good = false; break; }
            for (int64_t j = 0; j < m; ++j) rows[k][j] = body[m - 1 - j];
            idx += 2 + ln;
        }
        good = good && idx == len;
        if (!good) {
            std::memset(rows[0], 0, 32);
            std::memset(rows[1], 0, 32);
        }
        ok[i] = good ? 1 : 0;
    }
    return 0;
}

// secp256k1 hybrid-GLV prep (mirrors weierstrass.prepare_batch_hybrid_wide
// + _precheck_and_scalars for g_w = 8).  Inputs: e (raw SHA-256 as LE
// words), r, s, pub (x,y) — all (n, ...) row-major.  Outputs in the
// kernel's wire layout; returns 0.
int sm_k1_prep(int64_t n,
               const u64* e, const u64* rr, const u64* ss, const u64* pub,
               int32_t* g_idx,      // (16, n)
               u8* q_packed,        // (64, n)
               u16* qc_x, u16* qc_y, u16* qd_x, u16* qd_y,   // (n,16) each
               u16* r_limbs,        // (n, 16)
               u8* rn_ok, u8* precheck,
               u64* work)           // scratch: 3*n*4 words
{
    const Ctx& C = ctx();
    const Mod* N = &C.k1n;
    const Mod* P = &C.k1p;
    u64* sw = work;              // (n,4) s-values for batch inversion
    u64* scratch = work + 4 * n; // (n,4) prefix products
    u64* em = work + 8 * n;      // (n,4) e mod n
    // pass 1: validate + substitute
    for (int64_t i = 0; i < n; ++i) {
        const u64* r4 = rr + 4 * i;
        const u64* s4 = ss + 4 * i;
        const u64* x4 = pub + 8 * i;
        const u64* y4 = pub + 8 * i + 4;
        bool ok = !mp_is_zero(r4, 4) && mp_cmp(r4, N->m, 4) < 0
               && !mp_is_zero(s4, 4) && mp_cmp(s4, N->m, 4) < 0
               && on_curve(P, x4, y4, K1_B, false);
        precheck[i] = ok ? 1 : 0;
        if (ok) {
            mp_copy(sw + 4 * i, s4, 4);
            // e mod n: e < 2^256 < 2n → one conditional subtract
            const u64* e4 = e + 4 * i;
            if (mp_cmp(e4, N->m, 4) >= 0) mp_sub(em + 4 * i, e4, N->m, 4);
            else mp_copy(em + 4 * i, e4, 4);
        } else {
            u64 one[4] = {1, 0, 0, 0};
            mp_copy(sw + 4 * i, one, 4);
            mp_zero(em + 4 * i, 4);
        }
    }
    batch_inv(N, sw, n, scratch);
    // pass 2: scalars, GLV, points, windows
    for (int64_t i = 0; i < n; ++i) {
        bool ok = precheck[i];
        u64 u1[4], u2[4];
        if (ok) {
            mod_mul(N, em + 4 * i, sw + 4 * i, u1);
            u64 rmod[4];
            mp_copy(rmod, rr + 4 * i, 4);   // valid ⇒ r < n already
            mod_mul(N, rmod, sw + 4 * i, u2);
        } else {
            mp_zero(u1, 4);
            mp_zero(u2, 4);
        }
        bool sa, sb, sc, sd;
        u64 aa[2], ab[2], ac[2], ad[2];
        if (!glv_split(C, u1, &sa, aa, &sb, ab)) return -2;
        if (!glv_split(C, u2, &sc, ac, &sd, ad)) return -2;
        // Q legs: Qc = (sign c applied to pub), Qd = (sign d applied to phi)
        u64 qx[4], qy[4], py[4], phix[4];
        if (ok) {
            mp_copy(qx, pub + 8 * i, 4);
            mp_copy(qy, pub + 8 * i + 4, 4);
        } else {
            // substitute G (matching the Python prep)
            const u64 GX[4] = {0x59F2815B16F81798ull, 0x029BFCDB2DCE28D9ull,
                               0x55A06295CE870B07ull, 0x79BE667EF9DCBBACull};
            const u64 GY[4] = {0x9C47D08FFB10D4B8ull, 0xFD17B448A6855419ull,
                               0x5DA4FBFC0E1108A8ull, 0x483ADA7726A3C465ull};
            mp_copy(qx, GX, 4);
            mp_copy(qy, GY, 4);
        }
        mod_mul(P, qx, K1_BETA, phix);
        // write Qc
        mp_copy(py, qy, 4);
        if (sc) mod_neg(P, qy, py);
        write_limbs(qc_x + 16 * i, qx);
        write_limbs(qc_y + 16 * i, py);
        // write Qd (phi point, sign d)
        mp_copy(py, qy, 4);
        if (sd) mod_neg(P, qy, py);
        write_limbs(qd_x + 16 * i, phix);
        write_limbs(qd_y + 16 * i, py);
        // G-leg gather indices: 16 outer windows of 8 bits, MSB-first
        u32 sbit = ((u32)(sa ? 1 : 0) << 16) | ((u32)(sb ? 1 : 0) << 17);
        for (int t = 0; t < 16; ++t) {
            int shift = 8 * (15 - t);
            u32 wa = (u32)((aa[shift / 64] >> (shift % 64)) & 0xFF);
            u32 wb = (u32)((ab[shift / 64] >> (shift % 64)) & 0xFF);
            g_idx[(int64_t)t * n + i] = (int32_t)(wa | (wb << 8) | sbit);
        }
        // Q-leg packed 2-bit joint digits, MSB-first (64 of them)
        for (int t = 0; t < 64; ++t) {
            int shift = 2 * (63 - t);
            u32 wc = (u32)((ac[shift / 64] >> (shift % 64)) & 3);
            u32 wd = (u32)((ad[shift / 64] >> (shift % 64)) & 3);
            q_packed[(int64_t)t * n + i] = (u8)(wc | (wd << 2));
        }
        // r candidates
        const u64* r4 = rr + 4 * i;
        u64 rw[4];
        if (ok) mp_copy(rw, r4, 4);
        else mp_zero(rw, 4);
        write_limbs(r_limbs + 16 * i, rw);
        u64 rn[4];
        u64 carry = mp_add(rn, rw, N->m, 4);
        rn_ok[i] = (!carry && mp_cmp(rn, P->m, 4) < 0) ? 1 : 0;
    }
    return 0;
}

// Differential-test seam for the half-gcd split: k (4 LE words, 0 < k < n)
// → neg1, v1, v2 (2 words each) with k*v2 ≡ (neg1 ? -v1 : v1) (mod n) and
// both legs < 2^128.  Returns -2 when the split degenerates.
int sm_r1_halfgcd(const u64* k, u8* neg1, u64* v1, u64* v2) {
    bool ng;
    if (!r1_halfgcd(k, &ng, v1, v2)) return -2;
    *neg1 = ng ? 1 : 0;
    return 0;
}

// Differential-test seam for the Solinas fast P-256 reduction used by the
// [v2]R ladder (vs sm_mulmod mod_id=3's Barrett path).  Inputs canonical.
int sm_r1p_mulfast(const u64* a, const u64* b, u64* r) {
    r1p_mul(a, b, r);
    return 0;
}

// secp256r1 half-gcd split prep (PR 3 fast path; mirrors
// weierstrass._prepare_r1_split_python bit-for-bit).  Per item:
//   u2 = v1/v2 (mod n), |v1|, v2 < 2^128  ⇒  the verify identity
//   [u1]G + [u2]Q = W  ⟺  [t]G + [v1']Q = [v2]W  with t = v2*u1 mod n.
// The device ladder computes W2 = [t_lo]G + [t_hi]G' + [v1']Q (G' =
// [2^128]G, 124 doublings) and accepts iff x(W2) == x([v2]R) projectively;
// x([v2]R) is computed HERE (decompress r — either parity works, x is
// parity-free — then a 4-bit Jacobian ladder, one batch inversion for the
// whole batch's affine x) and shipped as limbs.
//
// hg_ok[i] = 0 routes item i to the host-oracle fallback: r + n < p (the
// second x-candidate exists and the split compare can't see it), r not a
// valid x-coordinate (sqrt fails), or a defensive half-gcd bound check.
// Precheck failures keep hg_ok = 1: their verdict is already False and
// they get benign zero windows (W2 = identity ⇒ device False).
int sm_r1_prep_hg(int64_t n,
                  const u64* e, const u64* rr, const u64* ss, const u64* pub,
                  int32_t* g_idx,      // (16, n): row 2j = t_hi window j,
                                       //          row 2j+1 = t_lo window j
                  u8* q_digits,        // (32, n): 4-bit |v1| digits MSB-first
                  u16* q_x, u16* q_y,  // (n, 16) sign-adjusted Q
                  u16* xd_limbs,       // (n, 16) x([v2]R); 0 when hg_ok = 0
                  u8* hg_ok, u8* precheck,
                  u64* work)           // scratch: 5*n*4 words
{
    const Ctx& C = ctx();
    const Mod* N = &C.r1n;
    const Mod* P = &C.r1p;
    u64* sw = work;
    u64* scratch = work + 4 * n;
    u64* em = work + 8 * n;
    u64* Xd = work + 12 * n;
    u64* Zd = work + 16 * n;
    for (int64_t i = 0; i < n; ++i) {
        const u64* r4 = rr + 4 * i;
        const u64* s4 = ss + 4 * i;
        const u64* x4 = pub + 8 * i;
        const u64* y4 = pub + 8 * i + 4;
        bool ok = !mp_is_zero(r4, 4) && mp_cmp(r4, N->m, 4) < 0
               && !mp_is_zero(s4, 4) && mp_cmp(s4, N->m, 4) < 0
               && on_curve(P, x4, y4, R1_B, true);
        precheck[i] = ok ? 1 : 0;
        if (ok) {
            mp_copy(sw + 4 * i, s4, 4);
            const u64* e4 = e + 4 * i;
            if (mp_cmp(e4, N->m, 4) >= 0) mp_sub(em + 4 * i, e4, N->m, 4);
            else mp_copy(em + 4 * i, e4, 4);
        } else {
            u64 one[4] = {1, 0, 0, 0};
            mp_copy(sw + 4 * i, one, 4);
            mp_zero(em + 4 * i, 4);
        }
    }
    batch_inv(N, sw, n, scratch);
    const u64 R1GX[4] = {0xF4A13945D898C296ull, 0x77037D812DEB33A0ull,
                         0xF8BCE6E563A440F2ull, 0x6B17D1F2E12C4247ull};
    const u64 R1GY[4] = {0xCBB6406837BF51F5ull, 0x2BCE33576B315ECEull,
                         0x8EE7EB4A7C0F9E16ull, 0x4FE342E2FE1A7F9Bull};
    for (int64_t i = 0; i < n; ++i) {
        bool ok = precheck[i];
        u64 u1[4], u2[4];
        if (ok) {
            mod_mul(N, em + 4 * i, sw + 4 * i, u1);
            u64 rmod[4];
            mp_copy(rmod, rr + 4 * i, 4);
            mod_mul(N, rmod, sw + 4 * i, u2);
        } else {
            mp_zero(u1, 4);
            mp_zero(u2, 4);
        }
        u64 qx[4], qy[4];
        if (ok) {
            mp_copy(qx, pub + 8 * i, 4);
            mp_copy(qy, pub + 8 * i + 4, 4);
        } else {
            mp_copy(qx, R1GX, 4);
            mp_copy(qy, R1GY, 4);
        }
        bool hg = true, neg1 = false;
        u64 v1[2] = {0, 0}, v2[2] = {0, 0}, tt[4] = {0, 0, 0, 0}, ry[4];
        if (ok) {
            hg = r1_halfgcd(u2, &neg1, v1, v2);
            if (hg) {
                u64 v24[4] = {v2[0], v2[1], 0, 0};
                mod_mul(N, v24, u1, tt);       // t = v2*u1 mod n
            }
            u64 rn[4];
            u64 carry = mp_add(rn, rr + 4 * i, N->m, 4);
            if (!carry && mp_cmp(rn, P->m, 4) < 0) hg = false;
            if (hg) {
                // decompress r: y^2 = r^3 - 3r + b (r < n < p is canonical)
                const u64* r4 = rr + 4 * i;
                u64 r2[4], r3[4], z[4];
                r1p_mul(r4, r4, r2);
                r1p_mul(r2, r4, r3);
                r1p_sub(r3, r4, z);
                r1p_sub(z, r4, z);
                r1p_sub(z, r4, z);
                r1p_add(z, R1_B, z);
                if (!r1p_sqrt(z, ry)) hg = false;
            }
        }
        bool emit = ok && hg;
        if (emit) {
            u64 xD[4], zD[4];
            r1_mul_point(rr + 4 * i, ry, v2, xD, zD);
            mp_copy(Xd + 4 * i, xD, 4);
            mp_copy(Zd + 4 * i, zD, 4);
        } else {
            mp_zero(Xd + 4 * i, 4);
            mp_zero(Zd + 4 * i, 4);
            Zd[4 * i] = 1;
        }
        hg_ok[i] = hg ? 1 : 0;
        for (int t = 0; t < 8; ++t) {
            int shift = 16 * (7 - t);
            u32 whi = emit
                ? (u32)((tt[2 + shift / 64] >> (shift % 64)) & 0xFFFF) : 0;
            u32 wlo = emit
                ? (u32)((tt[shift / 64] >> (shift % 64)) & 0xFFFF) : 0;
            g_idx[(int64_t)(2 * t) * n + i] = (int32_t)whi;
            g_idx[(int64_t)(2 * t + 1) * n + i] = (int32_t)wlo;
        }
        for (int t = 0; t < 32; ++t) {
            int shift = 4 * (31 - t);
            q_digits[(int64_t)t * n + i] = emit
                ? (u8)((v1[shift / 64] >> (shift % 64)) & 0xF) : 0;
        }
        u64 py[4];
        mp_copy(py, qy, 4);
        if (emit && neg1) mod_neg(P, qy, py);
        write_limbs(q_x + 16 * i, qx);
        write_limbs(q_y + 16 * i, py);
    }
    // one batch inversion for every item's affine x([v2]R)
    batch_inv(P, Zd, n, scratch);
    for (int64_t i = 0; i < n; ++i) {
        u64 zi2[4], xa[4];
        r1p_mul(Zd + 4 * i, Zd + 4 * i, zi2);
        r1p_mul(Xd + 4 * i, zi2, xa);
        write_limbs(xd_limbs + 16 * i, xa);
    }
    return 0;
}

// The whole Ed25519 split-k prep of a batch in one call: what
// ops/ed25519.py prepare_batch_split returns, for all `cap` rows, from the
// rows' wire bytes.  Row i < n: signature sigs[sum(sig_len[..i]) ..) of
// sig_len[i] bytes (anything but 64: refused for that row alone, its wire
// words all zero), message likewise of ANY length, and the slot which[i]
// of its signer in a table of the batch's DISTINCT signers: the key's 32
// bytes, its cached (-A, -A') limb rows, and whether it decoded.  Written:
//   r_packed  the wire y, sign bit left in limb 15
//   rows      the signer's rows (sub_row where the length or the key was
//             refused: such a row does not hash, k := 0)
//   bb_idx    w=16 windows of s_lo (rows 0..7) and s_hi (8..15), MSB-first
//   a_packed  klo | khi<<2 2-bit digits of k = SHA-512(R || A || M) mod L
//   precheck  length, key, y < p and s < L all passed (s, k := 0 where
//             s >= L)
// Rows n .. cap-1 repeat row n-1 (the kernels' padding).  Returns -1 on bad
// sizes, -2 on a slot out of range, -3 on lengths that overrun a buffer.
int sm_ed_prep_words(int64_t n, int64_t cap,
                     const u8* sigs, int64_t sigs_len, const int64_t* sig_len,
                     const u8* msgs, int64_t msgs_len, const int64_t* msg_len,
                     const int32_t* which,      // (n,)
                     int64_t n_slots,
                     const u8* slot_keys,       // (n_slots, 32)
                     const u16* slot_rows,      // (n_slots, 6, 16)
                     const u8* slot_ok,         // (n_slots,)
                     const u16* sub_row,        // (6, 16)
                     int32_t* bb_idx,           // (16, cap)
                     u8* a_packed,              // (64, cap)
                     u16* rows,                 // (cap, 6, 16)
                     u16* r_packed,             // (cap, 16)
                     u8* precheck)              // (cap,)
{
    if (n < 0 || cap < n || (n == 0 && cap > 0) || n_slots < 0) return -1;
    const Mod* L = &ctx().edl;
    int64_t sig_at = 0, msg_at = 0;
    for (int64_t i = 0; i < n; ++i) {
        const int64_t sl = sig_len[i], ml = msg_len[i];
        if (sl < 0 || ml < 0 || sl > sigs_len - sig_at
                || ml > msgs_len - msg_at) return -3;
        if (which[i] < 0 || which[i] >= n_slots) return -2;
        const u8* sig = sigs + sig_at;
        const u8* msg = msgs + msg_at;
        sig_at += sl;
        msg_at += ml;
        const int64_t slot = which[i];
        const bool sig_ok = sl == 64;
        const bool keyed = sig_ok && slot_ok[slot];
        u16* rp = r_packed + 16 * i;
        u64 s[4] = {0, 0, 0, 0}, k[4] = {0, 0, 0, 0};
        bool ge_p = false, s_ok = true;    // a refused length reads s = 0
        if (sig_ok) {
            std::memcpy(rp, sig, 32);
            std::memcpy(s, sig + 32, 32);
            // non-canonical y (>= p = 2^255-19), the sign bit masked off
            ge_p = rp[0] >= 0xFFED && (rp[15] & 0x7FFF) == 0x7FFF;
            for (int j = 1; ge_p && j < 15; ++j) ge_p = rp[j] == 0xFFFF;
            s_ok = mp_cmp(s, L->m, 4) < 0;
            if (!s_ok) mp_zero(s, 4);
        } else {
            std::memset(rp, 0, 32);
        }
        std::memcpy(rows + 96 * i, keyed ? slot_rows + 96 * slot : sub_row,
                    192);
        if (keyed && s_ok) {
            Sha512 S;
            u8 dig[64];
            u64 h[8];
            sha512_init(&S);
            sha512_update(&S, sig, 32);
            sha512_update(&S, slot_keys + 32 * slot, 32);
            sha512_update(&S, msg, (size_t)ml);
            sha512_final(&S, dig);
            std::memcpy(h, dig, 64);        // RFC 8032: the digest as LE
            mod_red(L, h, k);
        }
        precheck[i] = (keyed && !ge_p && s_ok) ? 1 : 0;
        ed_split_windows(s, k, cap, i, bb_idx, bb_idx + 8 * cap, a_packed);
    }
    for (int64_t i = n; i < cap; ++i) {
        std::memcpy(r_packed + 16 * i, r_packed + 16 * (n - 1), 32);
        std::memcpy(rows + 96 * i, rows + 96 * (n - 1), 192);
        precheck[i] = precheck[n - 1];
    }
    if (cap > n) {
        for (int t = 0; t < 16; ++t) {
            int32_t* row = bb_idx + (int64_t)t * cap;
            for (int64_t i = n; i < cap; ++i) row[i] = row[n - 1];
        }
        for (int t = 0; t < 64; ++t) {
            u8* row = a_packed + (int64_t)t * cap;
            std::memset(row + n, row[n - 1], (size_t)(cap - n));
        }
    }
    return 0;
}

}  // extern "C"
