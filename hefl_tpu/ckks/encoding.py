"""CKKS encode/decode between float weight vectors and RNS residue polynomials.

This is the analog of the reference's Pyfhel fractional encoder
(`HE.encryptFrac` / `HE.decryptFrac`, /root/reference/FLPyfhelin.py:217,295),
which packed ONE scalar per ciphertext (64i.32f fixed point). Here a whole
N-coefficient block of weights is packed per polynomial ("coefficient
packing"): encode is round(w * scale) reduced mod each RNS prime, decode is
mixed-radix CRT reconstruction divided by the tracked scale.

Coefficient packing (not slot/canonical-embedding packing) is the right
choice for encrypted FedAvg: the only homomorphic ops are ct+ct and
ct × plaintext-scalar (SURVEY.md §2.10), both of which act coefficient-wise,
so no FFT precision loss enters the pipeline and every coefficient is an
independent fixed-point weight.

Two decode paths:
  * `decode` — jittable float32 mixed-radix CRT, runs on TPU inside the FL
    loop (error ~2^-19 relative, far below SGD noise).
  * `decode_exact` — host-side exact Python-bignum CRT, the gold path used by
    tests and final model export at the trust boundary.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from hefl_tpu.ckks import modular
from hefl_tpu.ckks.ntt import NTTContext
from hefl_tpu.ckks.primes import host_to_mont


# The scaled value v = round(w*scale) is carried as a two-part split
# v = hi * 2**_SPLIT_BITS + lo with hi, lo independent int32s, reduced mod
# each RNS prime with one Montgomery multiply — so the encode envelope is
# set by the int32 range of `hi`, not of v itself. The wall backs off 256
# from 2**31 for the float32 rounding slop at that magnitude
# (2**31 * 2**-24 = 128). At the default scale 2**30 this admits
# |w| < ~2**16 (vs |w| < 2.0 for a single-int32 encode); for |w| < 2**9 the
# split is bit-exact (see `encode`), beyond that encode precision degrades
# like float32 itself. This matches the reference encoder's contract of a
# wide integer envelope with fixed fractional precision (64i.32f,
# /root/reference/FLPyfhelin.py:217).
_SPLIT_BITS = 15
_SPLIT = float(1 << _SPLIT_BITS)
_HI_BOUND = float(2**31 - 256)
ENCODE_BOUND = _HI_BOUND * _SPLIT


def encode(ctx: NTTContext, values: jnp.ndarray, scale: float) -> jnp.ndarray:
    """float[..., N] -> canonical residues uint32[..., L, N] (coefficient domain).

    v = round(values*scale) is computed as hi = round(w * scale/2**15)
    (clipped to +/-_HI_BOUND — saturation, not int32 wraparound, exactly
    like the reference's fixed-point envelope) plus lo = round((w*scale/2**15
    - hi) * 2**15). For |w*scale| < 2**39 every step is exact in float32
    (products by powers of two are exact; the residual after subtracting the
    rounded hi is a representable multiple of the operand ulp), so the split
    reproduces round(w*scale) up to the same +/-0.5 quantization as a direct
    rounding. Beyond 2**39 the value is already coarser than 2**15 ulps in
    float32, so lo is exactly 0 and precision degrades gracefully with the
    float32 input itself. `encode_overflow_count` reports saturation.

    Exactness of the hi/lo recombination assumes `scale` is a power of two
    (the default 2**30 and every config in the repo); other scales encode
    with one extra half-ulp of rounding slop.
    """
    v = values.astype(jnp.float32)
    s_hi = jnp.float32(scale / _SPLIT)
    hi_f = jnp.clip(jnp.round(v * s_hi), -_HI_BOUND, _HI_BOUND)
    r = v * s_hi - hi_f                       # exact where |v*s_hi| < 2**24
    lo = jnp.clip(jnp.round(r * _SPLIT), -_SPLIT, _SPLIT).astype(jnp.int32)
    hi = hi_f.astype(jnp.int32)
    p = jnp.asarray(ctx.p)                    # uint32[L, 1]
    # numpy-remainder semantics (sign follows divisor -> canonical residues)
    # via shift-multiply Barrett: bitwise-identical to `jnp.remainder` but
    # with no hardware divide per element (ISSUE 4). |lo| <= 2**15 < p needs
    # only the conditional add; |hi| can reach 2**31 and takes the full
    # signed Barrett.
    hi_res = modular.barrett_mod_signed(hi[..., None, :], p)
    lo_l = lo[..., None, :]
    lo_res = jnp.where(lo_l < 0, lo_l + p.astype(jnp.int32), lo_l).astype(jnp.uint32)
    shift_mont = jnp.asarray(
        [[host_to_mont(1 << _SPLIT_BITS, int(pi))] for pi in np.asarray(ctx.p)[:, 0]],
        dtype=jnp.uint32,
    )
    hi_shift = modular.mont_mul(hi_res, shift_mont, p, jnp.asarray(ctx.pinv_neg))
    return modular.add_mod(hi_shift, lo_res, p)


def encode_packed(ctx: NTTContext, hi: jnp.ndarray, lo: jnp.ndarray) -> jnp.ndarray:
    """Exact integer encode of v = hi * 2**31 + lo (hi, lo uint32 < 2**31)
    -> canonical residues uint32[..., L, N].

    The packed-quantized path (ckks.quantize) carries up-to-62-bit bit-field
    integers; routing them through the float `encode` would shear off
    everything past the 24-bit float32 mantissa, so this encode never touches
    floats: residues are (hi mod p) * (2**31 mod p) + (lo mod p), all
    division-free modular integer ops — bit-exact for the full range.
    """
    p = jnp.asarray(ctx.p)                    # uint32[L, 1]
    mu = modular.barrett_mu(p)
    hi_res = modular.barrett_mod(hi[..., None, :], p, mu)
    lo_res = modular.barrett_mod(lo[..., None, :], p, mu)
    shift_mont = jnp.asarray(
        [
            [host_to_mont((1 << 31) % int(pi), int(pi))]
            for pi in np.asarray(ctx.p)[:, 0]
        ],
        dtype=jnp.uint32,
    )
    hi_shift = modular.mont_mul(hi_res, shift_mont, p, jnp.asarray(ctx.pinv_neg))
    return modular.add_mod(hi_shift, lo_res, p)


def decode_int_center(ctx: NTTContext, residues) -> np.ndarray:
    """Residues uint32[..., L, N] -> the centered CRT value as EXACT int64.

    The packed-quantized decode needs the integer bit-for-bit (its payload
    is bit fields), which rules out both the float32 jittable `decode` and
    `decode_exact`'s float64 output (exact only to 2**53). Digits come from
    the same exact `_mixed_radix_digits` extraction; the recombination runs
    host-side in uint64 two's-complement — multiplication/addition wrap mod
    2**64, and since the true centered value of any packed payload satisfies
    |v| < 2**62 (quantize.MAX_PACKED_BITS), the wrapped result IS the value.
    Values outside +/-2**63 would alias silently, so callers must respect
    the MAX_PACKED_BITS ceiling (`interleave_fields` enforces it on the
    encode side).
    """
    digits = _mixed_radix_digits(ctx, jnp.asarray(residues))
    p = np.asarray(ctx.p)[:, 0]
    acc = None
    prefix = 1
    for i, d in enumerate(digits):
        c = np.uint64(prefix & 0xFFFFFFFFFFFFFFFF)
        term = np.asarray(d).astype(np.int64).astype(np.uint64) * c
        acc = term if acc is None else acc + term
        prefix *= int(p[i])
    return acc.astype(np.int64)


def encode_overflow_count(values: jnp.ndarray, scale: float) -> jnp.ndarray:
    """How many of `values` would saturate in `encode` at this scale
    (jittable diagnostic; 0 on a healthy pipeline)."""
    scaled = jnp.abs(values.astype(jnp.float32)) * jnp.float32(scale)
    return jnp.sum(scaled > ENCODE_BOUND)


def _mixed_radix_digits(ctx: NTTContext, residues: jnp.ndarray):
    """Centered mixed-radix digits of the CRT value: v = Σ_i d_i * (p0..p_{i-1}).

    Every digit is centered (|d_i| <= p_i/2, int32) with the borrow folded
    into the next digit's computation. Centering all digits — not just the
    top one — is what keeps the caller's float32 recombination accurate: for
    a value v that is small relative to q, canonical digits would be
    full-sized with catastrophic cancellation between terms, while centered
    digits shrink with v itself. Digit extraction is exact uint32 modular
    arithmetic; only the recombination uses floats.
    """
    p = np.asarray(ctx.p)[:, 0].astype(object)  # exact python ints
    num_l = residues.shape[-2]

    digits: list[jnp.ndarray] = []
    for i in range(num_l):
        pi = int(p[i])
        pi_u = jnp.uint32(pi)
        pinv_i = jnp.uint32(int(ctx.pinv_neg[i, 0]))
        # acc = (x_i - Σ_{j<i} d_j * prefix_j) * prefix_i^{-1} mod p_i
        acc = residues[..., i, :]
        run = 1
        for j, d in enumerate(digits):
            coeff_mont = jnp.uint32(host_to_mont(run, pi))
            # d_j is a centered int32 with |d_j| <= p_j/2 < p_i... not quite:
            # |d_j| <= p_j/2 where p_j can exceed p_i, so one conditional add
            # may leave a residue of p_i..p_j/2. Use the signed Barrett —
            # still division-free, exact for the full int32 range.
            d_res = modular.barrett_mod_signed(d, jnp.uint32(pi))
            term = modular.mont_mul(d_res, coeff_mont, pi_u, pinv_i)
            acc = modular.sub_mod(acc, term, pi_u)
            run *= int(p[j])
        if i > 0:
            inv_mont = jnp.uint32(host_to_mont(pow(run % pi, pi - 2, pi), pi))
            acc = modular.mont_mul(acc, inv_mont, pi_u, pinv_i)
        digits.append(modular.to_signed_center(acc, pi_u))
    return digits


def decode_coefficients(ctx: NTTContext, scale: float) -> np.ndarray:
    """The float decode's L factors, float32[L]: digit i is worth
    `p_0..p_{i-1} / scale`, the product formed on the host in float64 and
    rounded to float32 once. `decode_with_coefficients` takes them as data,
    not as constants: a program compiled around it serves every scale (a
    round with another surviving-client count) unchanged."""
    p = np.asarray(ctx.p)[:, 0]
    inv_scale = 1.0 / float(scale)
    coeffs = [inv_scale]
    radix = 1.0
    for i in range(1, len(p)):
        radix *= float(int(p[i - 1]))
        coeffs.append(radix * inv_scale)
    return np.asarray(coeffs, dtype=np.float32)


def decode_with_coefficients(
    ctx: NTTContext, residues: jnp.ndarray, coeffs: jnp.ndarray
) -> jnp.ndarray:
    """Canonical residues uint32[..., L, N] and `decode_coefficients`'
    float32[L] -> float32[..., N]: exact digits, then the float32
    recombination, digit 0 first. The jittable body of `decode`, and of
    the owner's compiled decode (`fl.secure._decode_unpack`)."""
    digits = _mixed_radix_digits(ctx, residues)
    out = digits[0].astype(jnp.float32) * coeffs[0]
    for i in range(1, len(digits)):
        out = out + digits[i].astype(jnp.float32) * coeffs[i]
    return out


def decode(ctx: NTTContext, residues: jnp.ndarray, scale: float) -> jnp.ndarray:
    """Canonical residues uint32[..., L, N] -> float32[..., N] (jittable).

    Mixed-radix CRT with float32 recombination: exact for |v| < 2**24*p0 and
    within ~2**-19 relative error at our full q (3x27-bit primes) — an order
    of magnitude below the SGD noise floor, and far below the reference's
    per-weight fixed-point error budget.
    """
    return decode_with_coefficients(
        ctx, residues, decode_coefficients(ctx, scale)
    )


def decode_exact(
    ctx: NTTContext, residues: np.ndarray, scale: float, prefer_native: bool = True
) -> np.ndarray:
    """Exact host-side decode; float64 output.

    Used at the trust boundary (owner decrypt -> model export) and as the
    gold reference in tests, mirroring how the reference's final
    `decrypt_import_weights` step is a host operation
    (/root/reference/FLPyfhelin.py:263-281). Dispatches to the C++
    `__int128` Garner CRT (hefl_tpu.native — the SEAL-bignum analog) when
    available; the Python object-array bignum path below is the
    always-available fallback and the gold model the native code is tested
    against (`prefer_native=False` forces it).
    """
    res = np.asarray(residues)
    if prefer_native:
        from hefl_tpu import native

        fast = native.crt_decode_center(res, np.asarray(ctx.p)[:, 0], scale)
        if fast is not None:
            return fast
    p = [int(x) for x in np.asarray(ctx.p)[:, 0]]
    q = 1
    for pi in p:
        q *= pi
    # Garner CRT with python ints over an object array.
    v = res[..., 0, :].astype(object)
    prefix = 1
    for i in range(1, len(p)):
        prefix *= p[i - 1]
        inv = pow(prefix % p[i], p[i] - 2, p[i])
        diff = (res[..., i, :].astype(object) - v) % p[i]
        t = (diff * inv) % p[i]
        v = v + t * prefix
    # center mod q
    v = np.where(v > q // 2, v - q, v)
    return (v / float(scale)).astype(np.float64)


# ---------------------------------------------------------------------------
# Shaped jaxpr probes (ISSUE 8): the exact-integer encode/decode regions,
# exported for analysis.lint — no rem/div (barrett_mu's [L, 1]
# constant-table divide is the one allowlisted exception), no float
# contamination (a single f32 round-trip would shear packed bit fields).
# ---------------------------------------------------------------------------


def exact_int_probes() -> dict:
    import functools

    @functools.lru_cache(maxsize=1)
    def _ntt():
        from hefl_tpu.ckks.keys import CkksContext

        return CkksContext.create(n=256).ntt

    ntt = _ntt()
    num_l = int(np.asarray(ntt.p).shape[0])
    hi = jnp.zeros((2, ntt.n), jnp.uint32)
    lo = jnp.zeros((2, ntt.n), jnp.uint32)
    res = jnp.zeros((2, num_l, ntt.n), jnp.uint32)
    return {
        "ckks.encoding.encode_packed": (
            lambda h, l: encode_packed(ntt, h, l), (hi, lo)
        ),
        "ckks.encoding.mixed_radix_digits": (
            lambda r: tuple(_mixed_radix_digits(ntt, r)), (res,)
        ),
    }


# ---------------------------------------------------------------------------
# Slot (canonical-embedding) packing — host-side float64.
#
# Coefficient packing (above) is the FedAvg wire format: ct+ct and ct x
# scalar act coefficient-wise. Slot packing evaluates the plaintext
# polynomial at N/2 conjugate-paired primitive 2N-th roots of unity, so
# ct_mul (ops.ct_mul) acts ELEMENTWISE on slots — the semantics needed for
# encrypted inner products / inference. Slot j's root is zeta^{5^j mod 2N}
# (the standard Galois-orbit ordering: the automorphism X -> X^5 then
# cyclically shifts slots, which is what makes ops.ct_rotate a rotation;
# X -> X^{-1} is slot conjugation). Host-side float64 like `decode_exact`:
# packing choice is a trust-boundary encode step, not an inner-loop op.
# ---------------------------------------------------------------------------


def num_slots(ctx: NTTContext) -> int:
    return ctx.n // 2


def _orbit_positions(n: int) -> np.ndarray:
    """pos[j] = (5^j mod 2n - 1) / 2: index of slot j's root within the
    natural odd-exponent enumeration e^{i*pi*(2t+1)/n}, t = 0..n-1."""
    g = 1
    pos = np.empty(n // 2, dtype=np.int64)
    for j in range(n // 2):
        pos[j] = (g - 1) // 2
        g = (g * 5) % (2 * n)
    return pos


def encode_slots(ctx: NTTContext, z: np.ndarray, scale: float) -> np.ndarray:
    """complex (or real) [..., N/2] slot values -> residues uint32[..., L, N]."""
    n = ctx.n
    z = np.asarray(z, dtype=np.complex128)
    if z.shape[-1] != n // 2:
        raise ValueError(f"expected {n // 2} slots, got {z.shape[-1]}")
    pos = _orbit_positions(n)
    ev = np.zeros(z.shape[:-1] + (n,), dtype=np.complex128)
    ev[..., pos] = z                                           # root 5^j
    ev[..., n - 1 - pos] = np.conj(z)                          # root -5^j (conjugate)
    tw = np.exp(-1j * np.pi * np.arange(n) / n)                # zeta^{-n}
    a = np.real(np.fft.fft(ev, axis=-1) / n * tw)
    coeffs = np.round(a * scale).astype(np.int64)
    p = np.asarray(ctx.p)[:, 0].astype(np.int64)               # [L]
    res = np.mod(coeffs[..., None, :], p[:, None])
    return res.astype(np.uint32)


def encode_slots_const(ctx: NTTContext, c: float, scale: float) -> np.ndarray:
    """Constant-in-every-slot plaintext without the N-point FFT.

    The canonical embedding of a constant real vector is the constant
    polynomial (coefficient 0 = round(c·scale), all others 0), so the
    residues can be written directly in O(L) work instead of
    encode_slots' O(N log N) host FFT — the serving-path win for
    ct × scalar-constant multiplies and bias adds on the serving path.
    Matches encode_slots(ctx, full(N/2, c), scale) bit-exactly while
    |c|·scale stays below ~0.5/(1e-13·N) (the FFT path's float roundoff is
    ~1e-13·N·|c|·scale; past that threshold the two paths may round the
    integer coefficient differently — this direct path is the exact one).
    """
    p = np.asarray(ctx.p)[:, 0].astype(np.int64)
    coeff = int(round(c * scale))
    q = 1
    for pi in p:
        q *= int(pi)
    # Saturation guard (cheap, O(1)): a coefficient past q/2 wraps mod q and
    # decodes to an uncorrelated value with no error signal downstream.
    if 2 * abs(coeff) >= q:
        raise ValueError(
            f"encode_slots_const saturates: |round(c*scale)|={abs(coeff):.3e} "
            f"must stay below q/2~{q / 2:.3e}; lower the scale or add primes"
        )
    res = np.zeros((len(p), ctx.n), np.int64)
    res[:, 0] = np.mod(coeff, p)
    return res.astype(np.uint32)


def decode_slots(ctx: NTTContext, residues: np.ndarray, scale: float) -> np.ndarray:
    """Residues uint32[..., L, N] -> complex128 slot values [..., N/2]."""
    n = ctx.n
    coeffs = decode_exact(ctx, residues, 1.0)                  # exact integers
    tw = np.exp(1j * np.pi * np.arange(n) / n)                 # zeta^{n}
    ev = np.fft.ifft(coeffs * tw, axis=-1) * n
    return ev[..., _orbit_positions(n)] / float(scale)
