"""CKKS cipher operations: encrypt, decrypt, add, plaintext-multiply, rescale.

Covers the full homomorphic op surface the reference exercises
(SURVEY.md §2.7, §2.8, §2.10):

    reference (Pyfhel/SEAL, per scalar)         here (batched, on TPU)
    -------------------------------------       -------------------------------
    HE.encryptFrac(w[k])      :217              encrypt(ctx, pk, encode(w), key)
    HE.decryptFrac(ct)        :295              decode(decrypt(ctx, sk, ct))
    PyCtxt + PyCtxt           :381              ct_add
    PyCtxt * plaintext denom  :385              ct_mul_scalar (exact tracked scale)
    (relin keygen — dead code :357)             gen_relin_key + ct_mul, for real
                                                (beyond parity: the reference
                                                never multiplies ciphertexts)

Ciphertexts are `Ciphertext(c0, c1, scale)` with components
`uint32[..., L, N]` living permanently in evaluation (NTT) domain — addition,
scalar multiply, and the cross-client `psum` are all pointwise there, so the
aggregation path never runs a transform.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from hefl_tpu.ckks import modular
from hefl_tpu.ckks.keys import (
    CkksContext,
    GaloisKey,
    PublicKey,
    RelinKey,
    SecretKey,
    sample_gaussian_residues,
    sample_ternary_residues,
)
from hefl_tpu.ckks.ntt import ntt_forward, ntt_inverse, to_mont
from hefl_tpu.ckks.primes import host_to_mont


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Ciphertext:
    """RLWE pair in eval domain. Decrypt(c0 + c1*s) recovers m*scale + noise.

    `scale` is static metadata (python float): the exact cumulative integer
    factor the plaintext has been multiplied by. Tracking the *exact* applied
    multiplier (not an idealized Delta^2) means plaintext-scalar multiplies
    introduce zero scale-quantization error.
    """

    c0: jax.Array
    c1: jax.Array
    scale: float = dataclasses.field(metadata=dict(static=True))


def encrypt_samples(
    ctx: CkksContext, key: jax.Array, batch: tuple = ()
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The (u, e0, e1) coefficient-domain randomness of one encrypt call.

    Split out of `encrypt` so callers with a pre-stacked ciphertext batch
    (fl.secure.encrypt_stack) can sample per client with the HISTORICAL key
    derivation (bitwise-identical streams) and then run ONE fused core call
    over the whole stack instead of a vmap of kernels.
    """
    k_u, k_e0, k_e1 = jax.random.split(key, 3)
    return (
        sample_ternary_residues(ctx, k_u, batch),
        sample_gaussian_residues(ctx, k_e0, batch),
        sample_gaussian_residues(ctx, k_e1, batch),
    )


def _encrypt_core_xla(
    ctx: CkksContext,
    m_res: jax.Array,
    u: jax.Array,
    e0: jax.Array,
    e1: jax.Array,
    b_mont: jax.Array,
    a_mont: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """The deterministic encrypt core on the XLA graph path (the bit-exact
    semantics reference the fused Pallas kernel is tested against).

    The four forward transforms ride ONE stacked NTT call — identical math
    and bitwise-identical residues to four separate calls, but a quarter of
    the stage-graph ops for XLA to schedule."""
    ntt = ctx.ntt
    p = jnp.asarray(ntt.p)
    pinv = jnp.asarray(ntt.pinv_neg)
    u_eval, e0_eval, e1_eval, m_eval = ntt_forward(
        ntt, jnp.stack([u, e0, e1, m_res])
    )
    c0 = modular.add_mod(
        modular.add_mod(modular.mont_mul(u_eval, b_mont, p, pinv), e0_eval, p),
        m_eval,
        p,
    )
    c1 = modular.add_mod(modular.mont_mul(u_eval, a_mont, p, pinv), e1_eval, p)
    return c0, c1


def encrypt_core(
    ctx: CkksContext,
    pk: PublicKey,
    m_res: jax.Array,
    u: jax.Array,
    e0: jax.Array,
    e1: jax.Array,
    backend: str | None = None,
) -> Ciphertext:
    """Deterministic encrypt of sampled randomness, backend-dispatched.

    ct = (b*u + e0 + m, a*u + e1), all eval-domain. The fused Pallas
    backend runs the whole thing (4 NTTs + pointwise key combination) as
    one Mosaic dispatch per (prime, ciphertext) row; XLA is the reference.
    Selection: `backend` override > HEFL_HE env > auto (ckks.backend).
    """
    from hefl_tpu.ckks.backend import resolve_he_backend
    from hefl_tpu.obs import scopes as obs_scopes

    # Phase scope (obs): both backends' encrypt ops (the 4 NTTs + pointwise
    # key combination, or the one fused Pallas dispatch) trace as
    # hefl.encrypt.
    with jax.named_scope(obs_scopes.ENCRYPT):
        if resolve_he_backend(ctx, backend) == "pallas":
            from hefl_tpu.ckks import pallas_ntt

            c0, c1 = pallas_ntt.encrypt_fused_pallas(
                ctx.ntt, m_res, u, e0, e1, pk.b_mont, pk.a_mont
            )
        else:
            c0, c1 = _encrypt_core_xla(
                ctx, m_res, u, e0, e1, pk.b_mont, pk.a_mont
            )
    return Ciphertext(c0=c0, c1=c1, scale=ctx.scale)


@partial(jax.jit, static_argnums=0)
def encrypt(
    ctx: CkksContext, pk: PublicKey, m_res: jax.Array, key: jax.Array
) -> Ciphertext:
    """Public-key encrypt coefficient-domain residues `m_res` [..., L, N].

    ct = (b*u + e0 + m, a*u + e1), all eval-domain. Batched over leading dims
    of `m_res` with independent (u, e0, e1) per ciphertext.
    """
    batch = m_res.shape[:-2]
    u, e0, e1 = encrypt_samples(ctx, key, batch)
    return encrypt_core(ctx, pk, m_res, u, e0, e1)


@partial(jax.jit, static_argnums=0)
def decrypt(ctx: CkksContext, sk: SecretKey, ct: Ciphertext) -> jax.Array:
    """-> coefficient-domain residues uint32[..., L, N] of m*scale + noise.

    Backend-dispatched like `encrypt_core`: the fused Pallas kernel runs
    c0 + c1*s and the inverse NTT as one dispatch; XLA is the reference.
    """
    from hefl_tpu.ckks.backend import resolve_he_backend
    from hefl_tpu.obs import scopes as obs_scopes

    with jax.named_scope(obs_scopes.DECRYPT):
        if resolve_he_backend(ctx) == "pallas":
            from hefl_tpu.ckks import pallas_ntt

            return pallas_ntt.decrypt_fused_pallas(
                ctx.ntt, ct.c0, ct.c1, sk.s_mont
            )
        p = jnp.asarray(ctx.ntt.p)
        d_eval = modular.add_mod(
            ct.c0,
            modular.mont_mul(ct.c1, sk.s_mont, p, jnp.asarray(ctx.ntt.pinv_neg)),
            p,
        )
        return ntt_inverse(ctx.ntt, d_eval)


def ct_add(ctx: CkksContext, a: Ciphertext, b: Ciphertext) -> Ciphertext:
    """Homomorphic addition (the server op at FLPyfhelin.py:381)."""
    if a.scale != b.scale:
        raise ValueError(f"scale mismatch: {a.scale} vs {b.scale}")
    p = jnp.asarray(ctx.ntt.p)
    return Ciphertext(
        c0=modular.add_mod(a.c0, b.c0, p),
        c1=modular.add_mod(a.c1, b.c1, p),
        scale=a.scale,
    )


def ct_add_plain(ctx: CkksContext, a: Ciphertext, m_res: jax.Array) -> Ciphertext:
    """ct + plaintext (coefficient-domain residues encoded at the same scale)."""
    p = jnp.asarray(ctx.ntt.p)
    return Ciphertext(
        c0=modular.add_mod(a.c0, ntt_forward(ctx.ntt, m_res), p),
        c1=a.c1,
        scale=a.scale,
    )


def _scalar_mont(ctx: CkksContext, k: int) -> np.ndarray:
    """Montgomery lift of a small plaintext integer per prime -> uint32[L, 1]."""
    p = np.asarray(ctx.ntt.p)[:, 0]
    return np.array([[host_to_mont(int(k), int(pi))] for pi in p], dtype=np.uint32)


def ct_mul_scalar(ctx: CkksContext, a: Ciphertext, k: int) -> Ciphertext:
    """ct * integer plaintext scalar; the FedAvg 1/N step.

    The reference multiplies by the *float* 1/N under BFV's fractional
    encoder (FLPyfhelin.py:385). Here the scalar is the integer k and the
    ciphertext's tracked scale absorbs it exactly: decode later divides by
    scale*k, so representing 1/N costs no precision at all.
    """
    k_mont = jnp.asarray(_scalar_mont(ctx, k))
    p = jnp.asarray(ctx.ntt.p)
    pinv = jnp.asarray(ctx.ntt.pinv_neg)
    return Ciphertext(
        c0=modular.mont_mul(a.c0, k_mont, p, pinv),
        c1=modular.mont_mul(a.c1, k_mont, p, pinv),
        scale=a.scale * k,
    )


def ct_mul_plain_poly(ctx: CkksContext, a: Ciphertext, m_res: jax.Array, pt_scale: float) -> Ciphertext:
    """ct * plaintext polynomial (coefficient-domain residues, encoded at pt_scale)."""
    m_mont = to_mont(ctx.ntt, ntt_forward(ctx.ntt, m_res))
    p = jnp.asarray(ctx.ntt.p)
    pinv = jnp.asarray(ctx.ntt.pinv_neg)
    return Ciphertext(
        c0=modular.mont_mul(a.c0, m_mont, p, pinv),
        c1=modular.mont_mul(a.c1, m_mont, p, pinv),
        scale=a.scale * pt_scale,
    )


def _keyswitch_coeff_xla(
    ctx: CkksContext, coeff: jax.Array, b_mont: jax.Array, a_mont: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Gadget key-switch of a COEFFICIENT-domain polynomial (XLA graph
    path — the bit-exact semantics reference of the fused Pallas kernel).

    Decompose in the digit-refined CRT gadget base: each limb's canonical
    representative splits into base-2**w digits (w = ctx.ksk_digit_bits),
    every digit (< 2**w, trivially canonical under every prime) is lifted
    to all limbs, re-NTT'd, and inner-producted with the key components.
    Returns the eval-domain (c0, c1) correction pair. Noise ~2**w per
    component — the digit split is what keeps a key-switch on a fresh
    scale-2**30 ciphertext (rotations) far below the signal.
    """
    ntt = ctx.ntt
    p = jnp.asarray(ntt.p)
    pinv = jnp.asarray(ntt.pinv_neg)
    w = ctx.ksk_digit_bits
    d = ctx.ksk_num_digits
    mask = jnp.uint32((1 << w) - 1)
    digits = jnp.stack(
        [(coeff >> jnp.uint32(w * k)) & mask for k in range(d)], axis=-2
    )                                                             # [..., L, d, N]
    num_l = coeff.shape[-2]
    n = coeff.shape[-1]
    num_c = num_l * d + 1
    comp = digits.reshape(*coeff.shape[:-2], num_l * d, n)
    lifted = jnp.broadcast_to(
        comp[..., :, None, :], (*coeff.shape[:-2], num_l * d, num_l, n)
    )
    # Centered digits (zero-mean, see keys._center_correction_residues) plus
    # the constant-1 digit consuming the correction row: its eval-domain
    # representation is all-ones (a constant polynomial evaluates to itself).
    lifted = modular.sub_mod(lifted, jnp.uint32(1 << (w - 1)), p)
    d_eval = jnp.concatenate(
        [
            ntt_forward(ntt, lifted),
            jnp.ones((*coeff.shape[:-2], 1, num_l, n), jnp.uint32),
        ],
        axis=-3,
    )
    t0 = modular.mont_mul(d_eval, b_mont, p, pinv)                # [..., C, L, N]
    t1 = modular.mont_mul(d_eval, a_mont, p, pinv)
    c0, c1 = t0[..., 0, :, :], t1[..., 0, :, :]
    for i in range(1, num_c):                                     # modular tree-sum
        c0 = modular.add_mod(c0, t0[..., i, :, :], p)
        c1 = modular.add_mod(c1, t1[..., i, :, :], p)
    return c0, c1


def _keyswitch_coeff(
    ctx: CkksContext, coeff: jax.Array, b_mont: jax.Array, a_mont: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Backend-dispatched gadget key-switch (ISSUE 13).

    On the Pallas backend (`HEFL_HE`, resolved exactly like encrypt/decrypt
    via ckks.backend — env pin > auto, untileable rings always XLA) the
    whole decompose -> NTT -> digit x key accumulation chain runs as ONE
    Mosaic dispatch per (prime, ciphertext) row
    (`pallas_ntt.keyswitch_fused_pallas`); the XLA graph stays the
    bit-exact reference. Per-call (unstacked) key tensors only — callers
    that batch DIFFERENT keys per row (none today) keep the XLA path.
    """
    from hefl_tpu.ckks.backend import resolve_he_backend

    if b_mont.ndim == 3 and resolve_he_backend(ctx) == "pallas":
        from hefl_tpu.ckks import pallas_ntt

        return pallas_ntt.keyswitch_fused_pallas(
            ctx.ntt, coeff, b_mont, a_mont,
            digit_bits=ctx.ksk_digit_bits,
            num_digits=ctx.ksk_num_digits,
        )
    return _keyswitch_coeff_xla(ctx, coeff, b_mont, a_mont)


def _keyswitch_d2(ctx: CkksContext, d2: jax.Array, rlk: RelinKey) -> tuple[jax.Array, jax.Array]:
    """Key-switch the degree-2 component: d2*s^2 -> ct under s.

    On the Pallas backend the fused kernel runs the inverse NTT in-kernel
    too (`eval_input=True`) — relinearization is one dispatch end-to-end.
    """
    from hefl_tpu.ckks.backend import resolve_he_backend

    if rlk.b_mont.ndim == 3 and resolve_he_backend(ctx) == "pallas":
        from hefl_tpu.ckks import pallas_ntt

        return pallas_ntt.keyswitch_fused_pallas(
            ctx.ntt, d2, rlk.b_mont, rlk.a_mont,
            digit_bits=ctx.ksk_digit_bits,
            num_digits=ctx.ksk_num_digits,
            eval_input=True,
        )
    return _keyswitch_coeff_xla(
        ctx, ntt_inverse(ctx.ntt, d2), rlk.b_mont, rlk.a_mont
    )


def ct_apply_galois(ctx: CkksContext, a: Ciphertext, gk: GaloisKey) -> Ciphertext:
    """Apply the automorphism X -> X^g homomorphically and switch back to s.

    phi_g commutes with decryption up to the key change s -> phi_g(s):
    phi(c0) + phi(c1)*phi(s) = phi(m + noise). So: automorphism both
    components in the coefficient domain, then key-switch the phi(c1) part
    with the Galois key. No counterpart in the reference (SURVEY.md §2.10).
    """
    from hefl_tpu.ckks import galois

    ntt = ctx.ntt
    p = jnp.asarray(ntt.p)
    src, flip = galois.automorphism_tables(ctx.n, gk.g)
    pc0 = galois.apply_automorphism(ntt_inverse(ntt, a.c0), p, src, flip)
    pc1 = galois.apply_automorphism(ntt_inverse(ntt, a.c1), p, src, flip)
    k0, k1 = _keyswitch_coeff(ctx, pc1, gk.b_mont, gk.a_mont)
    return Ciphertext(
        c0=modular.add_mod(ntt_forward(ntt, pc0), k0, p),
        c1=k1,
        scale=a.scale,
    )


def ct_rotate(ctx: CkksContext, a: Ciphertext, gk: GaloisKey, steps: int) -> Ciphertext:
    """Cyclically LEFT-rotate the slot vector by `steps` (slot packing).

    `gk` must be the Galois key for `galois.galois_elt_rotation(n, steps)`;
    checked here so a mismatched key fails loudly instead of decrypting to
    a permutation the caller did not ask for.
    """
    from hefl_tpu.ckks import galois

    want = galois.galois_elt_rotation(ctx.n, steps)
    if gk.g != want:
        raise ValueError(f"galois key has g={gk.g}, rotation by {steps} needs g={want}")
    return ct_apply_galois(ctx, a, gk)


def ct_conjugate(ctx: CkksContext, a: Ciphertext, gk: GaloisKey) -> Ciphertext:
    """Conjugate every slot (slot packing)."""
    from hefl_tpu.ckks import galois

    want = galois.galois_elt_conjugation(ctx.n)
    if gk.g != want:
        raise ValueError(f"galois key has g={gk.g}, conjugation needs g={want}")
    return ct_apply_galois(ctx, a, gk)


# ---------------------------------------------------------------------------
# Hoisted rotations (ISSUE 18, Halevi-Shoup): decompose c1 ONCE, serve every
# baby-step rotation from the shared eval-domain digit tensors.
#
# The per-step gadget decomposition is the rotation hot path: base-2**w
# digit split + L*d forward NTTs, per rotation. But digit extraction acts on
# coefficients, so it does NOT commute with the SIGNED coefficient
# permutation phi_g — digits of phi_g(c1) are not a permutation of the
# digits of c1, and the centered-digit + correction-row decomposition
# `ct_rotate` uses (whose correction encrypts K*J*phi_g(s), J = all-ones)
# would need a correction digit R_g = phi_g(J)/J whose coefficients are
# full-range mod q, destroying the noise budget. The hoisted path therefore
# uses the UNCENTERED gadget identity sum_c digit_c(x)*g_c = x (exact, no
# correction row; digits in [0, 2**w) instead of centered — at most one bit
# more noise per component), which DOES hoist: phi_g is a ring automorphism
# fixing the integer gadget constants, so
#
#     sum_c phi_g(digit_c(c1)) * g_c = phi_g(c1),
#
# and in the eval domain phi_g is the pure permutation
# `galois.eval_permutation` — shared digits, one permutation per step.
# Pre-permuting the static KEY tensors with the inverse permutation moves
# even that gather out of the per-step inner product:
# sum_c perm(D_c)*B_c == perm(sum_c D_c * inv_perm(B_c)), so a step costs
# 2*(L*d) Montgomery multiplies + one output gather. Bitwise parity anchor:
# `hoisted_rotations_reference` runs the SAME decomposition step-by-step
# through the coefficient-domain automorphism + per-step NTTs (the XLA
# reference) — exact modular arithmetic makes the two bitwise-equal. The
# legacy `ct_rotate` loop (centered digits + correction row) computes the
# same rotation with a different decomposition, hence equal decrypted
# values but different noise bits — compared to tolerance, never bitwise.
# ---------------------------------------------------------------------------


def hoisted_digits(ctx: CkksContext, c1_coeff: jax.Array) -> jax.Array:
    """The shared decomposition: COEFFICIENT-domain c1 [..., L, N] ->
    uncentered eval-domain gadget digits uint32[..., L*d, L, N] (plain
    domain, canonical). This is the hoisted prefix — L*d forward NTTs paid
    ONCE for any number of rotation steps."""
    ntt = ctx.ntt
    w = ctx.ksk_digit_bits
    d = ctx.ksk_num_digits
    if (1 << w) > int(np.asarray(ntt.p)[:, 0].min()):
        raise ValueError(
            f"ksk_digit_bits={w} digits overflow the smallest prime; the "
            "uncentered hoisted decomposition needs 2**w <= min(p)"
        )
    mask = jnp.uint32((1 << w) - 1)
    num_l = c1_coeff.shape[-2]
    n = c1_coeff.shape[-1]
    digits = jnp.stack(
        [(c1_coeff >> jnp.uint32(w * k)) & mask for k in range(d)], axis=-2
    )                                                             # [..., L, d, N]
    comp = digits.reshape(*c1_coeff.shape[:-2], num_l * d, n)
    lifted = jnp.broadcast_to(
        comp[..., :, None, :], (*c1_coeff.shape[:-2], num_l * d, num_l, n)
    )
    return ntt_forward(ntt, lifted)


def hoisted_rotation_tables(ctx: CkksContext, gks: dict, steps):
    """Hoisted-plan tables for a rotation step sequence -> (perm i32[S, N],
    b_mont u32[S, L*d, L, N], a_mont u32[S, L*d, L, N]).

    Per step: the eval-domain automorphism permutation, and the Galois key
    rows PRE-GATHERED through the inverse permutation (static host work) —
    the correction row is dropped (the uncentered gadget identity is exact
    without it). Built once per scorer; validation (key presence, galois
    element match) all happens here, like `stack_rotation_steps`."""
    from hefl_tpu.ckks import galois

    steps = [int(s) for s in steps]
    num_r = ctx.num_primes * ctx.ksk_num_digits
    if not steps:
        zk = jnp.zeros((0, num_r, ctx.num_primes, ctx.n), jnp.uint32)
        return jnp.zeros((0, ctx.n), jnp.int32), zk, zk
    missing = [s for s in steps if s not in gks]
    if missing:
        raise ValueError(f"rotation keys missing for steps {missing}")
    perms, bks, aks = [], [], []
    for s in steps:
        want = galois.galois_elt_rotation(ctx.n, s)
        if gks[s].g != want:
            raise ValueError(
                f"galois key for step {s} has g={gks[s].g}, rotation needs "
                f"g={want}"
            )
        perm, inv_perm = galois.eval_permutation(ctx.ntt, want)
        perms.append(perm)
        inv = jnp.asarray(inv_perm)
        bks.append(jnp.take(gks[s].b_mont[:num_r], inv, axis=-1))
        aks.append(jnp.take(gks[s].a_mont[:num_r], inv, axis=-1))
    return jnp.asarray(np.stack(perms)), jnp.stack(bks), jnp.stack(aks)


def _hoisted_products_xla(
    ctx: CkksContext, c0: jax.Array, d_eval: jax.Array,
    b_mont: jax.Array, a_mont: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Per-step inner products against the shared digits (XLA graph path —
    the bit-exact semantics reference of the fused Pallas kernel):
    acc0[s] = c0 + sum_c D_c * B'[s, c], acc1[s] = sum_c D_c * A'[s, c].
    Outputs still await the per-step output permutation."""
    ntt = ctx.ntt
    p = jnp.asarray(ntt.p)
    pinv = jnp.asarray(ntt.pinv_neg)
    num_s, num_r = b_mont.shape[0], b_mont.shape[1]
    batch_ndim = c0.ndim - 2
    kshape = (num_s,) + (1,) * batch_ndim + b_mont.shape[1:]
    kb = b_mont.reshape(kshape)
    ka = a_mont.reshape(kshape)
    acc0 = modular.mont_mul(d_eval[..., 0, :, :], kb[..., 0, :, :], p, pinv)
    acc1 = modular.mont_mul(d_eval[..., 0, :, :], ka[..., 0, :, :], p, pinv)
    for c in range(1, num_r):                                     # modular tree-sum
        acc0 = modular.add_mod(
            acc0, modular.mont_mul(d_eval[..., c, :, :], kb[..., c, :, :], p, pinv), p
        )
        acc1 = modular.add_mod(
            acc1, modular.mont_mul(d_eval[..., c, :, :], ka[..., c, :, :], p, pinv), p
        )
    return modular.add_mod(acc0, c0[None], p), acc1


def hoisted_rotations_core(
    ctx: CkksContext, c0: jax.Array, d_eval: jax.Array,
    perms: jax.Array, b_mont: jax.Array, a_mont: jax.Array,
    backend: str | None = None,
) -> tuple[jax.Array, jax.Array]:
    """All planned rotations from the shared digit tensors -> stacked
    (r0, r1) uint32[S, ..., L, N], eval domain.

    Backend-dispatched like `_keyswitch_coeff` (`HEFL_HE` env / autoselect;
    untileable rings always XLA): on the Pallas backend the whole per-step
    digit x key accumulation runs as `pallas_ntt.hoisted_rotations_pallas`
    (one fused dispatch for every step), bitwise-equal to the XLA graph.
    The final eval-domain output permutation is a static gather either way.
    """
    from hefl_tpu.ckks.backend import resolve_he_backend

    if resolve_he_backend(ctx, backend) == "pallas":
        from hefl_tpu.ckks import pallas_ntt

        if pallas_ntt.supported(ctx.ntt):
            acc0, acc1 = pallas_ntt.hoisted_rotations_pallas(
                ctx.ntt, c0, d_eval, b_mont, a_mont
            )
        else:
            acc0, acc1 = _hoisted_products_xla(ctx, c0, d_eval, b_mont, a_mont)
    else:
        acc0, acc1 = _hoisted_products_xla(ctx, c0, d_eval, b_mont, a_mont)
    batch_ndim = c0.ndim - 2
    idx = perms.reshape((perms.shape[0],) + (1,) * (batch_ndim + 1) + (perms.shape[-1],))
    return (
        jnp.take_along_axis(acc0, idx, axis=-1),
        jnp.take_along_axis(acc1, idx, axis=-1),
    )


def hoisted_rotations(
    ctx: CkksContext, ct: Ciphertext, steps, gks: dict,
    backend: str | None = None,
) -> Ciphertext:
    """Rotate `ct` by every step in `steps` sharing ONE gadget
    decomposition -> stacked Ciphertext (leading axis S).

    Cost: 1 inverse NTT + L*d forward NTTs TOTAL, then 2*(L*d) Montgomery
    multiplies + one gather per step — vs (L*d + 1) forward NTTs (plus the
    inverse pair) PER STEP for a loop of `ct_rotate` calls."""
    perms, bk, ak = hoisted_rotation_tables(ctx, gks, steps)
    c1_coeff = ntt_inverse(ctx.ntt, ct.c1)
    d_eval = hoisted_digits(ctx, c1_coeff)
    r0, r1 = hoisted_rotations_core(ctx, ct.c0, d_eval, perms, bk, ak, backend)
    return Ciphertext(c0=r0, c1=r1, scale=ct.scale)


def hoisted_rotations_reference(
    ctx: CkksContext, ct: Ciphertext, steps, gks: dict
) -> Ciphertext:
    """The UNHOISTED twin (bitwise parity anchor, XLA only): the same
    uncentered decomposition applied step-by-step — per step, the
    coefficient-domain signed automorphism of every digit polynomial, L*d
    fresh forward NTTs, and the inner product against the ORIGINAL
    (unpermuted) key rows. Exact modular arithmetic makes this
    bitwise-equal to `hoisted_rotations`; it is also the honest cost model
    the hoisted path is benchmarked against (bench_inference)."""
    from hefl_tpu.ckks import galois

    ntt = ctx.ntt
    p = jnp.asarray(ntt.p)
    pinv = jnp.asarray(ntt.pinv_neg)
    w = ctx.ksk_digit_bits
    d = ctx.ksk_num_digits
    mask = jnp.uint32((1 << w) - 1)
    num_l = ctx.num_primes
    num_r = num_l * d
    c0_coeff = ntt_inverse(ntt, ct.c0)
    c1_coeff = ntt_inverse(ntt, ct.c1)
    digits = jnp.stack(
        [(c1_coeff >> jnp.uint32(w * k)) & mask for k in range(d)], axis=-2
    )
    comp = digits.reshape(*c1_coeff.shape[:-2], num_r, ctx.n)
    lifted = jnp.broadcast_to(
        comp[..., :, None, :], (*c1_coeff.shape[:-2], num_r, num_l, ctx.n)
    )
    r0s, r1s = [], []
    for s in steps:
        g = galois.galois_elt_rotation(ctx.n, int(s))
        if gks[int(s)].g != g:
            raise ValueError(f"galois key for step {s} has g={gks[int(s)].g}")
        src, flip = galois.automorphism_tables(ctx.n, g)
        pd = galois.apply_automorphism(lifted, p, src, flip)
        d_eval = ntt_forward(ntt, pd)
        bk = gks[int(s)].b_mont[:num_r]
        ak = gks[int(s)].a_mont[:num_r]
        t0 = modular.mont_mul(d_eval, bk, p, pinv)
        t1 = modular.mont_mul(d_eval, ak, p, pinv)
        k0, k1 = t0[..., 0, :, :], t1[..., 0, :, :]
        for c in range(1, num_r):
            k0 = modular.add_mod(k0, t0[..., c, :, :], p)
            k1 = modular.add_mod(k1, t1[..., c, :, :], p)
        pc0 = galois.apply_automorphism(c0_coeff, p, src, flip)
        r0s.append(modular.add_mod(ntt_forward(ntt, pc0), k0, p))
        r1s.append(k1)
    return Ciphertext(c0=jnp.stack(r0s), c1=jnp.stack(r1s), scale=ct.scale)


def ct_mul(ctx: CkksContext, a: Ciphertext, b: Ciphertext, rlk: RelinKey) -> Ciphertext:
    """Ciphertext x ciphertext multiply with relinearization.

    Beyond reference parity: the reference's pipeline never multiplies two
    ciphertexts and its relin keygen is dead code (FLPyfhelin.py:357-364,
    SURVEY.md §2.6); implemented here so the HE layer is a complete CKKS
    library. Under coefficient packing the product is the NEGACYCLIC
    CONVOLUTION of the packed vectors (elementwise products need slot
    packing); the result scale is the exact product of input scales —
    `rescale` afterwards to shed a limb and renormalize.
    """
    # Fail loudly before the plaintext wraps mod q (the same philosophy as
    # the q < scale*256 guard in CkksContext.create): the product's scaled
    # message needs headroom for |w| up to ~16 plus noise.
    out_scale = a.scale * b.scale
    if out_scale * 16 >= ctx.modulus:
        raise ValueError(
            f"ct_mul result scale 2**{int(out_scale).bit_length() - 1} leaves no "
            f"headroom under q~2**{ctx.modulus.bit_length()}; rescale between "
            "multiplies or add RNS primes"
        )
    ntt = ctx.ntt
    p = jnp.asarray(ntt.p)
    pinv = jnp.asarray(ntt.pinv_neg)
    b0m = to_mont(ntt, b.c0)
    b1m = to_mont(ntt, b.c1)
    d0 = modular.mont_mul(a.c0, b0m, p, pinv)
    d1 = modular.add_mod(
        modular.mont_mul(a.c0, b1m, p, pinv),
        modular.mont_mul(a.c1, b0m, p, pinv),
        p,
    )
    d2 = modular.mont_mul(a.c1, b1m, p, pinv)
    k0, k1 = _keyswitch_d2(ctx, d2, rlk)
    return Ciphertext(
        c0=modular.add_mod(d0, k0, p),
        c1=modular.add_mod(d1, k1, p),
        scale=out_scale,
    )


def rescale(ctx: CkksContext, a: Ciphertext) -> tuple["CkksContext", Ciphertext]:
    """Drop the last RNS limb and divide the plaintext by p_last.

    Standard RNS-CKKS rescale: c'_i = (c_i - [c_last]) * p_last^{-1} mod p_i.
    Ciphertext limbs live in evaluation domain under *per-prime* twiddles, so
    the dropped limb must round-trip through the coefficient domain: iNTT
    under p_last, re-NTT its (canonical, already-reduced — primes descend so
    p_last is smallest) representative under each head prime, then subtract.
    Our FedAvg pipeline never strictly needs rescale (one plaintext multiply
    fits the modulus budget), but it completes the CKKS op surface. Returns
    the shrunken context alongside the rescaled ciphertext.
    """
    num_l = ctx.num_primes
    if num_l < 2:
        raise ValueError("cannot rescale at the last level")
    p_np = np.asarray(ctx.ntt.p)[:, 0]
    p_last = int(p_np[-1])
    last_tables = ctx.ntt.slice_limbs(num_l - 1, num_l)
    head_tables = ctx.ntt.slice_limbs(0, num_l - 1)
    p_head = jnp.asarray(head_tables.p)
    pinv_head = jnp.asarray(head_tables.pinv_neg)
    inv_mont = jnp.asarray(
        np.array(
            [[host_to_mont(pow(p_last % int(pi), int(pi) - 2, int(pi)), int(pi))] for pi in p_np[:-1]],
            dtype=np.uint32,
        )
    )

    def _drop(c: jax.Array) -> jax.Array:
        c_head, c_last = c[..., :-1, :], c[..., -1:, :]
        last_coeff = ntt_inverse(last_tables, c_last)               # [..., 1, N] < p_last
        rep_eval = ntt_forward(head_tables, jnp.broadcast_to(last_coeff, c_head.shape))
        diff = modular.sub_mod(c_head, rep_eval, p_head)
        return modular.mont_mul(diff, inv_mont, p_head, pinv_head)

    sub_ctx = CkksContext(
        ntt=head_tables,
        scale=ctx.scale,
        sigma=ctx.sigma,
        ksk_digit_bits=ctx.ksk_digit_bits,
    )
    return sub_ctx, Ciphertext(
        c0=_drop(a.c0), c1=_drop(a.c1), scale=a.scale / p_last
    )


# ---------------------------------------------------------------------------
# Shaped jaxpr probe (ISSUE 13): the fused key-switch kernel's gadget-tensor
# contract, mirrored for the static-analysis gate
# (analysis.ranges.certify_keyswitch).
# ---------------------------------------------------------------------------


def keyswitch_gadget_probe(prime: int, digit_bits: int, num_digits: int):
    """The gadget key-switch's carrier arithmetic as a traceable mirror
    (analysis.ranges.certify_keyswitch).

    Mirrors, per RNS limb, what `_keyswitch_coeff_xla` and the fused
    `pallas_ntt.keyswitch_fused_pallas` kernel compute on the gadget
    tensors: base-2**w digit extraction from the canonical representative,
    digit centering, the digit x key Montgomery inner product over all
    L*d+1 components (the constant-1 correction row consuming the last),
    and the modular tree-sum — on the int64 carrier with `%` as the
    allowlisted probe modulo, which is the REDC canonical-residue CONTRACT
    (the wrapping uint32 cores are covered by the lint rules and the
    bitwise parity tests, like every other probe in this tree). The NTT
    between decompose and inner product is range-preserving (canonical in,
    canonical out) and is elided, exactly as the ladder probe elides it.

    Returning the raw digits lets the certificate check them against BOTH
    the 2**w gadget bound and the canonical range [0, p-1] — the fused
    kernel's `sub_mod` centering assumes canonical digits, so a digit
    width that overflows the prime is refuted here, statically.
    Trace under `jax.enable_x64(True)`. -> (fn, example_args).
    """
    p = int(prime)
    w = int(digit_bits)
    half = 1 << max(w - 1, 0)
    mask = (1 << w) - 1
    m = 4  # coefficients per probe limb; ranges are per-element anyway

    def probe(coeff, key_b, key_a):
        digits = []
        acc0 = jnp.zeros_like(coeff)
        acc1 = jnp.zeros_like(coeff)
        for k in range(int(num_digits)):
            digit = (coeff >> (w * k)) & mask
            digits.append(digit)
            centered = (digit + (p - half)) % p    # canonical
            acc0 = (acc0 + centered * key_b) % p
            acc1 = (acc1 + centered * key_a) % p
        # The constant-1 correction digit consumes the last key row.
        acc0 = (acc0 + key_b) % p
        acc1 = (acc1 + key_a) % p
        return jnp.stack(digits), acc0, acc1

    z = np.zeros((m,), np.int64)
    return probe, (z, z, z)


def hoisted_gadget_probe(prime: int, digit_bits: int, num_digits: int):
    """The HOISTED rotation's carrier arithmetic as a traceable mirror
    (analysis.ranges.certify_inference, ISSUE 18).

    Mirrors what `hoisted_digits` + `hoisted_rotations_core` compute per
    RNS limb: UNCENTERED base-2**w digit extraction (no centering, no
    correction row — the exact gadget identity the hoisted path relies
    on), then, inside a `lax.while_loop` over an ABSTRACT step count, the
    digit x pre-permuted-key Montgomery inner product, the c0 add, and the
    eval-domain output permutation (a `take` gather through the step's
    permutation table — range-preserving by construction, proven rather
    than assumed). The loop folds each step's outputs into a carried
    accumulator so the invariant holds for ANY number of hoisted steps.
    Int64 carrier, `%` as the allowlisted probe modulo, exactly like the
    ladder and key-switch probes. Trace under
    `jax.enable_x64(True)`. -> (fn, example_args).

    Returning the raw digits lets the certificate check them against BOTH
    the 2**w gadget bound and the canonical range [0, p-1]: the hoisted
    path skips centering, so its digits must be canonical AS EXTRACTED —
    a digit width overflowing the prime is refuted here, statically.
    """
    p = int(prime)
    w = int(digit_bits)
    mask = (1 << w) - 1
    m = 4  # coefficients per probe limb; ranges are per-element anyway

    def probe(num_steps, c0, c1, key_b, key_a, perm):
        digits = []
        for k in range(int(num_digits)):
            digits.append((c1 >> (w * k)) & mask)   # [0, 2**w - 1], canonical
        digit_stack = jnp.stack(digits)

        def cond(state):
            return state[0] > 0

        def body(state):
            remaining, a0, a1 = state
            # One hoisted step: inner product of the SHARED digits against
            # this step's (pre-inverse-permuted) key rows, the c0 add, and
            # the output permutation.
            k0 = jnp.zeros_like(c0)
            k1 = jnp.zeros_like(c1)
            for k in range(int(num_digits)):
                k0 = (k0 + digit_stack[k] * key_b) % p
                k1 = (k1 + digit_stack[k] * key_a) % p
            r0 = jnp.take((c0 + k0) % p, perm, axis=-1)
            r1 = jnp.take(k1, perm, axis=-1)
            return remaining - 1, (a0 + r0) % p, (a1 + r1) % p

        _, a0, a1 = jax.lax.while_loop(
            cond, body, (num_steps, jnp.zeros_like(c0), jnp.zeros_like(c1))
        )
        return digit_stack, a0, a1

    z = np.zeros((m,), np.int64)
    return probe, (np.int64(0), z, z, z, z, np.zeros((m,), np.int64))


def exact_int_probes() -> dict:
    """The key-switch gadget as a declared exact-integer region
    (analysis.lint): digit extraction, centering, and the digit x key
    accumulation are watched by the no-float / no-stray-div rules (the
    `%` is the allowlisted probe modulo). The hoisted-rotation mirror
    (uncentered digits, shared across the step loop) is a second declared
    region under the same rules."""
    fn, args = keyswitch_gadget_probe(2**27 - 39, 5, 6)
    hfn, hargs = hoisted_gadget_probe(2**27 - 39, 5, 6)
    return {
        "ckks.ops.keyswitch_gadget": (fn, args),
        "ckks.ops.hoisted_gadget": (hfn, hargs),
    }
