"""Negacyclic number-theoretic transform over RNS limbs, batched for TPU.

The reference delegates all polynomial arithmetic in Z_q[x]/(x^N+1) to SEAL's
C++ NTT (via Pyfhel, SURVEY.md §2.12). Here the forward transform is the
merged Cooley-Tukey decimation-in-time with the 2N-th root folded into
bit-reversed twiddle tables (Longa-Naehrig style), and the inverse is the
matching Gentleman-Sande decimation-in-frequency — so no separate psi^i
pre/post-scaling pass and no runtime bit-reversal permutation.

Shapes: residue tensors are `uint32[..., L, N]` (L = number of RNS primes,
N = polynomial degree, N in the TPU lane dimension). The log2(N) stages are a
static Python loop inside jit — XLA sees straight-line vector code, every
butterfly a fused mul/add across lanes.

Domain convention: "evaluation domain" means bit-reversed NTT order.
Ciphertexts live their whole life in evaluation domain (add / ct×pt / psum
are pointwise there); only encode/decode cross back to coefficients.
"""

from __future__ import annotations

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from hefl_tpu.ckks import primes as primes_mod
from hefl_tpu.ckks.modular import add_mod, mont_mul, shoup_mul, sub_mod

# NTT backend selector: "auto" uses the fused Pallas kernel on TPU when the
# ring fits the (>=8, 128) uint32 tile, the stage-unrolled XLA graph
# otherwise (CPU tests, tiny test rings). Override with HEFL_NTT=xla|pallas;
# "pallas-interpret" routes every supported ring through the Pallas kernels
# (interpreted off-TPU, no error on unsupported rings) — the CI shard that
# runs the kernel family's code path inside the regular test tier.
_BACKEND = os.environ.get("HEFL_NTT", "auto")


def on_tpu_backend() -> bool:
    """True when the default JAX backend is a TPU."""
    return jax.default_backend() == "tpu"


def _use_pallas(ctx: "NTTContext") -> bool:
    if _BACKEND == "xla":
        return False
    if _BACKEND == "auto" and not on_tpu_backend():
        return False  # cheap check first: never import pallas off-TPU in auto
    if _BACKEND not in ("auto", "pallas", "pallas-interpret"):
        raise ValueError(
            f"HEFL_NTT={_BACKEND!r}: expected 'auto', 'xla', 'pallas' or "
            "'pallas-interpret'"
        )
    from hefl_tpu.ckks import pallas_ntt  # local: avoids circular import

    if _BACKEND == "pallas" and not pallas_ntt.supported(ctx):
        raise ValueError(
            f"HEFL_NTT=pallas forced but ring n={ctx.n} does not fit the "
            f"(>=8, 128) uint32 tile; use n>=1024 or HEFL_NTT=auto"
        )
    # "pallas-interpret" silently falls back to XLA on unsupported rings so
    # the whole suite (tiny test rings included) can run under one env.
    return pallas_ntt.supported(ctx)


@dataclasses.dataclass(frozen=True)
class NTTContext:
    """Per-modulus-chain constant tables, all device-ready numpy.

    Built once per CKKS context (host-side bignum in :mod:`primes`), then
    closed over by the jitted transforms. Everything is `uint32[L, ...]` with
    twiddles in Montgomery form.
    """

    n: int
    logn: int
    p: np.ndarray             # uint32[L, 1]
    pinv_neg: np.ndarray      # uint32[L, 1]
    r2: np.ndarray            # uint32[L, 1]
    psi_rev: np.ndarray       # uint32[L, N]
    psi_inv_rev: np.ndarray   # uint32[L, N]
    n_inv_mont: np.ndarray    # uint32[L, 1]

    @classmethod
    def build(cls, prime_list: list[int], n: int, seed: int = 0) -> "NTTContext":
        infos = [primes_mod.PrimeInfo.build(p, n, seed=seed) for p in prime_list]
        col = lambda attr: np.array([[getattr(i, attr)] for i in infos], dtype=np.uint32)  # noqa: E731
        return cls(
            n=n,
            logn=n.bit_length() - 1,
            p=col("p"),
            pinv_neg=col("pinv_neg"),
            r2=col("r2"),
            psi_rev=np.stack([i.psi_rev for i in infos]),
            psi_inv_rev=np.stack([i.psi_inv_rev for i in infos]),
            n_inv_mont=col("n_inv_mont"),
        )

    def slice_limbs(self, lo: int, hi: int) -> "NTTContext":
        """Sub-context over primes [lo, hi) — used by rescale and level drops."""
        return NTTContext(
            n=self.n,
            logn=self.logn,
            p=self.p[lo:hi],
            pinv_neg=self.pinv_neg[lo:hi],
            r2=self.r2[lo:hi],
            psi_rev=self.psi_rev[lo:hi],
            psi_inv_rev=self.psi_inv_rev[lo:hi],
            n_inv_mont=self.n_inv_mont[lo:hi],
        )

    def __hash__(self):  # static-arg hashing for jit
        # Twiddle tables are seed-dependent (choice of primitive root), so
        # they must participate in the jit static-arg identity — otherwise a
        # context built with a different root could silently reuse a compiled
        # executable holding the wrong tables as constants.
        return hash((self.n, tuple(int(x) for x in self.p[:, 0]), self.psi_rev[:, :2].tobytes()))

    def __eq__(self, other):
        return (
            isinstance(other, NTTContext)
            and self.n == other.n
            and np.array_equal(self.p, other.p)
            and np.array_equal(self.psi_rev, other.psi_rev)
        )


@dataclasses.dataclass(frozen=True)
class ShoupTables:
    """Plain-domain twiddles + Harvey/Shoup quotient constants.

    Derived (exact host bignum, cached per context) from the Montgomery
    tables the context stores/serializes, so the wire format is untouched:
    plain = mont * 2**-32 mod p, shoup = floor(plain * 2**32 / p). The
    butterfly multiply then costs ONE wide multiply instead of the two a
    Montgomery REDC needs — the division-free fast path both the XLA graph
    and the fused Pallas kernels run.
    """

    psi: np.ndarray           # uint32[L, N] plain-domain forward twiddles
    psi_shoup: np.ndarray     # uint32[L, N] floor(psi * 2**32 / p)
    psi_inv: np.ndarray       # uint32[L, N] plain-domain inverse twiddles
    psi_inv_shoup: np.ndarray
    n_inv: np.ndarray         # uint32[L, 1] plain-domain N^{-1}
    n_inv_shoup: np.ndarray   # uint32[L, 1]


@functools.lru_cache(maxsize=16)
def shoup_tables(ctx: NTTContext) -> ShoupTables:
    p = np.asarray(ctx.p)[:, 0].astype(object)[:, None]       # [L, 1]
    inv32 = np.array(
        [[pow(1 << 32, -1, int(pi))] for pi in p[:, 0]], dtype=object
    )

    def unmont(mont: np.ndarray) -> np.ndarray:
        return (mont.astype(object) * inv32) % p

    def shoup(plain: np.ndarray) -> np.ndarray:
        return (plain << 32) // p

    psi = unmont(np.asarray(ctx.psi_rev))
    psi_inv = unmont(np.asarray(ctx.psi_inv_rev))
    n_inv = unmont(np.asarray(ctx.n_inv_mont))
    return ShoupTables(
        psi=psi.astype(np.uint32),
        psi_shoup=shoup(psi).astype(np.uint32),
        psi_inv=psi_inv.astype(np.uint32),
        psi_inv_shoup=shoup(psi_inv).astype(np.uint32),
        n_inv=n_inv.astype(np.uint32),
        n_inv_shoup=shoup(n_inv).astype(np.uint32),
    )


# Trace-time transform counters (ISSUE 18): every ntt_forward/ntt_inverse
# CALL bumps these by the number of [L, N] polynomial transforms its input
# carries (batch x component axes; shapes are static, so the count is too).
# Inside jit the bump happens at TRACE time — a `lax.scan` body counts ONCE
# however many stages it runs — which is exactly the per-stage/shared-prefix
# cost model the hoisting tests assert against (tests/test_hoisted.py).
_TRACE_TRANSFORMS = {"forward": 0, "inverse": 0}


def transform_trace_counts() -> dict:
    """Snapshot of the trace-time transform counters (copies, not a view)."""
    return dict(_TRACE_TRANSFORMS)


def _count_transforms(kind: str, a: jnp.ndarray) -> None:
    _TRACE_TRANSFORMS[kind] += int(np.prod(a.shape[:-2], dtype=np.int64))


def ntt_forward(ctx: NTTContext, a: jnp.ndarray) -> jnp.ndarray:
    """Coefficient domain -> evaluation (bit-reversed NTT) domain.

    `a`: uint32[..., L, N] canonical residues. Static unrolled radix-2 CT
    stages; stage s has m=2**s blocks of half-width t=N/2m, twiddle slice
    psi_rev[:, m:2m].
    """
    _count_transforms("forward", a)
    if _use_pallas(ctx):
        from hefl_tpu.ckks import pallas_ntt

        return pallas_ntt.ntt_forward_pallas(ctx, a)
    n, logn = ctx.n, ctx.logn
    p = jnp.asarray(ctx.p)
    tabs = shoup_tables(ctx)
    batch = a.shape[:-2]
    num_l = a.shape[-2]
    for s in range(logn):
        m = 1 << s
        t = n // (2 * m)
        blocks = a.reshape(*batch, num_l, m, 2, t)
        lo = blocks[..., 0, :]
        hi = blocks[..., 1, :]
        tw = jnp.asarray(tabs.psi[:, m : 2 * m])[:, :, None]         # [L, m, 1]
        tw_sh = jnp.asarray(tabs.psi_shoup[:, m : 2 * m])[:, :, None]
        v = shoup_mul(hi, tw, tw_sh, p[..., None])
        out_lo = add_mod(lo, v, p[..., None])
        out_hi = sub_mod(lo, v, p[..., None])
        a = jnp.stack([out_lo, out_hi], axis=-2).reshape(*batch, num_l, n)
    return a


def ntt_inverse(ctx: NTTContext, a: jnp.ndarray) -> jnp.ndarray:
    """Evaluation (bit-reversed) domain -> coefficient domain, including the
    final N^{-1} scaling (folded in as one extra Montgomery multiply)."""
    _count_transforms("inverse", a)
    if _use_pallas(ctx):
        from hefl_tpu.ckks import pallas_ntt

        return pallas_ntt.ntt_inverse_pallas(ctx, a)
    n, logn = ctx.n, ctx.logn
    p = jnp.asarray(ctx.p)
    tabs = shoup_tables(ctx)
    batch = a.shape[:-2]
    num_l = a.shape[-2]
    for s in range(logn - 1, -1, -1):
        h = 1 << s
        t = n // (2 * h)
        blocks = a.reshape(*batch, num_l, h, 2, t)
        lo = blocks[..., 0, :]
        hi = blocks[..., 1, :]
        tw = jnp.asarray(tabs.psi_inv[:, h : 2 * h])[:, :, None]     # [L, h, 1]
        tw_sh = jnp.asarray(tabs.psi_inv_shoup[:, h : 2 * h])[:, :, None]
        out_lo = add_mod(lo, hi, p[..., None])
        diff = sub_mod(lo, hi, p[..., None])
        out_hi = shoup_mul(diff, tw, tw_sh, p[..., None])
        a = jnp.stack([out_lo, out_hi], axis=-2).reshape(*batch, num_l, n)
    return shoup_mul(
        a, jnp.asarray(tabs.n_inv), jnp.asarray(tabs.n_inv_shoup), p
    )


def pointwise_mul(ctx: NTTContext, a: jnp.ndarray, b_mont: jnp.ndarray) -> jnp.ndarray:
    """Evaluation-domain product a ∘ b where `b_mont` is pre-lifted to
    Montgomery form (e.g. a key polynomial). Result is plain-domain."""
    return mont_mul(a, b_mont, jnp.asarray(ctx.p), jnp.asarray(ctx.pinv_neg))


def to_mont(ctx: NTTContext, a: jnp.ndarray) -> jnp.ndarray:
    """Lift residues to Montgomery form (multiply by 2**32 mod p)."""
    return mont_mul(a, jnp.asarray(ctx.r2), jnp.asarray(ctx.p), jnp.asarray(ctx.pinv_neg))


def negacyclic_poly_mul(ctx: NTTContext, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Full coefficient-domain negacyclic product (test/reference path, not hot)."""
    ea = ntt_forward(ctx, a)
    eb = to_mont(ctx, ntt_forward(ctx, b))
    return ntt_inverse(ctx, pointwise_mul(ctx, ea, eb))
