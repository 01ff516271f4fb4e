"""Encrypted inference: linear scoring of slot-packed features under CKKS.

Beyond the reference's capability surface: its pipeline only ever AGGREGATES
under encryption (ct+ct and ct x plaintext-scalar,
/root/reference/FLPyfhelin.py:366-390) — the model itself always runs on
plaintext. With the rebuild's slot packing (encoding.encode_slots), ct x
plaintext-polynomial multiplies, and Galois rotations, a server holding only
(context, pk, rotation keys) can additionally score an ENCRYPTED feature
vector against its own plaintext linear model — private inference riding the
same crypto layer as the FL training loop:

    scores[k] = <x, W[k]> + b[k]   computed entirely under encryption:

  1. slot-wise product  ct_x (*) encode_slots(W[k])      (ops.ct_mul_plain_poly)
  2. rotate-and-sum     log2(slots) rotations+adds fold every slot into the
                        total inner product (each slot ends up holding it)
  3. bias               ct_add_plain of b[k] at the product scale

The client decrypts num_classes scores — the server never sees features and
the client never sees W. Every step is jit-compatible (rotation count and
class count are static).

Serving plans (ISSUE 13): the ladder above costs K x log2(slots)
key-switches per sample. `BsgsLinearScorer` replaces it with a baby-step
giant-step plan over the model's generalized diagonals (Halevi-Shoup):
all K class scores ride ONE output ciphertext, the query's inverse NTT is
hoisted out of the baby-rotation sweep, the automorphism tables and Galois
keys for every planned step are hoisted (stacked) at build time, and the
per-score key-switch count drops to ~2*sqrt(d + K) — independent of K.
Batched serving (`score_many`) pads query batches to power-of-two buckets
so any batch size hits a small set of compiled programs, each amortizing
one fused dispatch chain (the Pallas key-switch kernel batches across the
whole query batch) over every query in it.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import numpy as np
import jax
import jax.numpy as jnp

from hefl_tpu.ckks import encoding, galois, ops
from hefl_tpu.ckks.keys import CkksContext, GaloisKey, PublicKey, SecretKey, gen_galois_key
from hefl_tpu.ckks.ops import Ciphertext
from hefl_tpu.obs import scopes as obs_scopes


def rotation_steps(num_slots: int) -> list[int]:
    """Power-of-two left-rotation steps a full rotate-and-sum needs."""
    steps = []
    s = 1
    while s < num_slots:
        steps.append(s)
        s *= 2
    return steps


def gen_rotation_keys(
    ctx: CkksContext, sk: SecretKey, key: jax.Array
) -> dict[int, GaloisKey]:
    """Galois keys for every power-of-two rotation up to slots/2 — the key
    bundle the scoring server holds (log2(slots) keys; never sk itself)."""
    keys = {}
    for i, step in enumerate(rotation_steps(encoding.num_slots(ctx.ntt))):
        k = jax.random.fold_in(key, i)
        keys[step] = gen_galois_key(
            ctx, sk, k, galois.galois_elt_rotation(ctx.n, step)
        )
    return keys


def gen_rotation_keys_for_steps(
    ctx: CkksContext, sk: SecretKey, key: jax.Array, steps
) -> dict[int, GaloisKey]:
    """Galois keys for an ARBITRARY set of left-rotation steps — the key
    bundle a BSGS scoring server holds (`BsgsPlan.rotation_steps_needed`,
    ~2*sqrt(d + K) keys vs the ladder's log2(slots); more key material is
    the classic BSGS trade for fewer key-switches per score). Key
    derivation folds in the STEP value, so the same (master key, step)
    always yields the same Galois key whatever set it is generated in."""
    out = {}
    for step in sorted({int(s) for s in steps}):
        if step == 0:
            continue
        out[step] = gen_galois_key(
            ctx, sk, jax.random.fold_in(key, step),
            galois.galois_elt_rotation(ctx.n, step),
        )
    return out


def encrypt_features(
    ctx: CkksContext, pk: PublicKey, x: np.ndarray, key: jax.Array
) -> Ciphertext:
    """Real feature vector [d] (d <= slots) -> slot-packed ciphertext.
    Zero-padded so the rotate-and-sum over all slots is exact."""
    slots = encoding.num_slots(ctx.ntt)
    if x.shape[-1] > slots:
        raise ValueError(f"{x.shape[-1]} features exceed {slots} slots")
    z = np.zeros(x.shape[:-1] + (slots,), np.float64)
    z[..., : x.shape[-1]] = np.asarray(x, np.float64)
    res = encoding.encode_slots(ctx.ntt, z, ctx.scale)
    return ops.encrypt(ctx, pk, jnp.asarray(res), key)


def rotate_and_sum(
    ctx: CkksContext, ct: Ciphertext, gks: dict[int, GaloisKey]
) -> Ciphertext:
    """Fold all slots into their total: after log2(slots) rotate+add stages
    every slot holds sum_j z_j. (Unrolled op-by-op form; the serving path
    uses `rotate_and_sum_scan`, which is this ladder as one `lax.scan`.)"""
    for step in rotation_steps(encoding.num_slots(ctx.ntt)):
        ct = ops.ct_add(ctx, ct, ops.ct_rotate(ctx, ct, gks[step], step))
    return ct


def stack_rotation_steps(
    ctx: CkksContext, gks: dict[int, GaloisKey], steps
):
    """Stack automorphism tables and Galois keys for an ARBITRARY rotation
    step sequence into scan-able arrays: -> (src i32[S, N], flip
    bool[S, N], b_mont u32[S, C, L, N], a_mont u32[S, C, L, N]). This is
    the hoisting half of a serving plan: every per-step table lookup and
    key/element consistency check happens HERE, once per scorer build, so
    the jitted program sees pure data and needs no per-stage validation."""
    steps = [int(s) for s in steps]
    if not steps:
        num_c = ctx.num_primes * ctx.ksk_num_digits + 1
        zk = jnp.zeros((0, num_c, ctx.num_primes, ctx.n), jnp.uint32)
        return (
            jnp.zeros((0, ctx.n), jnp.int32),
            jnp.zeros((0, ctx.n), bool),
            zk,
            zk,
        )
    missing = [s for s in steps if s not in gks]
    if missing:
        raise ValueError(f"rotation keys missing for steps {missing}")
    srcs, flips = [], []
    for s in steps:
        want = galois.galois_elt_rotation(ctx.n, s)
        if gks[s].g != want:
            raise ValueError(
                f"galois key for step {s} has g={gks[s].g}, rotation needs "
                f"g={want}"
            )
        src, flip = galois.automorphism_tables(ctx.n, want)
        srcs.append(src)
        flips.append(flip)
    return (
        jnp.asarray(np.stack(srcs)),
        jnp.asarray(np.stack(flips)),
        jnp.stack([gks[s].b_mont for s in steps]),
        jnp.stack([gks[s].a_mont for s in steps]),
    )


def stack_rotation_ladder(ctx: CkksContext, gks: dict[int, GaloisKey]):
    """The power-of-two rotate-and-sum ladder's stacked tables — the
    classic serving plan, `stack_rotation_steps` at steps 1, 2, 4, ...."""
    return stack_rotation_steps(
        ctx, gks, rotation_steps(encoding.num_slots(ctx.ntt))
    )


def ladder_stage_forward_ntts(ctx: CkksContext) -> int:
    """Forward [L, N] transforms ONE `rotate_and_sum_scan` stage pays:
    L*d gadget-digit NTTs + the rotated-c0 re-NTT. Pinned by a trace-count
    assertion in tests/test_hoisted.py (`ntt.transform_trace_counts`).

    Why the ladder CANNOT ride the hoisted decomposition
    (`ops.hoisted_rotations`, ISSUE 18): hoisting shares one gadget
    decomposition across rotations of the SAME ciphertext, but each ladder
    stage rotates the PREVIOUS stage's output — the scan carry
    ct <- ct + rot(ct) feeds stage k's c1 from stage k-1's key-switch, so
    there is no shared input to decompose. Every stage pays this full
    per-rotation cost by construction; the BSGS baby sweep (all rotations
    of one fixed query) is where hoisting applies."""
    return ctx.num_primes * ctx.ksk_num_digits + 1


def rotate_and_sum_scan(ctx: CkksContext, ct: Ciphertext, ladder) -> Ciphertext:
    """`rotate_and_sum` as ONE `lax.scan` over the ladder stages.

    The unrolled ladder inlines log2(slots) copies of the
    rotate+key-switch body (each with its own NTT stack) into the HLO —
    the 40-110 s serving compiles measured on CPU
    (INFERENCE_SMOKE_CPU.md) were dominated by exactly that. The scan
    compiles the stage body ONCE and feeds the per-stage automorphism
    tables and Galois keys in as data (`stack_rotation_ladder`); the
    automorphism was already a gather, so tables-as-data costs nothing
    extra. Same arithmetic, same result — pinned by the parity test in
    tests/test_he_inference.py.

    Per-stage cost stays `ladder_stage_forward_ntts(ctx)` forward NTTs:
    the scan CARRY (each stage rotates the previous stage's output) is
    what keeps this ladder outside the hoisted-decomposition fast path —
    see `ladder_stage_forward_ntts` for the full argument."""
    from hefl_tpu.ckks.modular import add_mod
    from hefl_tpu.ckks.ntt import ntt_forward, ntt_inverse
    from hefl_tpu.ckks.ops import _keyswitch_coeff

    ntt = ctx.ntt
    p = jnp.asarray(ntt.p)

    def stage(carry, inp):
        c0, c1 = carry
        src, flip, b_mont, a_mont = inp
        # Leaf compute of the serving ladder: the stage body (inside the
        # scan, so the loop op itself stays a scope-less container). The
        # key-switch gets its own nested scope so trace attribution and
        # HLO coverage see the fused kernel as a first-class phase.
        with jax.named_scope(obs_scopes.SERVE_ROTATE):
            pc0 = galois.apply_automorphism(ntt_inverse(ntt, c0), p, src, flip)
            pc1 = galois.apply_automorphism(ntt_inverse(ntt, c1), p, src, flip)
            with jax.named_scope(obs_scopes.SERVE_KEYSWITCH):
                k0, k1 = _keyswitch_coeff(ctx, pc1, b_mont, a_mont)
            rot0 = add_mod(ntt_forward(ntt, pc0), k0, p)
            return (add_mod(c0, rot0, p), add_mod(c1, k1, p)), None

    (c0, c1), _ = jax.lax.scan(stage, (ct.c0, ct.c1), ladder)
    return Ciphertext(c0=c0, c1=c1, scale=ct.scale)


def _linear_apply(ctx: CkksContext, pt_scale: float, ct_x: Ciphertext, w_res, b_res, ladder):
    """Score encrypted samples (any leading batch shape on the ciphertext)
    against all K classes: broadcast ct x plaintext multiply over the K
    axis + ONE shared scanned rotate-and-sum ladder over the whole
    [..., K] block + bias add.

    Batching rides broadcasting, not `jax.vmap`: the ladder's key-switch
    then reaches `ops._keyswitch_coeff` with an explicit [..., K, L, N]
    batch, which the fused Pallas kernel flattens into its (prime, row)
    grid — one kernel dispatch chain per stage for the entire batch."""
    with jax.named_scope(obs_scopes.SERVE_SCORE):
        ct = ops.ct_mul_plain_poly(
            ctx,
            Ciphertext(
                c0=ct_x.c0[..., None, :, :],
                c1=ct_x.c1[..., None, :, :],
                scale=ct_x.scale,
            ),
            w_res,
            pt_scale,
        )
    ct = rotate_and_sum_scan(ctx, ct, ladder)   # scan call: scope-less
    with jax.named_scope(obs_scopes.SERVE_SCORE):
        return ops.ct_add_plain(ctx, ct, b_res)


@functools.lru_cache(maxsize=16)
def _linear_program(ctx: CkksContext, pt_scale: float):
    """ONE jitted program scoring all K classes of one sample. Replaces
    K x log2(slots) x ~4 separate op dispatches with a single compiled
    dispatch — the difference between a host-driven loop and a device
    program."""

    @jax.jit
    def run(ct_x: Ciphertext, w_res, b_res, ladder):
        return _linear_apply(ctx, pt_scale, ct_x, w_res, b_res, ladder)

    return run


@functools.lru_cache(maxsize=16)
def _linear_batch_program(ctx: CkksContext, pt_scale: float):
    """The batched-serving variant: ONE jitted program scoring a whole
    batch of encrypted samples (leading axis B on the ciphertext) — the
    throughput shape, amortizing dispatch and letting XLA tile the B×K
    lanes together. Same `_linear_apply` (broadcast batching handles the
    extra axis); a separate cache entry only because the jit cache is
    keyed per program object."""

    @jax.jit
    def run(ct_xs: Ciphertext, w_res, b_res, ladder):
        return _linear_apply(ctx, pt_scale, ct_xs, w_res, b_res, ladder)

    return run


def _encode_linear_model(
    ctx: CkksContext,
    weights: np.ndarray,
    bias: np.ndarray,
    ct_scale: float,
    pt_scale: float,
) -> tuple[jax.Array, jax.Array]:
    """Validate + slot-encode a plaintext linear model (weights [K, d<=slots],
    bias [K]) for scoring ciphertexts of scale `ct_scale`."""
    slots = encoding.num_slots(ctx.ntt)
    weights = np.asarray(weights, np.float64)
    bias = np.asarray(bias, np.float64)
    if weights.ndim != 2 or weights.shape[1] > slots:
        raise ValueError(f"weights must be [K, d<= {slots}], got {weights.shape}")
    if bias.shape != (weights.shape[0],):
        raise ValueError(f"bias must be [{weights.shape[0]}], got {bias.shape}")
    wz = np.zeros((weights.shape[0], slots), np.float64)
    wz[:, : weights.shape[1]] = weights
    w_res = jnp.asarray(encoding.encode_slots(ctx.ntt, wz, pt_scale))
    b_res = jnp.stack(
        [
            jnp.asarray(
                encoding.encode_slots_const(ctx.ntt, float(b), ct_scale * pt_scale)
            )
            for b in bias
        ]
    )
    return w_res, b_res


class LinearScorer:
    """Precompiled private-inference server for a FIXED plaintext linear model.

    Hoists everything per-model out of the per-sample path: weight/bias slot
    encoding (host FFTs) happens once here, and every `score` call is a
    single cached jitted device dispatch. This is the steady-state serving
    shape — `encrypted_linear` is the one-shot convenience wrapper over it.
    """

    def __init__(
        self,
        ctx: CkksContext,
        weights: np.ndarray,
        bias: np.ndarray,
        gks: dict[int, GaloisKey],
        pt_scale: float = 2.0**14,
        ct_scale: float | None = None,
    ):
        self.ctx = ctx
        self.pt_scale = pt_scale
        self.ct_scale = ctx.scale if ct_scale is None else ct_scale
        # Only the stacked ladder is retained: also holding the gks dict
        # would keep a second full copy of the Galois key material alive
        # for the scorer's lifetime.
        self._ladder = stack_rotation_ladder(ctx, gks)
        self.num_classes = int(np.asarray(weights).shape[0])
        self._w_res, self._b_res = _encode_linear_model(
            ctx, weights, bias, self.ct_scale, pt_scale
        )
        self._run = _linear_program(ctx, pt_scale)

    def score_batched(self, ct_x: Ciphertext) -> Ciphertext:
        """K class scores as ONE batched ciphertext (leading axis K)."""
        if ct_x.scale != self.ct_scale:
            raise ValueError(
                f"scorer was built for ct scale {self.ct_scale}, got {ct_x.scale}"
            )
        return self._run(ct_x, self._w_res, self._b_res, self._ladder)

    def score(self, ct_x: Ciphertext) -> list[Ciphertext]:
        batched = self.score_batched(ct_x)
        return [
            Ciphertext(c0=batched.c0[k], c1=batched.c1[k], scale=batched.scale)
            for k in range(self.num_classes)
        ]

    def score_many(self, ct_xs: Ciphertext) -> Ciphertext:
        """Score a whole BATCH of encrypted samples (ct_xs has a leading
        batch axis, e.g. from `encrypt_features(ctx, pk, x[B, d], key)`) in
        one device dispatch -> [B, K] batched score ciphertext. Decrypt
        with `decrypt_score_matrix`."""
        if ct_xs.scale != self.ct_scale:
            raise ValueError(
                f"scorer was built for ct scale {self.ct_scale}, got {ct_xs.scale}"
            )
        if ct_xs.c0.ndim != 3:
            raise ValueError(
                f"score_many needs a batched ciphertext [B, L, N], got limbs of "
                f"shape {ct_xs.c0.shape}; use score() for a single sample"
            )
        return _linear_batch_program(self.ctx, self.pt_scale)(
            ct_xs, self._w_res, self._b_res, self._ladder
        )


def encrypted_linear(
    ctx: CkksContext,
    ct_x: Ciphertext,
    weights: np.ndarray,
    bias: np.ndarray,
    gks: dict[int, GaloisKey],
    pt_scale: float = 2.0**14,
) -> list[Ciphertext]:
    """scores[k] = <x, weights[k]> + bias[k] under encryption.

    weights: float[K, d] (d <= slots), bias: float[K]. Returns K ciphertexts,
    each carrying its score replicated across all slots at scale
    ct_x.scale * pt_scale. The caller owns neither x nor sk; only the
    plaintext model. All K classes run as one jitted device program.
    For repeated scoring with a fixed model, build a `LinearScorer` once.
    """
    return LinearScorer(
        ctx, weights, bias, gks, pt_scale, ct_scale=ct_x.scale
    ).score(ct_x)


def decrypt_scores(
    ctx: CkksContext, sk: SecretKey, cts: list[Ciphertext]
) -> np.ndarray:
    """Owner-side: decrypt each class ciphertext, read slot 0 -> scores [K].

    `sk` must match `ctx`'s level: after rescales, slice it with
    `slice_secret_key(sk, ctx.num_primes)`.
    """
    scores = []
    for ct in cts:
        res = np.asarray(ops.decrypt(ctx, sk, ct))
        z = encoding.decode_slots(ctx.ntt, res, ct.scale)
        scores.append(float(np.real(z[..., 0])))
    return np.asarray(scores)


def decrypt_score_matrix(
    ctx: CkksContext, sk: SecretKey, ct: Ciphertext
) -> np.ndarray:
    """Owner-side: a batched score ciphertext (any leading axes, e.g.
    [B, K] from `score_many`) -> real scores of the same leading shape
    (slot 0 of every ciphertext), in one decrypt."""
    res = np.asarray(ops.decrypt(ctx, sk, ct))
    z = encoding.decode_slots(ctx.ntt, res, ct.scale)
    return np.real(z[..., 0])


def slice_secret_key(sk: SecretKey, num_primes: int) -> SecretKey:
    """Drop RNS limbs from sk to match a rescaled (shrunken) context."""
    return SecretKey(s_mont=sk.s_mont[:num_primes])


# ---------------------------------------------------------------------------
# Baby-step giant-step serving (ISSUE 13): the diagonal (Halevi-Shoup)
# linear layer — one output ciphertext for all K classes, ~2*sqrt(d + K)
# key-switches per score instead of the ladder's K*log2(slots).
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BsgsPlan:
    """A baby-step giant-step rotation plan for one scoring geometry.

    The linear layer is decomposed over generalized diagonals:
    y = Σ_t u_t ⊙ rot(x, t) with u_t[m] = W_pad[m, (m+t) mod slots], so
    slot m of the ONE output ciphertext holds class m's score. Only
    t ≡ t' (mod slots) with t' in [-(K-1), d-1] has a nonzero diagonal
    (d + K - 1 of them); writing t' = i*baby + j turns the sweep into
    `baby` rotations of the query x (the baby steps, all of the SAME
    ciphertext — its inverse NTT is hoisted out of the sweep) plus one
    rotation per giant block of the cheap plaintext-multiplied partial
    sums. Key-switches per score: (baby-1) + (#giants-1), independent of
    the class count K — the structural win over the per-class ladder.

    Plans are static, hashable jit keys; `giants` groups block indices by
    their rotation step (i*baby mod slots — blocks sharing a step, e.g.
    the identity pair i=0 / i*baby = -slots reachable when K nears the
    slot count, merge their diagonal rows and rotate once). The identity
    group rides FIRST, so the program seeds its accumulator from row 0
    without a rotation or a step-0 Galois key.
    """

    slots: int
    d: int
    num_classes: int
    baby: int                       # block size b
    t_lo: int                       # diagonal window [t_lo, t_hi] — one
    t_hi: int                       # residue class mod slots at most once
    giants: tuple[tuple[int, ...], ...]  # block-index groups, one per step;
                                    # identity (step 0) group first
    baby_steps: tuple[int, ...]     # rotation steps 1 .. baby-1
    giant_steps: tuple[int, ...]    # distinct nonzero steps, giants[1:]

    @property
    def num_keyswitches(self) -> int:
        """Key-switches one score costs under this plan."""
        return len(self.baby_steps) + len(self.giant_steps)

    @property
    def rotation_steps_needed(self) -> tuple[int, ...]:
        """The Galois-key bundle the serving server must hold."""
        return tuple(sorted(set(self.baby_steps) | set(self.giant_steps)))

    def forward_ntts(self, gadget_rows: int, hoisted: bool) -> int:
        """Forward [L, N] polynomial transforms one score pays in the
        rotation sweeps (baby + giant), for a context with `gadget_rows`
        = L*d gadget components (ISSUE 18 — the printed, gated number).

        Unhoisted, every baby rotation pays its own decomposition:
        gadget_rows digit NTTs + the rotated-c0 re-NTT. Hoisted, the
        whole baby sweep shares ONE decomposition (gadget_rows NTTs
        total; c0 needs no NTT — its eval form is permuted in place).
        Giant rotations act on DISTINCT partial sums, so they stay
        per-rotation in both plans."""
        per_rot = gadget_rows + 1
        giant = len(self.giant_steps) * per_rot
        if hoisted:
            return gadget_rows + giant
        return len(self.baby_steps) * per_rot + giant


def ladder_keyswitches(slots: int, num_classes: int) -> int:
    """Key-switches one score costs under the rotate-and-sum ladder —
    the baseline `BsgsPlan.num_keyswitches` is measured against."""
    return num_classes * len(rotation_steps(slots))


def bsgs_plan(
    slots: int, d: int, num_classes: int, baby: int | None = None
) -> BsgsPlan:
    """Plan the BSGS sweep for (slots, d features, K classes).

    Any 1 <= d <= slots works — non-power-of-two feature counts simply
    change which diagonals are nonzero, unlike the ladder, whose fold
    depth is pinned to log2(slots) regardless of d. The default block
    size b = round(sqrt(d + K - 1)) balances baby against giant
    rotations; pass `baby` to override (b=1 degenerates to pure giants).
    """
    if not 1 <= d <= slots:
        raise ValueError(f"need 1 <= d <= {slots} features, got {d}")
    if not 1 <= num_classes <= slots:
        raise ValueError(
            f"need 1 <= num_classes <= {slots}, got {num_classes}"
        )
    t_lo = -(num_classes - 1)
    # Each residue class mod `slots` may appear at most ONCE: the window
    # [-(K-1), d-1] has d + K - 1 entries, and when that exceeds `slots`
    # (full-width d) the wrapped classes would be double-counted — cap the
    # window at one full cycle. The diagonal builder computes the TRUE
    # (wrapped) diagonal of each class, so a capped window still covers
    # every nonzero entry of W.
    t_hi = min(d - 1, t_lo + slots - 1)
    n_diag = t_hi - t_lo + 1
    b = int(baby) if baby else max(1, round(math.sqrt(n_diag)))
    # Group blocks by rotation step: blocks sharing (i*b) mod slots —
    # the identity pair i=0 / i*b = -slots, or duplicate nonzero steps
    # when the window spans a full block cycle — merge their diagonal
    # rows (diagonals are disjoint residue classes, so the merge is a
    # plain sum) and rotate once. The identity group always exists
    # (i = 0) and seeds the accumulator without a key-switch.
    by_step: dict[int, list[int]] = {}
    for i in range(t_lo // b, t_hi // b + 1):
        by_step.setdefault((i * b) % slots, []).append(i)
    steps = [0] + sorted(s for s in by_step if s != 0)
    return BsgsPlan(
        slots=int(slots), d=int(d), num_classes=int(num_classes), baby=b,
        t_lo=t_lo, t_hi=t_hi,
        giants=tuple(tuple(by_step[s]) for s in steps),
        baby_steps=tuple(range(1, b)),
        giant_steps=tuple(steps[1:]),
    )


def _bsgs_diag_tables(
    ctx: CkksContext, plan: BsgsPlan, weights: np.ndarray,
    pt_scale: float, queries_per_ct: int = 1,
):
    """Hoisted plaintext half of the plan: the pre-rotated generalized
    diagonals v_{i,j} = rot(u_{(i*b+j) mod s}, -i*b), slot-encoded at
    pt_scale and lifted to eval-domain Montgomery form ->
    uint32[G, baby, L, N]. Blocks whose t' falls outside the nonzero
    window encode as exact zeros (they contribute nothing and keep the
    table dense, so the device program is one scan over the baby axis).

    With `queries_per_ct` = q > 1 the scoring matrix becomes
    block-diagonal with q identical W blocks of size D = slots/q — the
    slot-packed multi-query layout. Its generalized diagonals are the
    D-periodic tiling of the single block's (no block ever crosses into
    its neighbour: every in-window t satisfies |t| < D, and the crossing
    entries are exactly the zeros of the block diagonal), so q queries
    ride ONE ciphertext through the UNCHANGED device program — the
    per-query key-switch count divides by q.
    """
    from hefl_tpu.ckks.ntt import ntt_forward, to_mont

    s, b, num_k, d = plan.slots, plan.baby, plan.num_classes, plan.d
    q = int(queries_per_ct)
    block = s // q
    weights = np.asarray(weights, np.float64)
    vecs = np.zeros((len(plan.giants), b, s))
    rows = np.arange(num_k)
    for g_idx, group in enumerate(plan.giants):
        for i in group:
            for j in range(b):
                t = i * b + j
                if t < plan.t_lo or t > plan.t_hi:
                    continue
                if q == 1:
                    # Single-query: cyclic over the whole slot ring (the
                    # full-width d == slots window wraps legitimately).
                    cols = (rows + t) % s
                    sel = cols < d
                    u = np.zeros(s)
                    u[rows[sel]] = weights[rows[sel], cols[sel]]
                else:
                    # Packed: per-block coordinates, never wrapping — the
                    # in-window t always lands inside the D-slot block.
                    cols = rows + t
                    sel = (cols >= 0) & (cols < d)
                    blk = np.zeros(block)
                    blk[rows[sel]] = weights[rows[sel], cols[sel]]
                    u = np.tile(blk, q)
                # host-side hoist of the giant's inverse rotation:
                # np.roll(u, k)[m] = u[m-k] is the LEFT-rotation by -k.
                # Blocks in one group share the step mod slots, so their
                # rolled rows land identically aligned and sum exactly.
                vecs[g_idx, j] += np.roll(u, i * b)
    res = jnp.asarray(encoding.encode_slots(ctx.ntt, vecs, pt_scale))
    return to_mont(ctx.ntt, ntt_forward(ctx.ntt, res))


def _bsgs_apply(
    ctx: CkksContext, plan: BsgsPlan, pt_scale: float, ct_x: Ciphertext,
    u_mont, b_res, baby_tables, giant_tables, mode: str = "hoisted",
):
    """The BSGS scoring program body (any leading batch shape on ct_x).

    Three sweeps: baby rotations of the query, the modular contraction of
    the pre-rotated diagonals against the rotation stack, and the giant
    rotate-and-accumulate. All K class scores land in one ciphertext at
    scale ct_scale * pt_scale.

    `mode` selects the baby sweep's decomposition (ISSUE 18):

      "hoisted"   — ONE shared gadget decomposition (`ops.hoisted_digits`)
                    feeds every baby step as a batched inner product +
                    eval permutation (`ops.hoisted_rotations_core`); the
                    serving default. `baby_tables` are
                    `ops.hoisted_rotation_tables`.
      "unhoisted" — the same uncentered decomposition applied step-by-step
                    (coefficient automorphism of the digit polys + per-step
                    NTTs). BITWISE-equal to "hoisted" (exact modular
                    arithmetic on identical digits) — the parity anchor
                    and the honest per-step cost model. `baby_tables` are
                    `stack_rotation_steps`.
      "legacy"    — the original centered-digit `ct_rotate` decomposition
                    (per-step, correction row). Same rotation, different
                    noise bits: equal to the others only after decryption,
                    to tolerance. `baby_tables` are `stack_rotation_steps`.

    Giant rotations act on DISTINCT partial sums, so they stay on the
    legacy per-rotation path in every mode (and stay bitwise-identical
    across the hoisted/unhoisted pair).
    """
    from hefl_tpu.ckks import modular
    from hefl_tpu.ckks.modular import add_mod
    from hefl_tpu.ckks.ntt import ntt_forward, ntt_inverse
    from hefl_tpu.ckks.ops import _keyswitch_coeff

    ntt = ctx.ntt
    p = jnp.asarray(ntt.p)
    pinv = jnp.asarray(ntt.pinv_neg)
    batch_ndim = ct_x.c0.ndim - 2
    g_count = len(plan.giants)

    def rotate(c0_coeff, c1_coeff, src, flip, b_mont, a_mont):
        """One rotation of a COEFFICIENT-domain pair; -> eval-domain."""
        with jax.named_scope(obs_scopes.SERVE_ROTATE):
            pc0 = galois.apply_automorphism(c0_coeff, p, src, flip)
            pc1 = galois.apply_automorphism(c1_coeff, p, src, flip)
        with jax.named_scope(obs_scopes.SERVE_KEYSWITCH):
            k0, k1 = _keyswitch_coeff(ctx, pc1, b_mont, a_mont)
        with jax.named_scope(obs_scopes.SERVE_ROTATE):
            return add_mod(ntt_forward(ntt, pc0), k0, p), k1

    # Hoisting: ONE inverse NTT of the query feeds every baby rotation.
    with jax.named_scope(obs_scopes.SERVE_ROTATE):
        cc0 = ntt_inverse(ntt, ct_x.c0)
        cc1 = ntt_inverse(ntt, ct_x.c1)

    if not plan.baby_steps:
        rots0 = ct_x.c0[None]
        rots1 = ct_x.c1[None]
    elif mode == "hoisted":
        # Shared-prefix sweep: decompose once, serve every step as a
        # batched digit x key product + output permutation.
        with jax.named_scope(obs_scopes.SERVE_HOIST):
            d_eval = ops.hoisted_digits(ctx, cc1)
            r0, r1 = ops.hoisted_rotations_core(
                ctx, ct_x.c0, d_eval, *baby_tables
            )
        rots0 = jnp.concatenate([ct_x.c0[None], r0], axis=0)
        rots1 = jnp.concatenate([ct_x.c1[None], r1], axis=0)
    elif mode == "unhoisted":
        # The bitwise twin: identical uncentered digits, but the
        # automorphism + NTTs re-run per step (the cost hoisting removes).
        with jax.named_scope(obs_scopes.SERVE_HOIST):
            num_r = ctx.num_primes * ctx.ksk_num_digits
            w = ctx.ksk_digit_bits
            mask = jnp.uint32((1 << w) - 1)
            digs = jnp.stack(
                [(cc1 >> jnp.uint32(w * k)) & mask
                 for k in range(ctx.ksk_num_digits)], axis=-2
            )
            comp = digs.reshape(*cc1.shape[:-2], num_r, ctx.n)
            lifted = jnp.broadcast_to(
                comp[..., :, None, :],
                (*cc1.shape[:-2], num_r, ctx.num_primes, ctx.n),
            )

        def unhoisted_stage(carry, inp):
            src, flip, b_mont, a_mont = inp
            with jax.named_scope(obs_scopes.SERVE_HOIST):
                pd = galois.apply_automorphism(lifted, p, src, flip)
                d_eval = ntt_forward(ntt, pd)
                bk, ak = b_mont[:num_r], a_mont[:num_r]
                t0 = modular.mont_mul(d_eval, bk, p, pinv)
                t1 = modular.mont_mul(d_eval, ak, p, pinv)
                k0, k1 = t0[..., 0, :, :], t1[..., 0, :, :]
                for c in range(1, num_r):
                    k0 = add_mod(k0, t0[..., c, :, :], p)
                    k1 = add_mod(k1, t1[..., c, :, :], p)
                pc0 = galois.apply_automorphism(cc0, p, src, flip)
                r0 = add_mod(ntt_forward(ntt, pc0), k0, p)
            return carry, (r0, k1)

        _, (r0, r1) = jax.lax.scan(unhoisted_stage, 0, baby_tables)
        rots0 = jnp.concatenate([ct_x.c0[None], r0], axis=0)
        rots1 = jnp.concatenate([ct_x.c1[None], r1], axis=0)
    else:

        def baby_stage(carry, inp):
            return carry, rotate(cc0, cc1, *inp)

        _, (r0, r1) = jax.lax.scan(baby_stage, 0, baby_tables)
        rots0 = jnp.concatenate([ct_x.c0[None], r0], axis=0)
        rots1 = jnp.concatenate([ct_x.c1[None], r1], axis=0)

    # Giant partial sums: contract the diagonal table against the baby
    # rotation stack, mod p, scanning the baby axis (body compiled once).
    def prod_stage(acc, inp):
        r0, r1, u_j = inp             # r0/r1 [..., L, N]; u_j [G, L, N]
        u_exp = u_j.reshape(
            (g_count,) + (1,) * batch_ndim + u_j.shape[1:]
        )
        with jax.named_scope(obs_scopes.SERVE_SCORE):
            s0 = add_mod(acc[0], modular.mont_mul(r0[None], u_exp, p, pinv), p)
            s1 = add_mod(acc[1], modular.mont_mul(r1[None], u_exp, p, pinv), p)
        return (s0, s1), None

    zeros = jnp.zeros((g_count,) + ct_x.c0.shape, jnp.uint32)
    (s0, s1), _ = jax.lax.scan(
        prod_stage, (zeros, zeros),
        (rots0, rots1, jnp.moveaxis(u_mont, 1, 0)),
    )

    # Giant sweep: the identity-step group seeds the accumulator (no
    # rotation); every other group rotates by its giant step and adds.
    y0, y1 = s0[0], s1[0]
    if plan.giant_steps:

        def giant_stage(carry, inp):
            a0, a1 = carry
            sg0, sg1 = inp[0], inp[1]
            with jax.named_scope(obs_scopes.SERVE_ROTATE):
                gc0 = ntt_inverse(ntt, sg0)
                gc1 = ntt_inverse(ntt, sg1)
            rr0, rr1 = rotate(gc0, gc1, *inp[2:])
            with jax.named_scope(obs_scopes.SERVE_ROTATE):
                return (add_mod(a0, rr0, p), add_mod(a1, rr1, p)), None

        (y0, y1), _ = jax.lax.scan(
            giant_stage, (y0, y1), (s0[1:], s1[1:]) + tuple(giant_tables)
        )

    out = Ciphertext(c0=y0, c1=y1, scale=ct_x.scale * pt_scale)
    with jax.named_scope(obs_scopes.SERVE_SCORE):
        return ops.ct_add_plain(ctx, out, b_res)


@functools.lru_cache(maxsize=16)
def _bsgs_program(
    ctx: CkksContext, plan: BsgsPlan, pt_scale: float, mode: str = "hoisted"
):
    """ONE jitted BSGS scoring program per (context, plan, scale, mode) —
    shared by every batch bucket shape through the jit shape cache."""

    @jax.jit
    def run(ct_x: Ciphertext, u_mont, b_res, baby_tables, giant_tables):
        return _bsgs_apply(
            ctx, plan, pt_scale, ct_x, u_mont, b_res, baby_tables,
            giant_tables, mode,
        )

    return run


def serving_batch_bucket(batch: int) -> int:
    """Next power-of-two batch bucket. `score_many` pads query batches up
    to these, so ANY batch size hits one of log2(max_batch) compiled
    programs instead of compiling per size (the no-new-compile guard)."""
    return 1 << max(0, (int(batch) - 1).bit_length())


class BsgsLinearScorer:
    """Precompiled BSGS private-inference server for a FIXED linear model
    (the serving default; `LinearScorer` keeps the per-class ladder as
    the reference plan).

    Everything per-model is hoisted out of the per-query path at build
    time: the BSGS plan, the stacked automorphism tables + Galois keys
    for every planned step, the pre-rotated diagonal encodings (host
    FFTs), and the bias row. `score` returns ONE ciphertext carrying all
    K class scores (slot m = class m — decrypt with
    `decrypt_class_scores`), at plan.num_keyswitches key-switches per
    sample vs the ladder's K*log2(slots).

    `queries_per_ct` = q > 1 turns on SLOT packing (d and K must fit the
    D = slots/q block): clients pack q feature vectors into one
    ciphertext (`encrypt_query_block`), the diagonals tile q-fold, the
    device program is unchanged, and one pass scores q queries — block r
    of the output holds query r's scores at slots r*D .. r*D+K-1
    (decrypt with `decrypt_class_scores(..., queries_per_ct=q)`). The
    per-QUERY key-switch cost divides by q on top of the BSGS saving.

    `rotation_mode` (ISSUE 18) picks the baby sweep's decomposition — see
    `_bsgs_apply`. The default "hoisted" shares ONE gadget decomposition
    across the whole sweep (`self.hoisted_ntts` forward NTTs vs
    `self.unhoisted_ntts` for the per-step twin); "unhoisted" is its
    bitwise parity anchor; "legacy" keeps the original centered-digit
    per-step plan (equal scores to tolerance only — a different
    decomposition carries different noise bits).
    """

    def __init__(
        self,
        ctx: CkksContext,
        weights: np.ndarray,
        bias: np.ndarray,
        gks: dict[int, GaloisKey],
        pt_scale: float = 2.0**14,
        ct_scale: float | None = None,
        baby: int | None = None,
        queries_per_ct: int = 1,
        rotation_mode: str = "hoisted",
    ):
        if rotation_mode not in ("hoisted", "unhoisted", "legacy"):
            raise ValueError(
                f"rotation_mode must be hoisted|unhoisted|legacy, got "
                f"{rotation_mode!r}"
            )
        weights = np.asarray(weights, np.float64)
        bias = np.asarray(bias, np.float64)
        slots = encoding.num_slots(ctx.ntt)
        q = int(queries_per_ct)
        if q < 1 or slots % q != 0:
            raise ValueError(
                f"queries_per_ct must divide slots={slots}, got {q}"
            )
        block = slots // q
        if weights.ndim != 2 or weights.shape[1] > block:
            raise ValueError(
                f"weights must be [K, d<= {block}] (slots/queries_per_ct), "
                f"got {weights.shape}"
            )
        if bias.shape != (weights.shape[0],):
            raise ValueError(
                f"bias must be [{weights.shape[0]}], got {bias.shape}"
            )
        if weights.shape[0] > block:
            raise ValueError(
                f"{weights.shape[0]} classes exceed the {block}-slot "
                "query block"
            )
        self.ctx = ctx
        self.pt_scale = pt_scale
        self.ct_scale = ctx.scale if ct_scale is None else ct_scale
        self.queries_per_ct = q
        self.rotation_mode = rotation_mode
        self.num_classes, d = weights.shape
        self.plan = bsgs_plan(slots, d, self.num_classes, baby)
        if rotation_mode == "hoisted":
            self._baby_tables = ops.hoisted_rotation_tables(
                ctx, gks, self.plan.baby_steps
            )
        else:
            self._baby_tables = stack_rotation_steps(
                ctx, gks, self.plan.baby_steps
            )
        self._giant_tables = stack_rotation_steps(
            ctx, gks, self.plan.giant_steps
        )
        # The printed, gated hoisting numbers: forward NTTs one score pays
        # in the rotation sweeps under each decomposition.
        rows = ctx.num_primes * ctx.ksk_num_digits
        self.gadget_rows = rows
        self.hoisted_ntts = self.plan.forward_ntts(rows, hoisted=True)
        self.unhoisted_ntts = self.plan.forward_ntts(rows, hoisted=False)
        self._u_mont = _bsgs_diag_tables(
            ctx, self.plan, weights, pt_scale, q
        )
        bz = np.zeros(slots)
        bz.reshape(q, block)[:, : self.num_classes] = bias
        self._b_res = jnp.asarray(
            encoding.encode_slots(ctx.ntt, bz, self.ct_scale * pt_scale)
        )
        self._run = _bsgs_program(ctx, self.plan, pt_scale, rotation_mode)

    def _check_scale(self, ct: Ciphertext) -> None:
        if ct.scale != self.ct_scale:
            raise ValueError(
                f"scorer was built for ct scale {self.ct_scale}, got "
                f"{ct.scale}"
            )

    def score(self, ct_x: Ciphertext) -> Ciphertext:
        """All K class scores of one sample as ONE ciphertext."""
        self._check_scale(ct_x)
        if ct_x.c0.ndim != 2:
            raise ValueError(
                f"score takes one sample [L, N], got {ct_x.c0.shape}; "
                "use score_many for a batch"
            )
        return self._run(
            ct_x, self._u_mont, self._b_res, self._baby_tables,
            self._giant_tables,
        )

    def score_many(self, ct_xs: Ciphertext) -> Ciphertext:
        """Score a whole batch [B, L, N] -> [B] score ciphertexts in one
        device dispatch. The batch is padded to the next power-of-two
        bucket (`serving_batch_bucket`) so arbitrary sizes reuse a small
        set of compiled programs; pad rows are zero ciphertexts and are
        sliced away before returning."""
        self._check_scale(ct_xs)
        if ct_xs.c0.ndim != 3:
            raise ValueError(
                f"score_many needs a batched ciphertext [B, L, N], got "
                f"limbs of shape {ct_xs.c0.shape}; use score() for a "
                "single sample"
            )
        batch = ct_xs.c0.shape[0]
        bucket = serving_batch_bucket(batch)
        if bucket != batch:
            pad = ((0, bucket - batch), (0, 0), (0, 0))
            ct_xs = Ciphertext(
                c0=jnp.pad(ct_xs.c0, pad), c1=jnp.pad(ct_xs.c1, pad),
                scale=ct_xs.scale,
            )
        out = self._run(
            ct_xs, self._u_mont, self._b_res, self._baby_tables,
            self._giant_tables,
        )
        if bucket != batch:
            out = Ciphertext(
                c0=out.c0[:batch], c1=out.c1[:batch], scale=out.scale
            )
        return out


def encrypt_query_block(
    ctx: CkksContext,
    pk: PublicKey,
    xs: np.ndarray,
    key: jax.Array,
    queries_per_ct: int,
) -> Ciphertext:
    """Client-side slot packing for multi-query serving: feature vectors
    [..., q, d] -> one ciphertext per leading index, query r in slots
    [r*D, r*D + d) with D = slots/q. Short batches (fewer than q queries)
    zero-pad; their score blocks decrypt to the bias alone."""
    slots = encoding.num_slots(ctx.ntt)
    q = int(queries_per_ct)
    if q < 1 or slots % q != 0:
        raise ValueError(f"queries_per_ct must divide slots={slots}, got {q}")
    block = slots // q
    xs = np.asarray(xs, np.float64)
    if xs.ndim < 2 or xs.shape[-2] > q or xs.shape[-1] > block:
        raise ValueError(
            f"query block must be [..., <= {q}, <= {block}], got {xs.shape}"
        )
    z = np.zeros(xs.shape[:-2] + (q, block), np.float64)
    z[..., : xs.shape[-2], : xs.shape[-1]] = xs
    z = z.reshape(xs.shape[:-2] + (slots,))
    res = encoding.encode_slots(ctx.ntt, z, ctx.scale)
    return ops.encrypt(ctx, pk, jnp.asarray(res), key)


def decrypt_class_scores(
    ctx: CkksContext,
    sk: SecretKey,
    ct: Ciphertext,
    num_classes: int,
    queries_per_ct: int = 1,
) -> np.ndarray:
    """Owner-side decrypt of a BSGS score ciphertext (batched leading
    axes fine): slots 0..K-1 -> real scores [..., K] in one decrypt.
    With `queries_per_ct` = q > 1 (slot-packed serving) each D-slot block
    carries one query's scores -> [..., q, K]."""
    res = np.asarray(ops.decrypt(ctx, sk, ct))
    z = encoding.decode_slots(ctx.ntt, res, ct.scale)
    q = int(queries_per_ct)
    if q == 1:
        return np.real(z[..., :num_classes])
    block = z.shape[-1] // q
    z = z.reshape(z.shape[:-1] + (q, block))
    return np.real(z[..., :num_classes])


# ---------------------------------------------------------------------------
# Shaped jaxpr probes (ISSUE 12): the static-analysis gate, extended to the
# serving side — `analysis.ranges.certify_inference` proves the
# rotate-and-sum ladder's integer invariants over this mirror.
# ---------------------------------------------------------------------------


def rotation_ladder_range_probe(prime: int, digit_bits: int, num_digits: int):
    """The rotate-and-sum serving ladder's carrier arithmetic as ONE
    traceable loop (analysis.ranges.certify_inference).

    Mirrors, per ladder stage, what `rotate_and_sum_scan`'s body computes
    on each RNS limb — automorphism (a gather through the rotation table
    plus the sign flip, taken at its worst case `(p - x) mod p`; the
    unflipped element shares the interval), the gadget key-switch
    (base-2**w digit decomposition, digit centering, digit x key
    inner-product summed mod p against the Galois key tensors), and the
    rotate+add re-canonicalization — as a `lax.while_loop` over an
    ABSTRACT stage count, so the carried (c0, c1) invariant is proven for
    ANY ladder depth, not the log2(slots) stages one trace happens to
    run.

    The wrapping uint32 Montgomery cores are deliberately NOT mirrored
    bit-for-bit: the probe computes the digit x key product on the int64
    carrier and reduces with `%` (the allowlisted probe modulo), which is
    the REDC canonical-residue CONTRACT — the analyzer proves the product
    fits the exact-integer ceiling and the reduction restores [0, p-1];
    the cores' own wraparound is covered by the lint rules and bitwise
    parity tests, exactly like every other probe in this tree. Trace
    under `jax.enable_x64(True)`. -> (fn, example_args).
    """
    p = int(prime)
    w = int(digit_bits)
    half = 1 << max(w - 1, 0)
    mask = (1 << w) - 1
    m = 4  # coefficients per probe limb; ranges are per-element anyway

    def probe(depth, c0, c1, key_b, key_a, src):
        def cond(state):
            return state[0] > 0

        def body(state):
            remaining, c0, c1 = state
            # Rotation: gather through the automorphism table, sign flip
            # at its worst case (canonical-preserving).
            g0 = jnp.take(c0, src, axis=-1)
            g1 = jnp.take(c1, src, axis=-1)
            pc0 = (p - g0) % p
            pc1 = (p - g1) % p
            # Gadget key-switch: digit-decompose pc1, center, inner-product
            # against the key tensors, modular tree-sum.
            ks0 = jnp.zeros_like(c0)
            ks1 = jnp.zeros_like(c1)
            for kk in range(int(num_digits)):
                digit = (pc1 >> (w * kk)) & mask       # [0, 2**w - 1]
                centered = (digit + (p - half)) % p    # canonical
                ks0 = (ks0 + centered * key_b) % p
                ks1 = (ks1 + centered * key_a) % p
            return remaining - 1, (pc0 + ks0) % p, ks1

        _, c0, c1 = jax.lax.while_loop(cond, body, (depth, c0, c1))
        return c0, c1

    z = np.zeros((m,), np.int64)
    return probe, (np.int64(0), z, z, z, z, np.zeros((m,), np.int64))


def exact_int_probes() -> dict:
    """The serving side's declared exact-integer regions (analysis.lint):
    the ladder probe and the composed two-layer BSGS probe — regions that
    CONTAIN their loops, so carried residues are watched by the no-float /
    no-stray-div rules (the `%` is the allowlisted probe modulo)."""
    fn, args = rotation_ladder_range_probe(2**27 - 39, 9, 3)
    mfn, margs = mlp_bsgs_range_probe(2**27 - 39, 5, 6)
    return {
        "he_inference.rotate_ladder": (fn, args),
        "he_inference.mlp_compose": (mfn, margs),
    }


def _const_eval_residues(ctx: CkksContext, c: np.ndarray, scale: float) -> np.ndarray:
    """Eval-domain RNS residues of constant-in-every-slot plaintexts.

    A constant polynomial evaluates to its constant at every NTT point, so
    the eval-domain representation of encode_slots_const(c, scale) is just
    round(c*scale) mod p_i broadcast over all N points — built here as a
    [..., L, 1] table in one vectorized host pass, no NTT anywhere. The
    whole constant table for an output layer (K·H entries) costs K·H·L
    integer ops on the host.
    """
    coeffs = np.round(np.asarray(c, np.float64) * scale).astype(np.int64)
    p = np.asarray(ctx.ntt.p)[:, 0].astype(np.int64)
    q = ctx.modulus
    if np.any(2 * np.abs(coeffs.astype(object)) >= q):
        raise ValueError(
            f"constant plaintext saturates: |round(c*scale)| up to "
            f"{np.max(np.abs(coeffs))} must stay below q/2 (q~2**{q.bit_length()})"
        )
    return np.mod(coeffs[..., None], p)[..., None].astype(np.uint32)  # [..., L, 1]


def _const_eval_mont(ctx: CkksContext, c: np.ndarray, scale: float) -> np.ndarray:
    """Montgomery lift of `_const_eval_residues` (x * 2**32 mod p), uint32[..., L, 1]."""
    res = _const_eval_residues(ctx, c, scale).astype(np.int64)
    p = np.asarray(ctx.ntt.p)[:, 0].astype(np.int64)[:, None]
    return ((res << 32) % p).astype(np.uint32)  # residues < 2**27: int64-safe


def _sliced_context(ctx: CkksContext) -> CkksContext:
    """The statically-known context `ops.rescale` will return: one limb fewer."""
    return CkksContext(
        ntt=ctx.ntt.slice_limbs(0, ctx.num_primes - 1),
        scale=ctx.scale,
        sigma=ctx.sigma,
        ksk_digit_bits=ctx.ksk_digit_bits,
    )


def _mlp_tail_apply(ctx: CkksContext, pt_scale: float, rescales: int, h, rlk, w2m, b2e):
    """Everything after the hidden linear layer (any leading batch shape on
    the [..., H, L, N] hidden ciphertext): square activation (batched
    ct×ct + relin), `rescales` rescale stages, and the full output layer
    scores_k = Σ_j w2[k,j]·h²_j + b2[k].

    The output layer exploits that each h²_j already holds its value in
    every slot: multiplying by the CONSTANT w2[k,j] is a Montgomery
    pointwise multiply by the broadcast eval-domain constant — no NTT, no
    rotation — and the Σ_j is a modular contraction over the hidden axis.
    Batching is broadcast, not `jax.vmap`, so the relinearization's
    key-switch sees its explicit batch (fused-kernel friendly).
    """
    from hefl_tpu.ckks import modular

    with jax.named_scope(obs_scopes.SERVE_SCORE):
        sq = ops.ct_mul(ctx, h, h, rlk)    # batched over the H axis
        cur = ctx
        for _ in range(rescales):
            cur, sq = ops.rescale(cur, sq)
        p = jnp.asarray(cur.ntt.p)
        pinv = jnp.asarray(cur.ntt.pinv_neg)
        # [K,H,L,1] consts × [..., 1,H,L,N] limbs → [..., K,H,L,N],
        # contract the H axis (-3) mod p.
        t0 = modular.mont_mul(sq.c0[..., None, :, :, :], w2m, p, pinv)
        t1 = modular.mont_mul(sq.c1[..., None, :, :, :], w2m, p, pinv)
        c0, c1 = t0[..., 0, :, :], t1[..., 0, :, :]
        for j in range(1, t0.shape[-3]):   # static H: unrolled modular sum
            c0 = modular.add_mod(c0, t0[..., j, :, :], p)
            c1 = modular.add_mod(c1, t1[..., j, :, :], p)
        c0 = modular.add_mod(c0, jnp.broadcast_to(b2e, c0.shape), p)
    return Ciphertext(c0=c0, c1=c1, scale=sq.scale * pt_scale)


@functools.lru_cache(maxsize=16)
def _mlp_tail_program(ctx: CkksContext, pt_scale: float, rescales: int):
    """ONE jitted program for the per-sample MLP tail — this replaces the
    former K×H-dispatch host loop (plus K×H host encodes), the same
    treatment `_linear_program` gives the linear path."""

    @jax.jit
    def run(h: Ciphertext, rlk, w2m, b2e):
        return _mlp_tail_apply(ctx, pt_scale, rescales, h, rlk, w2m, b2e)

    return run


@functools.lru_cache(maxsize=16)
def _mlp_tail_batch_program(ctx: CkksContext, pt_scale: float, rescales: int):
    """Batched-serving MLP tail: one jitted program over a whole batch of
    hidden-layer ciphertexts (leading axis B, broadcast batching)."""

    @jax.jit
    def run(hs: Ciphertext, rlk, w2m, b2e):
        return _mlp_tail_apply(ctx, pt_scale, rescales, hs, rlk, w2m, b2e)

    return run


def encrypted_mlp(
    ctx: CkksContext,
    ct_x: Ciphertext,
    w1: np.ndarray,
    b1: np.ndarray,
    w2: np.ndarray,
    b2: np.ndarray,
    gks: dict[int, GaloisKey],
    rlk,
    pt_scale: float = 2.0**14,
    rescales: int = 2,
) -> tuple[CkksContext, list[Ciphertext]]:
    """Private 1-hidden-layer MLP: scores = W2 · (W1 x + b1)² + b2, computed
    entirely under encryption — a DEPTH-2 homomorphic circuit.

    The square is the classic HE-friendly activation (CryptoNets): it is the
    one nonlinearity CKKS evaluates exactly, via ct × ct + relinearization.
    Level budget (why this needs `ctx` with num_primes >= 3 + rescales):

      1. hidden pre-activations   H × [ct×plain W1 row, rotate-and-sum,
                                  bias] — key-switches at FULL level, so the
                                  server's rotation keys work unchanged;
      2. square activation        ct_mul(h, h, rlk) at full level
                                  (scale Δ·pt_scale squared — the modulus
                                  must hold it, which ct_mul guards);
      3. `rescales` × rescale     shed limbs / renormalize the scale so the
                                  output layer and the f64 slot decode stay
                                  in exact range;
      4. output layer             scores_k = Σ_j W2[k,j]·h²_j + b2[k] as
                                  eval-domain constant multiplies + a
                                  modular contraction over H — no rotations
                                  (each h²_j already holds its value in
                                  every slot), no NTTs (a constant
                                  polynomial is constant at every NTT
                                  point).

    Steps 2–4 run as ONE jitted device program (`_mlp_tail_program`); the
    hidden layer is `_linear_program` — two dispatches total per sample,
    independent of H and K.

    Returns (shrunken context, K score ciphertexts); decrypt with
    `decrypt_scores(sub_ctx, slice_secret_key(sk, sub_ctx.num_primes), ...)`.
    The server holds only (ctx, rotation keys, rlk) and its plaintext
    weights; it never sees x, h, or the scores.
    """
    scorer = MlpScorer(
        ctx, w1, b1, w2, b2, gks, rlk, pt_scale, rescales, ct_scale=ct_x.scale
    )
    return scorer.sub_ctx, scorer.score(ct_x)


class MlpScorer:
    """Precompiled private-inference server for a FIXED depth-2 MLP.

    The MlpScorer analog of `LinearScorer`: all per-model work — hidden
    layer slot encodes, the statically-derived post-rescale context, and
    the output layer's eval-domain constant tables — happens once at
    construction; every `score` call is exactly two cached jitted device
    dispatches (`_linear_program` + `_mlp_tail_program`), independent of
    d, H, and K. Decrypt results against `self.sub_ctx` with
    `slice_secret_key(sk, self.sub_ctx.num_primes)`.
    """

    def __init__(
        self,
        ctx: CkksContext,
        w1: np.ndarray,
        b1: np.ndarray,
        w2: np.ndarray,
        b2: np.ndarray,
        gks: dict[int, GaloisKey],
        rlk,
        pt_scale: float = 2.0**14,
        rescales: int = 2,
        ct_scale: float | None = None,
    ):
        w1 = np.asarray(w1, np.float64)
        w2 = np.asarray(w2, np.float64)
        b2 = np.asarray(b2, np.float64)
        # Validate the OUTPUT layer's shapes up front (w1/b1 are validated
        # by _encode_linear_model before any ciphertext op): malformed input
        # should fail in microseconds, not after H squarings + rescales.
        if w1.ndim != 2:
            raise ValueError(f"w1 must be [H, d], got {w1.shape}")
        if w2.ndim != 2 or w2.shape[1] != w1.shape[0]:
            raise ValueError(f"w2 must be [K, {w1.shape[0]}], got {w2.shape}")
        if b2.shape != (w2.shape[0],):
            raise ValueError(f"b2 must be [{w2.shape[0]}], got {b2.shape}")
        self.ctx = ctx
        self.pt_scale = pt_scale
        self.ct_scale = ctx.scale if ct_scale is None else ct_scale
        self._ladder = stack_rotation_ladder(ctx, gks)   # sole key copy kept
        self.rlk = rlk
        self.num_classes = int(w2.shape[0])
        self._rescales = rescales
        self._w1_res, self._b1_res = _encode_linear_model(
            ctx, w1, b1, self.ct_scale, pt_scale
        )
        # Statically derive the post-rescale context and scales so the
        # output layer's constants are host-encoded at exactly the
        # levels/scales the device program will produce.
        cur = ctx
        h_scale = self.ct_scale * pt_scale
        sq_scale = h_scale * h_scale
        p_np = np.asarray(ctx.ntt.p)[:, 0]
        for i in range(rescales):
            sq_scale /= float(p_np[ctx.num_primes - 1 - i])
            cur = _sliced_context(cur)
        self.sub_ctx = cur
        self._w2m = jnp.asarray(_const_eval_mont(cur, w2, pt_scale))  # [K,H,L',1]
        self._b2e = jnp.asarray(
            _const_eval_residues(cur, b2, sq_scale * pt_scale)        # [K,L',1]
        )
        self._lin = _linear_program(ctx, pt_scale)
        self._tail = _mlp_tail_program(ctx, pt_scale, rescales)

    def score_batched(self, ct_x: Ciphertext) -> Ciphertext:
        """K class scores as ONE batched ciphertext at `self.sub_ctx`'s level."""
        if ct_x.scale != self.ct_scale:
            raise ValueError(
                f"scorer was built for ct scale {self.ct_scale}, got {ct_x.scale}"
            )
        h = self._lin(ct_x, self._w1_res, self._b1_res, self._ladder)
        return self._tail(h, self.rlk, self._w2m, self._b2e)

    def score(self, ct_x: Ciphertext) -> list[Ciphertext]:
        batched = self.score_batched(ct_x)
        return [
            Ciphertext(c0=batched.c0[k], c1=batched.c1[k], scale=batched.scale)
            for k in range(self.num_classes)
        ]

    def score_many(self, ct_xs: Ciphertext) -> Ciphertext:
        """Score a whole BATCH of encrypted samples in two device
        dispatches -> [B, K] batched score ciphertext at `self.sub_ctx`'s
        level. Decrypt with `decrypt_score_matrix` against
        `slice_secret_key(sk, self.sub_ctx.num_primes)`."""
        if ct_xs.scale != self.ct_scale:
            raise ValueError(
                f"scorer was built for ct scale {self.ct_scale}, got {ct_xs.scale}"
            )
        if ct_xs.c0.ndim != 3:
            raise ValueError(
                f"score_many needs a batched ciphertext [B, L, N], got limbs of "
                f"shape {ct_xs.c0.shape}; use score() for a single sample"
            )
        hs = _linear_batch_program(self.ctx, self.pt_scale)(
            ct_xs, self._w1_res, self._b1_res, self._ladder
        )
        return _mlp_tail_batch_program(self.ctx, self.pt_scale, self._rescales)(
            hs, self.rlk, self._w2m, self._b2e
        )


# ---------------------------------------------------------------------------
# Composed diagonal plans (ISSUE 18): the MLP hidden layer as BSGS. The
# ladder MlpScorer runs H per-class rotate-and-sum ladders for the hidden
# layer; BsgsMlpScorer replaces them with TWO composed Halevi-Shoup plans —
# layer-1 BSGS lands all H hidden pre-activations in slots 0..H-1 of ONE
# ciphertext (slots >= H are exactly zero by the diagonal construction), the
# square activation is a single ct_mul + relinearization (vs H of them),
# and after `rescales` rescale stages layer-2 BSGS reads those same slots as
# its d=H feature block. No re-layout between layers: the BSGS output
# layout IS the BSGS input layout.
# ---------------------------------------------------------------------------


def mlp_sub_context(ctx: CkksContext, rescales: int) -> CkksContext:
    """The statically-known post-rescale context a depth-2 MLP program ends
    at — layer-2 keys/tables must be built against THIS context."""
    cur = ctx
    for _ in range(int(rescales)):
        cur = _sliced_context(cur)
    return cur


def bsgs_mlp_plans(
    slots: int, d: int, hidden: int, num_classes: int,
    baby1: int | None = None, baby2: int | None = None,
) -> tuple[BsgsPlan, BsgsPlan]:
    """The two composed plans of a BSGS MLP: (d -> hidden) at full level,
    (hidden -> num_classes) at the post-rescale level. Callers use these
    to generate the two Galois-key bundles BEFORE building the scorer
    (layer 2's keys live on `mlp_sub_context(ctx, rescales)` under
    `slice_secret_key(sk, sub_ctx.num_primes)`)."""
    return (
        bsgs_plan(slots, d, hidden, baby1),
        bsgs_plan(slots, hidden, num_classes, baby2),
    )


@functools.lru_cache(maxsize=16)
def _mlp_bsgs_program(
    ctx: CkksContext, plan1: BsgsPlan, plan2: BsgsPlan, pt_scale: float,
    rescales: int, mode: str,
):
    """ONE jitted program for the whole composed MLP: layer-1 BSGS ->
    square (ct_mul + relin) -> rescales -> layer-2 BSGS. Three
    key-switch sweeps + one relinearization, two diagonal contractions,
    one compiled dispatch."""

    @jax.jit
    def run(
        ct_x: Ciphertext, rlk, u1, b1_res, baby1, giant1,
        u2, b2_res, baby2, giant2,
    ):
        h = _bsgs_apply(
            ctx, plan1, pt_scale, ct_x, u1, b1_res, baby1, giant1, mode
        )
        with jax.named_scope(obs_scopes.SERVE_SCORE):
            sq = ops.ct_mul(ctx, h, h, rlk)
        cur = ctx
        for _ in range(rescales):
            with jax.named_scope(obs_scopes.SERVE_SCORE):
                cur, sq = ops.rescale(cur, sq)
        return _bsgs_apply(
            cur, plan2, pt_scale, sq, u2, b2_res, baby2, giant2, mode
        )

    return run


class BsgsMlpScorer:
    """Precompiled depth-2 MLP server on COMPOSED diagonal plans
    (ISSUE 18): scores = W2 · (W1 x + b1)² + b2 with both linear layers as
    BSGS sweeps riding the hoisted-rotation fast path.

    vs `MlpScorer` (the ladder MLP): the hidden layer drops from
    H·log2(slots) ladder key-switches + H squarings to
    plan1.num_keyswitches + ONE squaring, and the output layer's
    constant-multiply contraction becomes a second diagonal plan (which,
    unlike the constant path, also works when hidden values must move
    between slots). Same circuit, same depth, same `rescales` budget —
    the decrypted scores match the ladder MLP to noise tolerance
    (different rotation sets carry different noise bits; the BITWISE
    anchor is rotation_mode "hoisted" vs "unhoisted", which share exact
    arithmetic — see `_bsgs_apply`).

    Keys: `gks1` on `ctx` covers plan1.rotation_steps_needed; `gks2` on
    `mlp_sub_context(ctx, rescales)` (generated under
    `slice_secret_key(sk, sub_ctx.num_primes)`) covers plan2's. Decrypt
    with `decrypt_class_scores(self.sub_ctx, sliced_sk, out, K)`.
    """

    def __init__(
        self,
        ctx: CkksContext,
        w1: np.ndarray,
        b1: np.ndarray,
        w2: np.ndarray,
        b2: np.ndarray,
        gks1: dict[int, GaloisKey],
        rlk,
        gks2: dict[int, GaloisKey],
        pt_scale: float = 2.0**14,
        rescales: int = 2,
        ct_scale: float | None = None,
        baby1: int | None = None,
        baby2: int | None = None,
        rotation_mode: str = "hoisted",
    ):
        if rotation_mode not in ("hoisted", "unhoisted", "legacy"):
            raise ValueError(
                f"rotation_mode must be hoisted|unhoisted|legacy, got "
                f"{rotation_mode!r}"
            )
        w1 = np.asarray(w1, np.float64)
        b1 = np.asarray(b1, np.float64)
        w2 = np.asarray(w2, np.float64)
        b2 = np.asarray(b2, np.float64)
        slots = encoding.num_slots(ctx.ntt)
        if w1.ndim != 2 or w1.shape[1] > slots:
            raise ValueError(f"w1 must be [H, d<= {slots}], got {w1.shape}")
        if b1.shape != (w1.shape[0],):
            raise ValueError(f"b1 must be [{w1.shape[0]}], got {b1.shape}")
        if w2.ndim != 2 or w2.shape[1] != w1.shape[0]:
            raise ValueError(f"w2 must be [K, {w1.shape[0]}], got {w2.shape}")
        if b2.shape != (w2.shape[0],):
            raise ValueError(f"b2 must be [{w2.shape[0]}], got {b2.shape}")
        hidden = int(w1.shape[0])
        if hidden > slots:
            raise ValueError(f"{hidden} hidden units exceed {slots} slots")
        self.ctx = ctx
        self.pt_scale = pt_scale
        self.ct_scale = ctx.scale if ct_scale is None else ct_scale
        self.rotation_mode = rotation_mode
        self.num_classes = int(w2.shape[0])
        self._rescales = int(rescales)
        self.plan1, self.plan2 = bsgs_mlp_plans(
            slots, w1.shape[1], hidden, self.num_classes, baby1, baby2
        )
        self.rlk = rlk
        self.sub_ctx = mlp_sub_context(ctx, rescales)
        # Statically-derived scales, mirroring MlpScorer: the hidden
        # ciphertext squares to h_scale**2, each rescale divides by the
        # dropped prime, layer 2 multiplies by pt_scale once more.
        h_scale = self.ct_scale * pt_scale
        sq_scale = h_scale * h_scale
        p_np = np.asarray(ctx.ntt.p)[:, 0]
        for i in range(self._rescales):
            sq_scale /= float(p_np[ctx.num_primes - 1 - i])
        self.sq_scale = sq_scale

        def tables(c, plan, gks, m):
            if m == "hoisted":
                baby = ops.hoisted_rotation_tables(c, gks, plan.baby_steps)
            else:
                baby = stack_rotation_steps(c, gks, plan.baby_steps)
            return baby, stack_rotation_steps(c, gks, plan.giant_steps)

        self._baby1, self._giant1 = tables(ctx, self.plan1, gks1, rotation_mode)
        self._baby2, self._giant2 = tables(
            self.sub_ctx, self.plan2, gks2, rotation_mode
        )
        self._u1 = _bsgs_diag_tables(ctx, self.plan1, w1, pt_scale, 1)
        self._u2 = _bsgs_diag_tables(self.sub_ctx, self.plan2, w2, pt_scale, 1)
        bz1 = np.zeros(slots)
        bz1[:hidden] = b1
        self._b1_res = jnp.asarray(
            encoding.encode_slots(ctx.ntt, bz1, h_scale)
        )
        bz2 = np.zeros(slots)
        bz2[: self.num_classes] = b2
        self._b2_res = jnp.asarray(
            encoding.encode_slots(self.sub_ctx.ntt, bz2, sq_scale * pt_scale)
        )
        # The printed, gated hoisting numbers for the COMPOSED circuit.
        rows1 = ctx.num_primes * ctx.ksk_num_digits
        rows2 = self.sub_ctx.num_primes * self.sub_ctx.ksk_num_digits
        self.hoisted_ntts = (
            self.plan1.forward_ntts(rows1, True)
            + self.plan2.forward_ntts(rows2, True)
        )
        self.unhoisted_ntts = (
            self.plan1.forward_ntts(rows1, False)
            + self.plan2.forward_ntts(rows2, False)
        )
        self._run = _mlp_bsgs_program(
            ctx, self.plan1, self.plan2, pt_scale, self._rescales,
            rotation_mode,
        )

    @property
    def num_keyswitches(self) -> int:
        """Key-switches per score: both plans' sweeps + the relinearization."""
        return self.plan1.num_keyswitches + self.plan2.num_keyswitches + 1

    def _check_scale(self, ct: Ciphertext) -> None:
        if ct.scale != self.ct_scale:
            raise ValueError(
                f"scorer was built for ct scale {self.ct_scale}, got "
                f"{ct.scale}"
            )

    def score(self, ct_x: Ciphertext) -> Ciphertext:
        """All K class scores of one sample as ONE ciphertext at
        `self.sub_ctx`'s level (slot k = class k)."""
        self._check_scale(ct_x)
        if ct_x.c0.ndim != 2:
            raise ValueError(
                f"score takes one sample [L, N], got {ct_x.c0.shape}; "
                "use score_many for a batch"
            )
        return self._run(
            ct_x, self.rlk, self._u1, self._b1_res, self._baby1,
            self._giant1, self._u2, self._b2_res, self._baby2, self._giant2,
        )

    def score_many(self, ct_xs: Ciphertext) -> Ciphertext:
        """Score a whole batch [B, L, N] in one device dispatch, padded to
        the power-of-two bucket like `BsgsLinearScorer.score_many`."""
        self._check_scale(ct_xs)
        if ct_xs.c0.ndim != 3:
            raise ValueError(
                f"score_many needs a batched ciphertext [B, L, N], got "
                f"limbs of shape {ct_xs.c0.shape}; use score() for a "
                "single sample"
            )
        batch = ct_xs.c0.shape[0]
        bucket = serving_batch_bucket(batch)
        if bucket != batch:
            pad = ((0, bucket - batch), (0, 0), (0, 0))
            ct_xs = Ciphertext(
                c0=jnp.pad(ct_xs.c0, pad), c1=jnp.pad(ct_xs.c1, pad),
                scale=ct_xs.scale,
            )
        out = self._run(
            ct_xs, self.rlk, self._u1, self._b1_res, self._baby1,
            self._giant1, self._u2, self._b2_res, self._baby2, self._giant2,
        )
        if bucket != batch:
            out = Ciphertext(
                c0=out.c0[:batch], c1=out.c1[:batch], scale=out.scale
            )
        return out


def mlp_bsgs_range_probe(prime: int, digit_bits: int, num_digits: int):
    """The two-layer composed BSGS circuit's carrier arithmetic as a
    traceable mirror (analysis.ranges.certify_inference, ISSUE 18).

    Mirrors, per RNS limb, what `_mlp_bsgs_program` computes: a layer-1
    HOISTED sweep (uncentered shared digits, digit x key products, eval
    permutation) as a `lax.while_loop` over an abstract step count, the
    square activation's Montgomery-contract products (d0/d1/d2 of
    `ops.ct_mul` at canonical inputs), the relinearization's centered
    gadget key-switch of d2, the rescale stage's subtract-and-scale
    ((x - rep) * p_last^{-1} mod p, at a canonical stand-in for the
    dropped limb's representative), and a layer-2 hoisted sweep on the
    result. Both sweeps are abstract-depth loops, so the carried
    invariants hold for ANY plan geometry. Int64 carrier, `%` as the
    allowlisted probe modulo; trace under `jax.enable_x64(True)`.
    -> (fn, example_args).
    """
    p = int(prime)
    w = int(digit_bits)
    half = 1 << max(w - 1, 0)
    mask = (1 << w) - 1
    m = 4  # coefficients per probe limb; ranges are per-element anyway

    def hoisted_sweep(steps, x0, x1, key_b, key_a, perm):
        digits = [((x1 >> (w * k)) & mask) for k in range(int(num_digits))]

        def cond(state):
            return state[0] > 0

        def body(state):
            remaining, a0, a1 = state
            k0 = jnp.zeros_like(x0)
            k1 = jnp.zeros_like(x1)
            for k in range(int(num_digits)):
                k0 = (k0 + digits[k] * key_b) % p
                k1 = (k1 + digits[k] * key_a) % p
            r0 = jnp.take((x0 + k0) % p, perm, axis=-1)
            r1 = jnp.take(k1, perm, axis=-1)
            return remaining - 1, (a0 + r0) % p, (a1 + r1) % p

        _, a0, a1 = jax.lax.while_loop(
            cond, body, (steps, jnp.zeros_like(x0), jnp.zeros_like(x1))
        )
        return a0, a1

    def probe(steps1, steps2, c0, c1, key_b, key_a, perm, rs_inv):
        # Layer 1: hoisted BSGS sweep.
        h0, h1 = hoisted_sweep(steps1, c0, c1, key_b, key_a, perm)
        # Square activation: ct_mul's d0/d1/d2 Montgomery-contract mirror.
        d0 = (h0 * h0) % p
        d1 = ((h0 * h1) % p + (h1 * h0) % p) % p
        d2 = (h1 * h1) % p
        # Relinearization: centered gadget key-switch of d2 (the
        # keyswitch_gadget_probe body, inline).
        k0 = jnp.zeros_like(d2)
        k1 = jnp.zeros_like(d2)
        for k in range(int(num_digits)):
            digit = (d2 >> (w * k)) & mask
            centered = (digit + (p - half)) % p
            k0 = (k0 + centered * key_b) % p
            k1 = (k1 + centered * key_a) % p
        s0 = (d0 + (k0 + key_b) % p) % p
        s1 = (d1 + (k1 + key_a) % p) % p
        # Rescale: (x - rep) * p_last^{-1} mod p, rep canonical (the
        # dropped limb's representative re-embedded under the head prime).
        rep = jnp.take(s0, perm, axis=-1)   # canonical stand-in
        s0 = (((s0 + (p - rep)) % p) * rs_inv) % p
        s1 = (((s1 + (p - rep)) % p) * rs_inv) % p
        # Layer 2: hoisted BSGS sweep on the rescaled hidden ciphertext.
        y0, y1 = hoisted_sweep(steps2, s0, s1, key_b, key_a, perm)
        return y0, y1

    z = np.zeros((m,), np.int64)
    return probe, (
        np.int64(0), np.int64(0), z, z, z, z, np.zeros((m,), np.int64), z,
    )
