"""FedBit-style quantization + bit-interleaving for CKKS slot packing.

The coefficient-packed pipeline (ckks/packing.py) spends one float32 weight
per ring coefficient, so every HE phase and every byte on the wire scales
with ``n_ct = ceil(total / N)``. Client *updates* (trained weights minus the
round's global weights) carry far less information than a float32: FedBit's
cross-layer co-design (PAPERS.md) quantizes them to ``b`` bits and
bit-interleaves ``k`` quantized coefficients into each slot, cutting
``n_ct`` — and with it encrypt/psum/decrypt work and uplink bytes — by the
packing factor ``k``.

This module holds the two HE-free halves of that co-design:

  * **Symmetric quantization** — ``q = clip(round(x / step), ±qmax)`` with
    ``qmax = 2**(b-1) - 1`` and ``step = clip / qmax``. ``step`` may be a
    scalar or any broadcastable array (per-tensor steps: broadcast each
    tensor's step over its span of the raveled flat vector), so per-tensor
    clips are first-class. Values beyond the clip SATURATE (exactly like the
    CKKS encoder envelope, encoding.ENCODE_BOUND) and `saturation_count`
    reports how many did — the packed analog of `encode_overflow_count`.

  * **Bit-interleaving with carry-free-addition headroom** — ``k`` shifted
    quantized values per slot::

        field_bits = b + ceil(log2(C))          # C = max summed clients
        v = sum_j u_j << (guard + j*field_bits) # u_j = q_j + qmax  (>= 0)

    Each field is ``ceil(log2(C))`` bits wider than a single value, so the
    homomorphic sum of up to C clients' slots never carries across fields,
    and the bottom ``guard`` bits absorb the CKKS decrypt noise (the sum is
    recovered by one rounding shift, bit-exact while |noise| < 2**(guard-1)).
    The packed integer must stay below BOTH q/2 (centered mod-q decode) and
    2**62 (the exact hi/lo integer encode + int64 digit recombination), so

        k_max = floor(log2(q_headroom) / field_bits),
        log2(q_headroom) = min(floor(log2 q) - 1, 62) - guard_eff

    with ``guard_eff = guard_bits + ceil(log2(C))`` (noise also sums over
    clients). `max_interleave` computes it; `PackingConfig.interleave = 0`
    means "use k_max".

Offsets compose with partial participation: a masked-out client's zeroed
ciphertext limbs contribute 0 (not ``qmax``), so the unpack subtracts
``surviving * qmax`` per field using the round's `RoundMeta.surviving` —
the same public count `decrypt_average` already uses as its denominator.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

# Exactness ceiling of the packed integer, independent of the ring:
#  * the hi/lo split encode (encoding.encode_packed) carries v = hi*2**31+lo
#    with hi < 2**31  ->  v < 2**62;
#  * the int64 mixed-radix recombination (encoding.decode_int_center) is
#    exact two's-complement for |v| < 2**63.
MAX_PACKED_BITS = 62


def qmax(bits: int) -> int:
    """Largest quantized magnitude at b bits (symmetric, zero-centered)."""
    return (1 << (bits - 1)) - 1


def symmetric_step(clip, bits: int):
    """Quantization step for a symmetric b-bit grid covering [-clip, clip]."""
    return clip / qmax(bits)


@dataclasses.dataclass(frozen=True)
class PackingConfig:
    """Quantized-packing knobs (frozen/hashable: rides in ExperimentConfig
    and in the lru_cached round-program factory key).

    bits:         quantization width b (0 disables packing entirely — the
                  historical one-float-per-coefficient path, bit-for-bit).
    interleave:   coefficients per slot k (0 = auto: the headroom-formula
                  maximum for the ring / client count — `max_interleave`).
    clip:         symmetric clip bound on a client's UPDATE (trained minus
                  global weights); |update| > clip saturates and is counted
                  (the packed analog of encode_overflow). Updates, not
                  weights: deltas are small and near-zero-centered, so a
                  b-bit grid spends its levels where the signal is.
                  A SCALAR applies one grid to every coefficient (the
                  historical path, bit-for-bit); a TUPLE is a per-tensor
                  clip schedule — one bound per parameter-tree leaf, in
                  ravel order, each tensor quantized on its own grid
                  (`PackedSpec.for_params` validates the length against
                  the model template and threads the per-coefficient
                  steps through pack/unpack).
    guard_bits:   low bits reserved per slot for CKKS decrypt noise (the
                  effective guard adds ceil(log2(C)) for the client sum).
    error_budget: declared max |packed - unpacked| error per averaged
                  coefficient. 0 = auto: step/2 + 1e-4 (each client's
                  quantization error is <= step/2, averaging cannot exceed
                  it; the margin covers the unpacked reference's own CKKS
                  decode error). Tests and the chaos gate assert against
                  whatever is declared here.
    error_feedback:
                  residual-carrying quantization (ISSUE 19): each client
                  keeps a per-coefficient residual, adds it to the update
                  BEFORE quantizing, and stores back the quantization
                  error (`ef_quantize`). The signal a b-bit grid cannot
                  express in round r re-enters the quantizer in round
                  r+1, so the MULTI-round quantization error stays O(step)
                  instead of accumulating — which is what makes b in
                  {2, 4} (and their ~2x deeper interleave from the same
                  headroom formula) usable. The residual state lives in
                  the STREAMING engine (fl.stream.StreamEngine holds the
                  per-client rows across rounds; the batched one-shot
                  round has nowhere to carry it and refuses). Refused in
                  combination with dp: the residual carries one round's
                  clipped-and-noised signal into the next upload, so a
                  client's round-r data influences round r+1's release —
                  per-round sensitivity accounting and cohort-subsampling
                  amplification both break (same hazard class as
                  staleness carry; fl.stream pins the refusal).
    """

    bits: int = 0
    interleave: int = 0
    clip: "float | tuple[float, ...]" = 0.5
    guard_bits: int = 16
    error_budget: float = 0.0
    error_feedback: bool = False

    def __post_init__(self):
        if self.bits and not 2 <= self.bits <= 16:
            raise ValueError(
                f"PackingConfig.bits={self.bits}: must be 0 (disabled) or "
                "2..16 (one sign bit + at least one magnitude bit; beyond "
                "16 the packing factor cannot beat the float path)"
            )
        if self.interleave < 0:
            raise ValueError("PackingConfig.interleave must be >= 0 (0 = auto)")
        if isinstance(self.clip, (list, tuple)):
            # Coerce to a tuple so the config stays hashable (it rides in
            # ExperimentConfig and the compile-once factory cache keys).
            object.__setattr__(
                self, "clip", tuple(float(c) for c in self.clip)
            )
            if self.bits and (
                not self.clip or any(c <= 0 for c in self.clip)
            ):
                raise ValueError(
                    "PackingConfig.clip: a per-tensor clip schedule needs "
                    "at least one entry, every entry > 0"
                )
        elif self.bits and self.clip <= 0:
            raise ValueError("PackingConfig.clip must be > 0")
        if self.bits and not 4 <= self.guard_bits <= 30:
            raise ValueError(
                f"PackingConfig.guard_bits={self.guard_bits}: need 4..30 "
                "(too small loses low fields to decrypt noise; too large "
                "starves the payload)"
            )
        if self.error_feedback and not self.bits:
            raise ValueError(
                "PackingConfig.error_feedback carries the QUANTIZER's "
                "residual; it is meaningless without packing (bits=0) — "
                "set bits (2 or 4 are the intended low-bit grids)"
            )

    @property
    def enabled(self) -> bool:
        return self.bits > 0

    @property
    def per_tensor(self) -> bool:
        """True when `clip` is a per-tensor schedule (tuple), not a scalar."""
        return isinstance(self.clip, tuple)

    @property
    def step(self) -> "float | tuple[float, ...]":
        """Quantization step(s): scalar clip -> one float (the historical
        contract, bit-for-bit); per-tensor clips -> the matching tuple."""
        if self.per_tensor:
            return tuple(
                float(symmetric_step(c, self.bits)) for c in self.clip
            )
        return float(symmetric_step(self.clip, self.bits))


def field_bits(bits: int, clients: int) -> int:
    """Width of one interleaved field: b payload bits plus ceil(log2(C))
    carry-free-addition headroom so a sum over <= C clients never crosses
    into the next field."""
    return bits + max(int(clients) - 1, 0).bit_length()


def payload_bits(modulus: int, guard: int) -> int:
    """Usable packed-integer bits for a ring modulus q and a noise guard:
    min(floor(log2 q) - 1, 62) - guard (centered-decode q/2 ceiling and the
    int64-exactness ceiling, whichever binds)."""
    return min(modulus.bit_length() - 2, MAX_PACKED_BITS) - guard


def max_interleave(modulus: int, bits: int, clients: int, guard_bits: int) -> int:
    """The headroom-formula packing factor:
    k = floor(log2(q_headroom) / (b + ceil(log2 C))).

    The closed-form k is cross-checked against the jaxpr range analysis
    (`analysis.ranges.certify_packing`, ISSUE 8) on every call: two
    independent derivations of the same carry-free invariant that can
    never disagree silently. A divergence is a BUG in one of them, not a
    configuration error, and raises RuntimeError loudly."""
    guard_eff = guard_bits + max(int(clients) - 1, 0).bit_length()
    avail = payload_bits(modulus, guard_eff)
    k = avail // field_bits(bits, clients)
    if k < 1:
        raise ValueError(
            f"no packing headroom: {avail} payload bits cannot hold one "
            f"{field_bits(bits, clients)}-bit field (bits={bits}, "
            f"clients={clients}, guard={guard_bits}); lower bits/guard or "
            "add RNS primes"
        )
    from hefl_tpu.analysis import ranges as _ranges

    cert = _ranges.certify_packing(
        int(modulus), bits, k, int(clients), guard_bits
    )
    if not cert.ok:
        raise RuntimeError(
            "headroom formula and range analysis disagree: the formula's "
            f"k={k} failed static certification — {cert.summary()} — this "
            "is a bug in one of the two derivations, not a config error"
        )
    return k


# ---------------------------------------------------------------------------
# Quantizer (jittable; step may be scalar or broadcastable per-tensor array).
# ---------------------------------------------------------------------------


def quantize(x: jnp.ndarray, step, bits: int) -> jnp.ndarray:
    """float -> int32 symmetric b-bit code, saturating at +/-qmax."""
    qm = qmax(bits)
    q = jnp.clip(jnp.round(x / step), -qm, qm)
    return q.astype(jnp.int32)


def dequantize(q: jnp.ndarray, step) -> jnp.ndarray:
    """int code -> float32 value on the quantization grid."""
    return q.astype(jnp.float32) * jnp.asarray(step, jnp.float32)


def ef_quantize(
    x: jnp.ndarray, residual: jnp.ndarray, step, bits: int
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Error-feedback quantization (ISSUE 19): quantize `x + residual` and
    return the new residual — the part of the carried signal the b-bit
    grid could not express this round.

        q           = quantize(x + residual)         # int32 in [-qmax, qmax]
        residual'   = (x + residual) - dequantize(q)

    While the carried value stays inside the clip, |residual'| <= step/2;
    a saturating coefficient parks its excess in the residual instead of
    losing it, so the signal re-enters the quantizer next round. The codes
    are CLIPPED exactly like the plain quantizer's, so the carry-free
    interleave invariant (`certify_packing`) is untouched by error
    feedback — the wire sees the same [-qmax, qmax] alphabet either way.
    Jit-safe; `step` may be scalar or per-tensor broadcastable.
    """
    carried = x.astype(jnp.float32) + residual.astype(jnp.float32)
    q = quantize(carried, step, bits)
    return q, carried - dequantize(q, step)


def saturation_count(x: jnp.ndarray, step, bits: int) -> jnp.ndarray:
    """How many of `x` saturate the b-bit grid at this step (jittable
    diagnostic, the packed analog of `encoding.encode_overflow_count`).
    Non-finite values count: they quantize to garbage and MUST be surfaced
    (the masked engine's NaN filter excludes such clients anyway)."""
    scaled = x / step
    bad = ~jnp.isfinite(scaled) | (jnp.abs(scaled) > qmax(bits) + 0.5)
    return jnp.sum(bad, dtype=jnp.int32)


# ---------------------------------------------------------------------------
# Bit-interleave <-> deinterleave. The packed integer is carried as a
# (hi, lo) uint32 pair with v = hi * 2**31 + lo (hi, lo < 2**31) — the same
# two-part split the float encoder uses, but built with pure integer ops so
# it is EXACT for the full 62-bit range (a float32 round-trip would destroy
# bits past the 24-bit mantissa).
# ---------------------------------------------------------------------------

_LO_BITS = 31
_LO_MASK = (1 << _LO_BITS) - 1


def interleave_fields(
    u: jnp.ndarray, k: int, fbits: int, guard: int
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """uint32 fields [..., k, n] -> (hi, lo) uint32 [..., n].

    Field j (< 2**fbits) lands at bit offset guard + j*fbits. Fields are
    masked to their width first (bit hygiene: a poisoned client's garbage
    code must not bleed into neighbors before the masked engine zeroes its
    ciphertext), and since offsets are disjoint the combine is pure OR —
    no carries, jit-safe, unrolled over the static k.
    """
    total = guard + k * fbits
    if total > MAX_PACKED_BITS:
        raise ValueError(
            f"interleave_fields: guard + k*field_bits = {total} exceeds the "
            f"{MAX_PACKED_BITS}-bit exact-integer ceiling"
        )
    mask = jnp.uint32((1 << fbits) - 1)
    shape = u.shape[:-2] + u.shape[-1:]
    hi = jnp.zeros(shape, jnp.uint32)
    lo = jnp.zeros(shape, jnp.uint32)
    for j in range(k):
        uj = u[..., j, :].astype(jnp.uint32) & mask
        o = guard + j * fbits
        if o >= _LO_BITS:
            hi = hi | (uj << (o - _LO_BITS))
        else:
            lo = lo | ((uj << o) & jnp.uint32(_LO_MASK))
            if o + fbits > _LO_BITS:
                hi = hi | (uj >> (_LO_BITS - o))
    return hi, lo


def packed_value_int64(hi: np.ndarray, lo: np.ndarray) -> np.ndarray:
    """(hi, lo) -> the packed integer as int64 (host-side; tests + the
    decode path's reference)."""
    return (np.asarray(hi).astype(np.int64) << _LO_BITS) | np.asarray(
        lo
    ).astype(np.int64)


def deinterleave_fields(
    v: np.ndarray, k: int, fbits: int, guard: int
) -> np.ndarray:
    """int64 packed sums [..., n] -> int64 field sums [..., k, n] (host).

    One arithmetic rounding shift absorbs the guard band (exact while the
    accumulated decrypt noise stays below 2**(guard-1) in magnitude), then
    fields are plain masked shifts. The exact inverse of
    `interleave_fields` + homomorphic addition.
    """
    v = np.asarray(v, dtype=np.int64)
    w = (v + (1 << (guard - 1))) >> guard if guard else v
    mask = np.int64((1 << fbits) - 1)
    return np.stack(
        [(w >> (j * fbits)) & mask for j in range(k)], axis=-2
    )


def decode_field_sums(
    fields: np.ndarray, step: float, offset: int, surviving: int
) -> np.ndarray:
    """Field sums over S surviving clients -> the dequantized AVERAGE.

    Each surviving client contributed u = q + offset (offset = qmax makes
    codes non-negative on the wire); zeroed (excluded) clients contributed
    nothing, so sum_fields = sum(q) + S*offset and the average update is
    (sum_fields - S*offset) * step / S.
    """
    if surviving <= 0:
        raise ValueError("decode_field_sums: surviving must be positive")
    q_sum = fields.astype(np.int64) - np.int64(surviving) * np.int64(offset)
    return (q_sum * (float(step) / surviving)).astype(np.float32)


# ---------------------------------------------------------------------------
# Shaped jaxpr probes (ISSUE 8): the static-analysis subsystem
# (hefl_tpu.analysis) proves this module's integer invariants by interval
# abstract interpretation of REAL jaxprs, not of a hand-written model — so
# the probes below must trace the same math the pipeline runs.
# ---------------------------------------------------------------------------


def packing_sum_probe(
    bits: int, k: int, fbits: int, guard: int, clients: int
):
    """The packed-aggregation integer pipeline as one traceable function.

    Mirrors, in plaintext integers, exactly what the homomorphic path
    computes: quantize (clip to ±qmax) → offset to non-negative codes →
    shift each of the k fields to its bit offset (`interleave_fields`'s
    math on the recombined value hi·2**31+lo) → FOLD over C clients as a
    `lax.scan` — one arrival at a time, the same loop shape `psum_mod` /
    `OnlineAccumulator.fold` iterate — → add the accumulated decrypt
    noise → outputs the analyzer bounds:

        (field_sums [k, m], noise_sum [m], packed_total [m])

    The C-client sums are loop CARRIES (ISSUE 12): the range analyzer
    derives their bounds by iterating the body jaxpr over the carried
    intervals to a post-fixpoint, so the carry-free-sum proof is the loop
    machinery's, not a closed-form reduce bound. Shift offsets may exceed
    63 for unsafe configs — that is the point: tracing still succeeds
    (shift amounts are small constants) and the audited loop-body pass
    reports the shift as the offending op. Trace under
    `jax.enable_x64(True)` so the int64 carrier is nameable.
    -> (fn, example_args).
    """
    import jax as _jax
    import jax.numpy as _jnp

    qm = qmax(bits)
    m = 2  # coefficients per probe slab; ranges are per-element anyway

    def probe(x, noise):
        q = quantize(x, 1.0, bits)                     # int32 in [-qm, qm]
        u = (q + qm).astype(_jnp.int64)                # [C, k, m] >= 0

        def fold(carry, inp):
            fs, ns, tot = carry
            u_c, n_c = inp                             # [k, m], [m]
            packed_c = _jnp.zeros((m,), _jnp.int64)
            for j in range(k):
                packed_c = packed_c + (u_c[j] << (guard + j * fbits))
            return (fs + u_c, ns + n_c, tot + packed_c + n_c), None

        zk = _jnp.zeros((k, m), _jnp.int64)
        zm = _jnp.zeros((m,), _jnp.int64)
        (field_sums, noise_sum, packed_total), _ = _jax.lax.scan(
            fold, (zk, zm, zm), (u, noise)
        )
        return field_sums, noise_sum, packed_total

    x = jnp.zeros((int(clients), k, m), jnp.float32)
    noise = np.zeros((int(clients), m), np.int64)
    return probe, (x, noise)


def exact_int_probes() -> dict:
    """This module's declared exact-integer regions as shaped jaxpr probes
    (analysis.lint walks them: no rem/div, no float contamination).

    The `ef_interleave_fields` region (ISSUE 19) is the error-feedback
    path's wire tail at the DEEPER low-bit grid EF exists to unlock
    (b=4 -> 7-bit fields at C<=8, k=4): `ef_quantize`'s residual add is
    float by construction, but its CODES are clipped to the same
    [-qmax, qmax] alphabet as the plain quantizer's, so everything from
    the non-negativity offset on is exact integers in the carry-free
    band — the claim this region keeps statically watched.
    """
    u = jnp.zeros((2, 4), jnp.uint32)

    def ef_tail(q):
        # q: EF-quantized codes (int32, |q| <= qmax(4) = 7 by clipping).
        u4 = (q + qmax(4)).astype(jnp.uint32)   # [..., k, n] >= 0
        return interleave_fields(u4, 4, 7, 5)

    q4 = jnp.zeros((2, 4, 4), jnp.int32)
    return {
        "ckks.quantize.interleave_fields": (
            lambda v: interleave_fields(v, 2, 9, 5), (u,)
        ),
        "ckks.quantize.ef_interleave_fields": (ef_tail, (q4,)),
    }


def quant_error_budget(cfg: PackingConfig) -> float:
    """The declared per-coefficient |packed - unpacked| budget: the
    configured override, else step/2 (the quantizer's worst case, which
    averaging over clients cannot exceed) + 1e-4 slack for the unpacked
    reference's own CKKS decode error. A per-tensor clip schedule budgets
    at its COARSEST grid (the worst per-coefficient case)."""
    if cfg.error_budget:
        return float(cfg.error_budget)
    step = cfg.step
    worst = max(step) if isinstance(step, tuple) else step
    return 0.5 * worst + 1e-4


def describe(cfg: PackingConfig, modulus: int, clients: int) -> dict:
    """Human/artifact-facing summary of a packing choice at one geometry."""
    fb = field_bits(cfg.bits, clients)
    guard_eff = cfg.guard_bits + max(int(clients) - 1, 0).bit_length()
    k = cfg.interleave or max_interleave(
        modulus, cfg.bits, clients, cfg.guard_bits
    )
    return {
        "bits": cfg.bits,
        "interleave": k,
        "field_bits": fb,
        "guard_bits": guard_eff,
        "clip": cfg.clip,
        "step": cfg.step,
        "payload_bits": payload_bits(modulus, guard_eff),
        "error_budget": quant_error_budget(cfg),
        "error_feedback": bool(cfg.error_feedback),
        "clients": int(clients),
        "headroom_ok": guard_eff + k * fb
        <= min(modulus.bit_length() - 2, MAX_PACKED_BITS),
    }


__all__ = [
    "MAX_PACKED_BITS",
    "PackingConfig",
    "qmax",
    "symmetric_step",
    "field_bits",
    "payload_bits",
    "max_interleave",
    "packing_sum_probe",
    "exact_int_probes",
    "quantize",
    "dequantize",
    "ef_quantize",
    "saturation_count",
    "interleave_fields",
    "packed_value_int64",
    "deinterleave_fields",
    "decode_field_sums",
    "quant_error_budget",
    "describe",
]
