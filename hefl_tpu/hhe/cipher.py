"""Client-side additive stream cipher over the packed integer domain.

The packed-quantized uplink (ckks.quantize / ckks.packing) ships, per CKKS
slot, one non-negative integer v < 2**62 carried as a (hi, lo) uint32 pair
(v = hi * 2**31 + lo). This module encrypts that integer under a cheap
symmetric cipher so the CLIENT never runs an NTT, never touches RNS
residues, and ships ~1x the packed plaintext bytes:

    w = (v + z) mod 2**62          z = keystream(key_c, round, slot)

The keystream is a counter-mode PRF over the same division-free uint32
primitives the modular hot path uses (ckks.modular.mul32_wide's 16-bit
schoolbook products): a SplitMix64-style 64-bit mixing permutation applied
to the (client-key, round, slot-index) counter, implemented entirely as
uint32 word pairs — jittable, Pallas-compatible, no 64-bit dtype, no
divide, no float. One PRF sweep plus one carry-propagating add per slot is
the entire client-side cost.

Why mod 2**62 and not mod q: 2**62 IS the packed domain's natural modulus
(quantize.MAX_PACKED_BITS — the exact-integer ceiling every packed value
respects), and it keeps the wire format identical to the packed plaintext
(8 bytes/slot -> ~1.0x expansion, vs 1.5x for mod-q RNS residues). The
mismatch against the server's mod-q arithmetic is benign BY CONSTRUCTION:
the transciphered plaintext per client is v - 2**62 * gamma (gamma in
{0, 1}, the cipher's wrap carry), so the decrypted aggregate is
sum(v) - 2**62 * Gamma + noise, and one mod-2**62 reduction (hhe_center_mod)
recovers sum(v) + noise EXACTLY — bitwise what the direct packed path
decodes — while |aggregate| < q/2 holds. `analysis.ranges.
certify_transciphering` proves both conditions statically for a
configuration, or rejects it naming the overflowing op.

Security note (documented, load-bearing): SplitMix64 is a stand-in PRF —
statistically strong, not a vetted cryptographic cipher. The pipeline is
cipher-agnostic (the keystream function is the single swap point for a
production ARX cipher such as ChaCha over the same (hi, lo) word-pair
layout); everything downstream — wire format, transciphering, parity and
range gates — is unchanged by that swap. The trust story lives in
README "Hybrid HE uplink": the server only ever sees symmetric
ciphertexts and CKKS-encrypted keystream pads; client master keys exist
in the clear only on the client and (key-wrapped) at the key authority.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from hefl_tpu.ckks.quantize import MAX_PACKED_BITS

# The cipher's modulus is the packed domain: 2**HHE_DOMAIN_BITS.
HHE_DOMAIN_BITS = MAX_PACKED_BITS
_LO_BITS = 31
_MASK31 = (1 << 31) - 1
# Per-upload wire header: client id (4) + round (4) + key epoch (4) +
# format tag (4) — constant, counted by sym_wire_bytes so the expansion
# record is honest about every byte.
WIRE_HEADER_BYTES = 16

# SplitMix64 mixing constants, split into (hi, lo) uint32 words.
_GAMMA = (0x9E3779B9, 0x7F4A7C15)
_MIX1 = (0xBF58476D, 0x1CE4E5B9)
_MIX2 = (0x94D049BB, 0x133111EB)


@dataclasses.dataclass(frozen=True)
class HheConfig:
    """Hybrid-HE uplink knobs (frozen/hashable: rides in ExperimentConfig).

    Defined here — next to the cipher it parameterizes — and re-exported
    through fl.config like PackingConfig, so the FL layer's config surface
    stays cycle-free.

    key_seed:  root of the per-client master-key derivation
               (`derive_client_keys`). In production each client generates
               its own master key and key-wraps it to the key authority;
               the seed-derived tree is the in-process simulation of that
               enrollment (every party the driver simulates can re-derive
               exactly the keys it is entitled to).
    """

    key_seed: int = 0


# ---------------------------------------------------------------------------
# 64-bit word-pair arithmetic on uint32 pairs (jittable, Pallas-safe:
# no int64 dtype, no divide, no float — the same discipline as ckks.modular).
# ---------------------------------------------------------------------------


def _add64(a_hi, a_lo, b_hi, b_lo):
    lo = a_lo + b_lo                               # wraps mod 2**32
    carry = (lo < a_lo).astype(jnp.uint32)
    return a_hi + b_hi + carry, lo


def _xor64(a_hi, a_lo, b_hi, b_lo):
    return a_hi ^ b_hi, a_lo ^ b_lo


def _shr64(hi, lo, k: int):
    """Logical right shift by a static 0 < k < 32."""
    return hi >> k, (lo >> k) | (hi << (32 - k))


def _mul64(a_hi, a_lo, b_hi, b_lo):
    """Low 64 bits of the product, via the 16-bit schoolbook core."""
    from hefl_tpu.ckks.modular import mul32_wide

    ll_hi, ll_lo = mul32_wide(a_lo, b_lo)
    return ll_hi + a_lo * b_hi + a_hi * b_lo, ll_lo


def _const64(pair):
    return jnp.uint32(pair[0]), jnp.uint32(pair[1])


def _mix64(hi, lo):
    """The SplitMix64 finalizer: xor-shift / multiply / xor-shift."""
    s_hi, s_lo = _shr64(hi, lo, 30)
    hi, lo = _xor64(hi, lo, s_hi, s_lo)
    hi, lo = _mul64(hi, lo, *_const64(_MIX1))
    s_hi, s_lo = _shr64(hi, lo, 27)
    hi, lo = _xor64(hi, lo, s_hi, s_lo)
    hi, lo = _mul64(hi, lo, *_const64(_MIX2))
    s_hi, s_lo = _shr64(hi, lo, 31)
    return _xor64(hi, lo, s_hi, s_lo)


# ---------------------------------------------------------------------------
# Key derivation (host-side) + the counter-mode keystream (jittable).
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=16)
def derive_client_keys(seed: int, num_clients: int) -> np.ndarray:
    """Per-client 128-bit master keys uint32[C, 4], derived from the
    enrollment seed by SHA-256 (host-side, once per experiment; read-only
    so the lru_cached array cannot be mutated under its consumers)."""
    out = np.empty((int(num_clients), 4), np.uint32)
    for c in range(int(num_clients)):
        d = hashlib.sha256(
            f"hefl-hhe-key-v1|{int(seed)}|{c}".encode()
        ).digest()
        out[c] = np.frombuffer(d[:16], np.uint32)
    out.setflags(write=False)
    return out


def keystream_pair(
    key: jnp.ndarray, round_index, shape: tuple[int, int]
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The (hi, lo) uint32 keystream for one client's round: uniform draws
    from [0, 2**62), one per slot of the packed geometry `shape` =
    (n_ct, n).

    Counter mode: the 64-bit block counter is (key[2] ^ round, key[3] ^
    slot_index); two SplitMix64 mixing passes keyed by (key[0], key[1])
    turn it into the output block, of which bits [31, 62) and [0, 31) are
    the (hi, lo) pair — hi, lo < 2**31, so hi * 2**31 + lo is uniform on
    exactly [0, 2**62). `round_index` may be traced (the no-new-compile
    guarantee: every round shares one executable).
    """
    n_ct, n = int(shape[0]), int(shape[1])
    idx = jax.lax.iota(jnp.uint32, n_ct * n).reshape(n_ct, n)
    r = jnp.asarray(round_index).astype(jnp.uint32)
    hi = jnp.broadcast_to(key[2] ^ r, idx.shape)
    lo = key[3] ^ idx
    hi, lo = _add64(hi, lo, key[0], key[1])
    hi, lo = _mix64(hi, lo)
    hi, lo = _xor64(hi, lo, key[1], key[0])
    hi, lo = _mix64(hi, lo)
    hi, lo = _add64(hi, lo, *_const64(_GAMMA))
    hi, lo = _mix64(hi, lo)
    return (hi >> 1) & jnp.uint32(_MASK31), lo & jnp.uint32(_MASK31)


# ---------------------------------------------------------------------------
# The cipher: one carry-propagating add / subtract per slot, mod 2**62.
# ---------------------------------------------------------------------------


def add_packed_mod(a_hi, a_lo, b_hi, b_lo):
    """(a + b) mod 2**62 on packed (hi, lo) pairs (hi, lo < 2**31)."""
    lo = a_lo + b_lo                                # < 2**32: no wrap
    carry = lo >> _LO_BITS
    hi = (a_hi + b_hi + carry) & jnp.uint32(_MASK31)
    return hi, lo & jnp.uint32(_MASK31)


def sub_packed_mod(a_hi, a_lo, b_hi, b_lo):
    """(a - b) mod 2**62 on packed (hi, lo) pairs."""
    borrow = (a_lo < b_lo).astype(jnp.uint32)
    lo = (a_lo - b_lo) & jnp.uint32(_MASK31)
    hi = (a_hi - b_hi - borrow) & jnp.uint32(_MASK31)
    return hi, lo


def stream_encrypt(hi, lo, key, round_index):
    """One client's packed update (hi, lo uint32[n_ct, n]) -> the symmetric
    ciphertext (same shape, same bytes): w = (v + keystream) mod 2**62."""
    z_hi, z_lo = keystream_pair(key, round_index, hi.shape[-2:])
    return add_packed_mod(hi, lo, z_hi, z_lo)


def stream_decrypt(w_hi, w_lo, key, round_index):
    """Inverse of `stream_encrypt` (tests + the key authority's mirror)."""
    z_hi, z_lo = keystream_pair(key, round_index, w_hi.shape[-2:])
    return sub_packed_mod(w_hi, w_lo, z_hi, z_lo)


def hhe_center_mod(v: np.ndarray, guard: int) -> np.ndarray:
    """Recover the packed aggregate from the transciphered decode (host).

    `v` is `encoding.decode_int_center` of the transciphered sum: the
    integer sum(v_c) - 2**62 * Gamma + E (Gamma = the per-client cipher
    wrap carries, |E| < 2**(guard-1) the decrypt noise) — read through an
    int64 two's-complement carrier whose own wraparound is benign because
    2**62 divides 2**64. One shifted mod-2**62 reduction removes the Gamma
    term exactly: valid while -2**(guard-1) <= sum(v) + E < 2**62 -
    2**(guard-1), the window `certify_transciphering` proves statically.
    The result is bitwise the direct packed path's decode input.
    """
    v = np.asarray(v, dtype=np.int64)
    mask = np.int64((1 << HHE_DOMAIN_BITS) - 1)
    h = np.int64(1 << max(int(guard) - 1, 0))
    return ((v + h) & mask) - h


# ---------------------------------------------------------------------------
# Wire accounting (the bench/perf-smoke record).
# ---------------------------------------------------------------------------


def sym_wire_bytes(spec) -> int:
    """Per-client uplink bytes of one HHE upload: the (hi, lo) uint32 pair
    per packed slot — the SAME bytes the packed plaintext occupies — plus
    the constant wire header."""
    return spec.n_ct * spec.n * 8 + WIRE_HEADER_BYTES


def hhe_bytes_on_wire_record(spec, num_limbs: int) -> dict:
    """The HHE `bytes_on_wire` artifact record.

    `plain_quantized` is the quantized update as the wire would ship it
    unencrypted — the packed (hi, lo) integer representation, 8 bytes per
    slot (the apples-to-apples baseline: same representation, encrypted
    vs not). `plain_codes` (the raw b-bit codes with no interleave
    headroom) is recorded alongside for transparency: the guard band and
    carry-free headroom are packing overhead the cipher inherits, not
    cipher expansion.
    """
    from hefl_tpu.ckks.packing import ciphertext_bytes

    wire = sym_wire_bytes(spec)
    plain_quantized = spec.n_ct * spec.n * 8
    plain_codes = -(-spec.total * spec.bits // 8)
    ckks = ciphertext_bytes(spec.n_ct, num_limbs, spec.n)
    return {
        "hhe_upload": wire,
        "plain_quantized": plain_quantized,
        "plain_codes": plain_codes,
        "ciphertext_packed": ckks,
        "expansion_hhe": round(wire / plain_quantized, 3),
        "expansion_vs_codes": round(wire / plain_codes, 3),
        "reduction_vs_ckks": round(ckks / wire, 2),
    }


# ---------------------------------------------------------------------------
# Shaped jaxpr probes (the PR-8 static-analysis gate, extended to HHE).
# ---------------------------------------------------------------------------


def exact_int_probes() -> dict:
    """This module's declared exact-integer regions for analysis.lint:
    the keystream PRF and the cipher add/sub — pure uint32, no rem/div,
    no float (one float round-trip would shear the packed bit fields the
    cipher carries)."""
    key = jnp.zeros((4,), jnp.uint32)
    hi = jnp.zeros((2, 8), jnp.uint32)
    lo = jnp.zeros((2, 8), jnp.uint32)
    counter_fn, counter_args = keystream_counter_probe()
    return {
        "hhe.cipher.keystream": (
            lambda k: keystream_pair(k, jnp.uint32(1), (2, 8)), (key,)
        ),
        "hhe.cipher.stream_encrypt": (
            lambda h, l, k: stream_encrypt(h, l, k, jnp.uint32(1)),
            (hi, lo, key),
        ),
        # The counter-mode round loop (ISSUE 12): the declared exact-int
        # region now CONTAINS the while loop, so its carried counter and
        # cipher words are lint-watched (no rem/div, no float) too.
        "hhe.cipher.keystream_counter": (counter_fn, counter_args),
    }


def transcipher_sum_probe(bits: int, k: int, fbits: int, guard: int,
                          clients: int):
    """The transciphered-aggregation integer pipeline as one traceable
    function (analysis.ranges.certify_transciphering).

    Mirrors, in plaintext integers, what the HHE path computes under
    encryption: quantize -> offset -> interleave into the packed value v
    per client; the symmetric cipher's wrap carry gamma in {0, 1} (an
    abstracted INPUT — its value depends on the secret keystream, its
    range does not); the transciphered per-client plaintext v - 2**62 *
    gamma; the C-client homomorphic sum plus decrypt noise. Outputs the
    analyzer bounds:

        (field_sums [k, m],        # carry-free-sum check (as packing)
         noise_sum [m],            # guard-band check
         transciphered_total [m],  # the q/2 wall: sum(v) - 2**62*Gamma + E
         recovered_shifted [m])    # sum(v) + E + 2**(guard-1): the
                                   # mod-2**62 recovery window [0, 2**62)

    Trace under `jax.enable_x64(True)` (the int64 carrier must be
    nameable; the analysis computes in unbounded ints).
    -> (fn, example_args).
    """
    from hefl_tpu.ckks import quantize

    qm = quantize.qmax(bits)
    m = 2
    domain = 1 << HHE_DOMAIN_BITS

    def probe(x, gamma, noise):
        q = quantize.quantize(x, 1.0, bits)            # int32 [-qm, qm]
        u = (q + qm).astype(jnp.int64)                 # [C, k, m] >= 0

        # The C-client sums as a lax.scan fold (ISSUE 12): one arrival at
        # a time, the loop shape the streaming engine actually iterates —
        # the analyzer derives the carried bounds as a loop post-fixpoint.
        def fold(carry, inp):
            fs, ns, tot, rec = carry
            u_c, g_c, n_c = inp                        # [k,m], [m], [m]
            packed_c = jnp.zeros((m,), jnp.int64)
            for j in range(k):
                packed_c = packed_c + (u_c[j] << (guard + j * fbits))
            trans_c = packed_c - g_c * domain          # per-client w - z
            return (
                fs + u_c, ns + n_c, tot + trans_c + n_c,
                rec + packed_c + n_c,
            ), None

        zk = jnp.zeros((k, m), jnp.int64)
        zm = jnp.zeros((m,), jnp.int64)
        (field_sums, noise_sum, total, rec), _ = jax.lax.scan(
            fold, (zk, zm, zm, zm), (u, gamma, noise)
        )
        recovered = rec + (1 << max(guard - 1, 0))
        return field_sums, noise_sum, total, recovered

    x = jnp.zeros((int(clients), k, m), jnp.float32)
    gamma = np.zeros((int(clients), m), np.int64)
    noise = np.zeros((int(clients), m), np.int64)
    return probe, (x, gamma, noise)


def keystream_counter_probe():
    """The counter-mode round-counter loop as one traceable function
    (ISSUE 12; analysis.ranges.certify_transciphering's loop leg).

    The cipher's per-round counter is the one piece of loop-carried
    integer state the HHE uplink owns: every round increments the 32-bit
    round counter (wrapping mod 2**32 BY DESIGN — modeled here as an
    explicit mask on an int64 carrier so the intent is a proven bound,
    not a silent uint32 wrap) and encrypts a fresh packed payload with
    fresh keystream words. The probe runs that loop over an ABSTRACT
    round count and mirrors `add_packed_mod`'s word-pair carry add at its
    REAL uint32 dtypes, so the analyzer proves, at any round count:

      * the round counter stays in [0, 2**32); the increment's int64
        carrier never wraps;
      * the lo-word add of two sub-2**31 words never wraps uint32, and
        both output words stay below 2**31 (the packed (hi, lo) wire
        invariant).

    The keystream DERIVATION (the SplitMix64 mix) wraps uint32
    intentionally and stays exempt from range analysis, exactly like the
    Montgomery cores — its words enter here as [0, 2**31) inputs, which
    is the only fact `keystream_pair`'s masking exports. Trace under
    `jax.enable_x64(True)`. -> (fn, example_args).
    """

    def probe(rounds, r0, mask, v_hi, v_lo, z_hi, z_lo):
        def cond(state):
            return state[0] > 0

        def body(state):
            remaining, r, _hi, _lo = state
            r = (r + 1) & mask                # the mod-2**32 counter
            w_hi, w_lo = add_packed_mod(v_hi, v_lo, z_hi, z_lo)
            return remaining - 1, r, w_hi, w_lo

        _, r, w_hi, w_lo = jax.lax.while_loop(
            cond, body,
            (rounds, r0, jnp.zeros_like(v_hi), jnp.zeros_like(v_lo)),
        )
        return r, w_hi, w_lo

    hi = np.zeros((2, 8), np.uint32)
    # The counter mask rides as a uint32 ARG (an in-trace 0xFFFFFFFF
    # literal cannot be named without x64; the argument form traces under
    # both modes and the analyzer receives its exact interval).
    return probe, (
        np.int64(0), np.int64(0), np.uint32(0xFFFFFFFF), hi, hi, hi, hi
    )


__all__ = [
    "HHE_DOMAIN_BITS",
    "WIRE_HEADER_BYTES",
    "HheConfig",
    "add_packed_mod",
    "sub_packed_mod",
    "derive_client_keys",
    "keystream_pair",
    "stream_encrypt",
    "stream_decrypt",
    "hhe_center_mod",
    "sym_wire_bytes",
    "hhe_bytes_on_wire_record",
    "exact_int_probes",
    "transcipher_sum_probe",
    "keystream_counter_probe",
]
