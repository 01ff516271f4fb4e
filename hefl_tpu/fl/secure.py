"""Encrypted FedAvg: the reference's HE pipeline as one SPMD program.

Reference flow (SURVEY.md §3.3-§3.5), all through pickle files:

    export_encrypted_clients_weights  FLPyfhelin.py:242  per-scalar encryptFrac
    aggregate_encrypted_weights       FLPyfhelin.py:366  per-scalar ct+ct, ct*1/N
    decrypt_import_weights            FLPyfhelin.py:263  per-scalar decryptFrac

Here each client's trained weights are packed into [n_ct, N] CKKS coefficient
blocks, encrypted on-device, and the server aggregation is a single
`psum` of ciphertext RNS limbs over ICI — homomorphic addition of every
client's every ciphertext in one collective. The 1/N FedAvg scaling costs
nothing: the decoder divides by `scale * num_clients` (the reference's
ct × plaintext-1/N step, FLPyfhelin.py:385, exists as `ops.ct_mul_scalar`
for API parity but the round path never needs the extra multiply).

Trust split preserved (SURVEY.md §2.6): the training/aggregation program
touches only `PublicKey`; `SecretKey` appears exclusively in
`decrypt_average`, the model-owner step.
"""

from __future__ import annotations

import functools
from functools import partial

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree
from jax.sharding import PartitionSpec as P

import numpy as np

from hefl_tpu.ckks import encoding, ops
from hefl_tpu.ckks.keys import CkksContext, PublicKey, SecretKey
from hefl_tpu.ckks.ops import Ciphertext
from hefl_tpu.ckks.packing import (
    PackedSpec,
    PackSpec,
    pack_pytree,
    pack_quantized_delta,
    pack_quantized_delta_ef,
    unpack_blocks,
    unpack_quantized,
)
from hefl_tpu.fl.config import TrainConfig
from hefl_tpu.fl.dp import calibration_clients
from hefl_tpu.fl.faults import RoundMeta, exclusion_bits, poison_tree
from hefl_tpu.fl.fedavg import (
    _mask_inputs,
    _round_geometry,
    _trivial_mask,
    masked_mean_tree,
    masked_mode,
    pad_index,
    replicate_on,
    train_block,
)
from hefl_tpu.ckks.modular import add_mod as modular_add_mod
from hefl_tpu.ckks.modular import barrett_mod, barrett_mu
from hefl_tpu.obs import metrics as obs_metrics
from hefl_tpu.obs import scopes as obs_scopes
from hefl_tpu.obs import spans as obs_spans
from hefl_tpu.parallel import (
    client_axes,
    client_mesh_size,
    pmean_tree,
    shard_map,
)
from hefl_tpu.parallel.collectives import MAX_PSUM_CLIENTS, hierarchical_psum_mod

# decrypt_average's host steps, as spans: kernel (the decrypt's launch),
# decode (the compiled decode's launch), unpack (what is left after it)
_DECRYPT_STEP = obs_spans.PHASE_PREFIX + "decrypt."


@partial(jax.jit, static_argnums=0)
def encrypt_params(
    ctx: CkksContext, pk: PublicKey, params, key: jax.Array
) -> Ciphertext:
    """Encrypt one client's parameter pytree -> batched Ciphertext [n_ct, L, N].

    The analog of `encrypt_export_weights` (FLPyfhelin.py:200-228), minus the
    export: 55 batched ciphertexts instead of 222,722 scalar Pyfhel calls.
    """
    with jax.named_scope(obs_scopes.ENCRYPT):
        blocks = pack_pytree(params, ctx.n)
        m_res = encoding.encode(ctx.ntt, blocks, ctx.scale)
        return ops.encrypt(ctx, pk, m_res, key)


@partial(jax.jit, static_argnums=(0, 5))
def encrypt_params_packed(
    ctx: CkksContext,
    pk: PublicKey,
    params,
    base_params,
    key: jax.Array,
    spec: PackedSpec,
) -> Ciphertext:
    """Encrypt one client's quantized bit-interleaved UPDATE (params minus
    base_params) -> batched Ciphertext [spec.n_ct, L, N]: the packed twin of
    `encrypt_params`, k-fold fewer rows through the same encrypt core."""
    with jax.named_scope(obs_scopes.ENCRYPT):
        hi, lo, _ = pack_quantized_delta(params, base_params, spec)
        m_res = encoding.encode_packed(ctx.ntt, hi, lo)
        ct = ops.encrypt(ctx, pk, m_res, key)
        return Ciphertext(c0=ct.c0, c1=ct.c1, scale=spec.guard_scale)


def _lazy_sum_mod(x: jax.Array, p: jax.Array) -> jax.Array:
    """Sum uint32 residues over axis 0 with lazy modular reduction.

    Up to MAX_PSUM_CLIENTS summands of <2**27 each fit uint32 without
    wraparound (the `psum_mod` argument), so reduction happens once per
    chunk of 32; chunk results are canonical and fold together with
    `add_mod` — any client count works, still O(1) reductions per ~32
    clients. The per-chunk reduction is shift-multiply Barrett
    (`modular.barrett_mod`, bitwise-equal to the historical `lax.rem`), so
    the hot path issues no hardware divides (ISSUE 4).
    """
    num = x.shape[0]
    p_full = jnp.broadcast_to(p, x.shape[1:])
    mu_full = jnp.broadcast_to(barrett_mu(p), x.shape[1:])

    def chunk_sum(c):
        return barrett_mod(jnp.sum(c, axis=0, dtype=jnp.uint32), p_full, mu_full)

    acc = chunk_sum(x[:MAX_PSUM_CLIENTS])
    for lo in range(MAX_PSUM_CLIENTS, num, MAX_PSUM_CLIENTS):
        acc = modular_add_mod(acc, chunk_sum(x[lo : lo + MAX_PSUM_CLIENTS]), p_full)
    return acc


def exact_int_probes() -> dict:
    """Shaped jaxpr probes of this module's declared exact-integer regions
    (ISSUE 8, analysis.lint): the lazy modular sum must stay rem/div- and
    float-free — it runs per ciphertext limb on the hot aggregation path."""
    p = jnp.full((3, 1), jnp.uint32(2**27 - 39))
    x = jnp.zeros((4, 3, 8), jnp.uint32)
    return {
        "fl.secure.lazy_sum_mod": (lambda v: _lazy_sum_mod(v, p), (x,)),
    }


def lazy_sum_chunk_probe(chunk: int = MAX_PSUM_CLIENTS):
    """Range probe (analysis.ranges.certify_aggregation): the lazy uint32
    accumulation inside `_lazy_sum_mod` — up to MAX_PSUM_CLIENTS canonical
    residues are summed WITHOUT reduction, so the no-wrap proof is
    sum < 2**32, statically, for the configured prime size."""

    def probe(x):
        return jnp.sum(x, axis=0, dtype=jnp.uint32)

    return probe, (jnp.zeros((int(chunk), 8), jnp.uint32),)


def _ct_sharded_encrypt_core(
    ctx: CkksContext, pk: PublicKey, m_res, u, e0, e1, ct_shards: int
) -> Ciphertext:
    """The stacked encrypt core with the ciphertext-row axis (axis 1 of
    [C, n_ct, ...]) sharded over the mesh's ``"ct"`` axis (ISSUE 15).

    Only callable inside a `shard_map` body on a 2-D ("clients", "ct")
    mesh. Encode and sampling already ran at the LOGICAL [n_ct] shape
    (replicated over ct — they are elementwise and cheap; the historical
    key derivation is untouched, so ciphertexts stay bitwise stable);
    here each device keeps its `n_ct / ct_shards` row slice, runs the
    NTT-heavy encrypt core on that slice only, and an all-gather over the
    ``"ct"`` axis reassembles the full [C, n_ct, ...] stack — bitwise the
    replicated result (sharding partitions rows, every row's math is
    identical), so everything downstream (masking, lazy sums, the psum
    tail, the owner decrypt) is untouched. ct_shards == 1 is the
    historical path, same compiled program.
    """
    if ct_shards <= 1:
        return ops.encrypt_core(ctx, pk, m_res, u, e0, e1)
    from hefl_tpu.parallel import CT_AXIS

    n_ct = int(m_res.shape[1])
    per = -(-n_ct // ct_shards)
    pad = per * ct_shards - n_ct

    def local_rows(t):
        if pad:
            t = jnp.concatenate(
                [t, jnp.zeros((t.shape[0], pad) + t.shape[2:], t.dtype)],
                axis=1,
            )
        start = jax.lax.axis_index(CT_AXIS) * per
        return jax.lax.dynamic_slice_in_dim(t, start, per, axis=1)

    ct = ops.encrypt_core(
        ctx, pk, local_rows(m_res), local_rows(u),
        local_rows(e0), local_rows(e1),
    )
    c0 = jax.lax.all_gather(ct.c0, CT_AXIS, axis=1, tiled=True)
    c1 = jax.lax.all_gather(ct.c1, CT_AXIS, axis=1, tiled=True)
    if pad:
        c0, c1 = c0[:, :n_ct], c1[:, :n_ct]
    return Ciphertext(c0=c0, c1=c1, scale=ct.scale)


def encrypt_stack(
    ctx: CkksContext, pk: PublicKey, p_out, enc_keys, ct_shards: int = 1
) -> Ciphertext:
    """Encrypt stacked per-client weight trees (leaves [C, ...]) into one
    [C, n_ct, L, N]-batched Ciphertext — the encrypt half of the round for
    weights that are already materialized (the secure-round tests).

    Pack/encode/sampling run per client (vmapped elementwise XLA, the
    HISTORICAL per-client key derivation so ciphertexts stay bitwise
    stable), then the whole [C, n_ct] stack goes through ONE
    `ops.encrypt_core` call — a single fused kernel dispatch on the Pallas
    backend instead of a vmap of per-client kernels, and one stacked NTT
    graph on XLA.
    """
    enc_one = lambda prm: encoding.encode(  # noqa: E731
        ctx.ntt, pack_pytree(prm, ctx.n), ctx.scale
    )
    m_res = jax.vmap(enc_one)(p_out)                    # [C, n_ct, L, N]
    n_ct = int(m_res.shape[1])
    u, e0, e1 = jax.vmap(
        lambda k: ops.encrypt_samples(ctx, k, (n_ct,))
    )(enc_keys)
    return _ct_sharded_encrypt_core(ctx, pk, m_res, u, e0, e1, ct_shards)


def encrypt_stack_packed(
    ctx: CkksContext,
    pk: PublicKey,
    p_out,
    base_params,
    enc_keys,
    spec: PackedSpec,
    ct_shards: int = 1,
) -> tuple[Ciphertext, jax.Array]:
    """The packed-quantized twin of `encrypt_stack`: each client's UPDATE
    (trained weights minus `base_params`, the round's global weights) is
    quantized to `spec.bits` bits and bit-interleaved `spec.k`-to-a-slot
    (ckks.packing), so the batched ciphertext is [C, n_ct/k, L, N] and
    every downstream kernel — the fused Pallas/XLA encrypt core here, the
    masked psum, the owner decrypt — sees k-fold fewer rows.

    -> (Ciphertext [C, spec.n_ct, L, N], saturation int32[C]): `saturation`
    counts each client's update coefficients that clipped at `spec.clip`
    (the packed analog of `encode_overflow_count`; it reports through the
    same `encode_overflow` output slot and drives the same
    on_overflow="exclude" machinery).
    """

    def enc_one(prm):
        hi, lo, sat = pack_quantized_delta(prm, base_params, spec)
        return encoding.encode_packed(ctx.ntt, hi, lo), sat

    m_res, sat = jax.vmap(enc_one)(p_out)
    n_ct = int(m_res.shape[1])
    u, e0, e1 = jax.vmap(
        lambda k: ops.encrypt_samples(ctx, k, (n_ct,))
    )(enc_keys)
    ct = _ct_sharded_encrypt_core(ctx, pk, m_res, u, e0, e1, ct_shards)
    return (
        Ciphertext(c0=ct.c0, c1=ct.c1, scale=spec.guard_scale),
        sat,
    )


def encrypt_stack_packed_ef(
    ctx: CkksContext,
    pk: PublicKey,
    p_out,
    base_params,
    enc_keys,
    spec: PackedSpec,
    residual_blk,
    ct_shards: int = 1,
) -> tuple[Ciphertext, jax.Array, jax.Array]:
    """The error-feedback twin of `encrypt_stack_packed` (ISSUE 19): each
    client's update is quantized THROUGH its carried residual
    (`ckks.packing.pack_quantized_delta_ef`) and the new residual rows
    come back as a third output for the engine's cross-round state.

    `residual_blk` is f32[C, spec.total] (one residual row per client,
    same client order as `p_out`). Wire geometry, encrypt core, and the
    saturation slot are identical to the plain packed path — EF only
    changes WHICH codes ride, never their alphabet.
    -> (Ciphertext [C, spec.n_ct, L, N], saturation int32[C],
    residual' f32[C, spec.total]).
    """

    def enc_one(prm, res):
        hi, lo, sat, new_res = pack_quantized_delta_ef(
            prm, base_params, res, spec
        )
        return encoding.encode_packed(ctx.ntt, hi, lo), sat, new_res

    m_res, sat, new_res = jax.vmap(enc_one)(p_out, residual_blk)
    n_ct = int(m_res.shape[1])
    u, e0, e1 = jax.vmap(
        lambda k: ops.encrypt_samples(ctx, k, (n_ct,))
    )(enc_keys)
    ct = _ct_sharded_encrypt_core(ctx, pk, m_res, u, e0, e1, ct_shards)
    return (
        Ciphertext(c0=ct.c0, c1=ct.c1, scale=spec.guard_scale),
        sat,
        new_res,
    )


def hhe_encrypt_stack(
    p_out,
    base_params,
    hhe_keys: jax.Array,
    round_index,
    spec: PackedSpec,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The hybrid-HE twin of `encrypt_stack_packed` (ISSUE 11): each
    client's quantized bit-interleaved UPDATE is encrypted under its
    symmetric stream cipher instead of CKKS — one counter-mode keystream
    add per packed slot, NO NTTs, no RNS residues, ~1x wire expansion
    (hhe.cipher). The server transciphers the result into CKKS
    (hhe.transcipher) before the quorum fold, so everything downstream is
    unchanged.

    -> (w_hi, w_lo uint32[C, spec.n_ct, N], saturation int32[C]):
    `saturation` reports through the same `encode_overflow` slot as the
    packed path (the on_overflow machinery is cipher-agnostic).
    """
    from hefl_tpu.hhe import cipher as hhe_cipher

    def enc_one(prm, key):
        hi, lo, sat = pack_quantized_delta(prm, base_params, spec)
        w_hi, w_lo = hhe_cipher.stream_encrypt(hi, lo, key, round_index)
        return w_hi, w_lo, sat

    return jax.vmap(enc_one)(p_out, hhe_keys)


def hhe_encrypt_stack_ef(
    p_out,
    base_params,
    hhe_keys: jax.Array,
    round_index,
    spec: PackedSpec,
    residual_blk,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """The error-feedback twin of `hhe_encrypt_stack` (ISSUE 19): the
    symmetric cipher rides the EF-quantized codes and the new residual
    rows come back for the engine's cross-round state. Keystream math and
    the transcipher contract are untouched — EF changes the codes, not
    the wire format. -> (w_hi, w_lo, saturation, residual')."""
    from hefl_tpu.hhe import cipher as hhe_cipher

    def enc_one(prm, key, res):
        hi, lo, sat, new_res = pack_quantized_delta_ef(
            prm, base_params, res, spec
        )
        w_hi, w_lo = hhe_cipher.stream_encrypt(hi, lo, key, round_index)
        return w_hi, w_lo, sat, new_res

    return jax.vmap(enc_one)(p_out, hhe_keys, residual_blk)


def _pad_rows(arr: jax.Array, mult: int) -> jax.Array:
    """Zero-pad axis 0 to a multiple of `mult` (ciphertext-shard padding)."""
    pad = (-arr.shape[0]) % mult
    if pad:
        arr = jnp.concatenate(
            [arr, jnp.zeros((pad, *arr.shape[1:]), arr.dtype)], axis=0
        )
    return arr


@functools.lru_cache(maxsize=8)
def _build_sharded_he(ctx: CkksContext, mesh):
    """Compile-once factory for ciphertext-sharded encrypt/decrypt (ISSUE 4).

    The [n_ct, L, N] residue tensors are embarrassingly parallel over the
    ciphertext axis, so both cores run under `shard_map` with the rows
    partitioned over the 1-D ``"ct"`` mesh (`parallel.make_ct_mesh`) and
    the key polynomials replicated. Every row's math is identical to the
    replicated path, so sharded results are BITWISE equal — sharding is
    pure throughput, no numerics knob.

    Callers reshard inputs onto THIS mesh first (`_onto_mesh`): a
    ciphertext straight out of a round program is committed to the round's
    client mesh, and jit refuses to mix device sets otherwise.
    """
    from hefl_tpu.parallel import CT_AXIS

    spec = P(CT_AXIS)

    def enc_body(m_res, u, e0, e1, b_mont, a_mont):
        ct = ops.encrypt_core(
            ctx, PublicKey(b_mont=b_mont, a_mont=a_mont), m_res, u, e0, e1
        )
        return ct.c0, ct.c1

    def dec_body(c0, c1, s_mont):
        return ops.decrypt(
            ctx, SecretKey(s_mont=s_mont),
            Ciphertext(c0=c0, c1=c1, scale=ctx.scale),
        )

    enc = jax.jit(shard_map(
        enc_body, mesh=mesh,
        in_specs=(spec, spec, spec, spec, P(), P()),
        out_specs=(spec, spec),
        check_vma=False,
    ))
    dec = jax.jit(shard_map(
        dec_body, mesh=mesh,
        in_specs=(spec, spec, P()),
        out_specs=spec,
        check_vma=False,
    ))
    return enc, dec


def _on_one_device(ct: Ciphertext) -> Ciphertext:
    """The owner's copy of a round output: a single-device ciphertext.

    A round program returns its aggregate replicated over the round's mesh.
    Decrypting that as it stands makes the owner-side decrypt a program
    over all of the mesh's devices, which a Mosaic kernel refuses ("cannot
    be automatically partitioned") and which would repeat the same work on
    every chip anyway. A replicated array's first shard IS the whole array:
    take that buffer (no copy); anything else is gathered onto one device.
    """
    def one(a):
        sharding = getattr(a, "sharding", None)
        if sharding is None or len(sharding.device_set) == 1:
            return a
        first = a.addressable_shards[0]
        if sharding.is_fully_replicated:
            return first.data
        return jax.device_put(a, first.device)

    return Ciphertext(c0=one(ct.c0), c1=one(ct.c1), scale=ct.scale)


def _onto_mesh(mesh, arr: jax.Array, sharded: bool) -> jax.Array:
    """Reshard one array onto the ct mesh (row-sharded or replicated).

    A plain argument pass is not enough: arrays committed to a different
    device set (e.g. a ciphertext from the round program's client mesh)
    make jit raise "incompatible devices". device_put performs the copy.
    """
    from jax.sharding import NamedSharding

    from hefl_tpu.parallel import CT_AXIS

    return jax.device_put(
        arr, NamedSharding(mesh, P(CT_AXIS) if sharded else P())
    )


def encrypt_params_sharded(
    ctx: CkksContext, pk: PublicKey, params, key: jax.Array, mesh
) -> Ciphertext:
    """`encrypt_params` with the ciphertext batch sharded over `mesh`.

    Pack/encode/sampling run at the LOGICAL [n_ct] shape with the identical
    key derivation as the replicated path (so ciphertexts are bitwise
    equal); only the deterministic core — the NTT-heavy part — is padded to
    the device count and sharded over the ``"ct"`` axis.
    """
    blocks = pack_pytree(params, ctx.n)
    m_res = encoding.encode(ctx.ntt, blocks, ctx.scale)
    n_ct = int(m_res.shape[0])
    u, e0, e1 = ops.encrypt_samples(ctx, key, (n_ct,))
    n_dev = int(mesh.devices.size)
    enc, _ = _build_sharded_he(ctx, mesh)
    c0, c1 = enc(
        *(_onto_mesh(mesh, _pad_rows(t, n_dev), True)
          for t in (m_res, u, e0, e1)),
        _onto_mesh(mesh, pk.b_mont, False),
        _onto_mesh(mesh, pk.a_mont, False),
    )
    return Ciphertext(c0=c0[:n_ct], c1=c1[:n_ct], scale=ctx.scale)


def decrypt_sharded(ctx: CkksContext, sk: SecretKey, ct: Ciphertext, mesh) -> jax.Array:
    """`ops.decrypt` with the [n_ct] ciphertext batch sharded over `mesh`;
    bitwise-equal coefficient residues."""
    n_ct = int(ct.c0.shape[0])
    n_dev = int(mesh.devices.size)
    _, dec = _build_sharded_he(ctx, mesh)
    res = dec(
        _onto_mesh(mesh, _pad_rows(ct.c0, n_dev), True),
        _onto_mesh(mesh, _pad_rows(ct.c1, n_dev), True),
        _onto_mesh(mesh, sk.s_mont, False),
    )
    return res[:n_ct]


def aggregate_encrypted(ctx: CkksContext, cts: Ciphertext) -> Ciphertext:
    """Homomorphic sum of a [C, n_ct, L, N]-batched ciphertext stack.

    The server loop of `aggregate_encrypted_weights` (FLPyfhelin.py:378-381)
    as one vectorized reduction; works on any host/device, no mesh needed.
    """
    p = jnp.asarray(ctx.ntt.p)
    return Ciphertext(
        c0=_lazy_sum_mod(cts.c0, p),
        c1=_lazy_sum_mod(cts.c1, p),
        scale=cts.scale,
    )


@partial(jax.jit, static_argnums=(0, 1))
def _decode_unpack(ntt, spec: PackSpec, res: jax.Array, coeffs: jax.Array):
    """Decrypted residues uint32[n_ct, L, N] -> the parameter pytree, as ONE
    program: exact mixed-radix digits, the float32 recombination by
    `coeffs` (float32[L], `encoding.decode_coefficients`), the padding cut
    and `spec.unravel`. The ring's tables and the `PackSpec` are static
    (both are built once an experiment; the specs of one tree at one ring
    compare equal, so a process traces this once a model); the decode
    scale is data, so a round with another surviving-client count runs the
    same executable. Takes the residues' sharding as it finds it
    (`decrypt_sharded`'s ciphertext axis)."""
    with jax.named_scope(obs_scopes.DECRYPT):
        return unpack_blocks(
            encoding.decode_with_coefficients(ntt, res, coeffs), spec
        )


def _decode_average(ntt, spec: PackSpec, res: jax.Array, scale: float):
    """Launch the compiled float decode at this round's scale. (A helper
    of its own: `decrypt_average`'s frame keeps its size.)"""
    obs_metrics.counter("he.decode_programs").inc()
    return _decode_unpack(
        ntt, spec, res, encoding.decode_coefficients(ntt, scale)
    )


def decrypt_average(
    ctx: CkksContext,
    sk: SecretKey,
    ct_sum: Ciphertext,
    num_clients: int | None = None,
    spec: PackSpec = None,
    exact: bool = False,
    meta: "RoundMeta | None" = None,
    mesh=None,
    packing: PackedSpec | None = None,
    base_params=None,
    hhe: bool = False,
):
    """Owner-side decrypt of the aggregated sum -> averaged parameter pytree.

    `decrypt_import_weights` (FLPyfhelin.py:263-281). Division by the
    client count happens in the decode scale — exact, no ciphertext op.
    `exact=True` routes through the host bignum CRT (the trust-boundary
    path used for final model export); default is the compiled f32 decode:
    digits, recombination and unpack as one program (`_decode_unpack`),
    launched once behind the decrypt kernel and counted by
    `he.decode_programs`.
    `mesh` (a `parallel.make_ct_mesh` mesh) shards the decrypt over the
    ciphertext axis — bitwise-equal residues, owner-side throughput scaling
    with devices (ISSUE 4).

    `packing` (a `ckks.packing.PackedSpec`) switches to the packed-quantized
    decode: the [n_ct/k, L, N] aggregate decrypts through the same core,
    then the payload integers are recovered EXACTLY (`decode_int_center` +
    one guard-rounding shift — decrypt noise cannot touch the bit fields
    while it stays under 2**(guard-1)), deinterleaved, offset-corrected by
    `surviving` (the same RoundMeta count the float path divides by), and
    dequantized into the AVERAGE update, which is added onto `base_params`
    (the round's global weights — required with `packing`). `exact` is
    moot (the packed decode is already exact); `spec` is unused.

    Under partial participation the denominator MUST be the round's
    surviving-client count, not the static experiment-wide total — dividing
    a k-client sum by C silently shrinks the model toward zero. Pass the
    masked round's `meta` (fl.faults.RoundMeta) and the decode divides by
    `meta.surviving`; `num_clients`, when also given, is cross-checked
    against the metadata's client count and a mismatch is an error (wrong
    round's metadata, or a stale static count). The pre-masking signature
    `decrypt_average(ctx, sk, ct, num_clients, spec)` keeps working: no
    meta means full participation and `num_clients` is the denominator.
    """
    if packing is None and spec is None:
        raise TypeError("decrypt_average: spec (the PackSpec) is required")
    if packing is not None and base_params is None:
        raise TypeError(
            "decrypt_average: the packed path decodes AVERAGE UPDATES — "
            "pass base_params (the round's global weights) to add them to"
        )
    if meta is not None:
        if num_clients is not None and int(num_clients) != int(meta.num_clients):
            raise ValueError(
                f"decrypt_average: caller-supplied num_clients={num_clients} "
                f"disagrees with the round metadata ({meta.num_clients} "
                "clients) — pass the RoundMeta from the SAME round (or drop "
                "num_clients and trust the metadata)"
            )
        surviving = int(meta.surviving)
        if surviving <= 0:
            raise ValueError(
                "decrypt_average: round metadata reports 0 surviving clients "
                "— the aggregate is an encryption of zero; skip the round "
                "instead of decoding it"
            )
    elif num_clients is None:
        raise TypeError(
            "decrypt_average: need num_clients or the round's RoundMeta"
        )
    else:
        surviving = int(num_clients)
    # The owner's three host steps, each a span (children of the driver's
    # `hefl.phase.decrypt` when it is open): launching the decrypt kernel,
    # launching the compiled decode (the exact and packed decodes run on
    # the host inside it), and whatever unpacking the host still does.
    with jax.named_scope(obs_scopes.DECRYPT):
        with obs_spans.span(_DECRYPT_STEP + "kernel"):
            if mesh is not None:
                res = decrypt_sharded(ctx, sk, ct_sum, mesh)
            else:
                res = ops.decrypt(ctx, sk, _on_one_device(ct_sum))
        if packing is not None:
            with obs_spans.span(_DECRYPT_STEP + "decode"):
                v = encoding.decode_int_center(ctx.ntt, res)
                if hhe:
                    # Transciphered aggregate: the decode carries the
                    # cipher's per-client wrap multiples (-2**62 * Gamma);
                    # one shifted mod-2**62 reduction recovers the exact
                    # packed sum — bitwise the direct path's decode input
                    # (hhe.cipher.hhe_center_mod; window proven by
                    # analysis.certify_transciphering).
                    from hefl_tpu.hhe.cipher import hhe_center_mod

                    v = hhe_center_mod(v, packing.guard)
                delta = unpack_quantized(v, packing, surviving)
            with obs_spans.span(_DECRYPT_STEP + "unpack"):
                base_flat, unravel = ravel_pytree(base_params)
                return unravel(base_flat + jnp.asarray(delta))
        # (the decode scale is written out twice rather than named: this
        # frame keeps the size it had, see run_experiment's note on frames)
        if exact:
            with obs_spans.span(_DECRYPT_STEP + "decode"):
                blocks = jnp.asarray(
                    encoding.decode_exact(
                        ctx.ntt, np.asarray(res), ct_sum.scale * surviving
                    ).astype(np.float32)
                )
            with obs_spans.span(_DECRYPT_STEP + "unpack"):
                return unpack_blocks(blocks, spec)
        with obs_spans.span(_DECRYPT_STEP + "decode"):
            blocks = _decode_average(
                ctx.ntt, spec, res, ct_sum.scale * surviving
            )
        # the program unpacked too (`blocks` is the pytree already): the
        # span stays, so that every round records the same set of them
        with obs_spans.span(_DECRYPT_STEP + "unpack"):
            return blocks


def secure_fedavg_round(
    module,
    cfg: TrainConfig,
    mesh,
    ctx: CkksContext,
    pk: PublicKey,
    global_params,
    xs: jax.Array,
    ys: jax.Array,
    key: jax.Array,
    with_plain_reference: bool = False,
    dp=None,
    participation=None,
    poison=None,
    num_real_clients: int | None = None,
    packing: PackedSpec | None = None,
) -> tuple:
    """One encrypted FedAvg round: local training + encrypt + psum, jitted.

    Same contract as `fedavg_round` but the output is the *encrypted sum*
    of client updates — the server (this program) never materializes any
    client's plaintext weights off its own device, and never holds sk.
    Follow with `decrypt_average(..., num_clients)` on the owner.

    xs: uint8[C, m, H, W, ch], ys: int32[C, m]. -> (Ciphertext [n_ct, L, N]
    replicated, metrics f32[C, E, 4], encode_overflow int32[C]).

    `encode_overflow[c]` counts client c's trained weights that saturated
    the encoder envelope (encoding.ENCODE_BOUND) — 0 on a healthy pipeline;
    any nonzero value means the flagship fidelity number is clipped and the
    scale must come down (VERDICT r2 weak #1's silent-saturation guard).

    with_plain_reference=True is a MEASUREMENT-ONLY mode that appends a
    final output: the plaintext FedAvg mean of the SAME in-program trained
    weights (pmean over the same mesh; the participation-masked mean when
    the round runs masked). It deliberately leaks what the encrypted path
    exists to hide — never use it in production — but it is the only way to
    check the full production pipeline (encode + encrypt + hierarchical
    psum-of-limbs + decrypt) against a plaintext reference at flagship
    scale: re-running training in a second XLA program is not
    bit-reproducible (fusion-level float differences flip the discrete
    best-epoch restore), so a cross-program comparison measures training
    chaos, not HE error. `chip_smoke.py` and the benchmark's checks use this.

    Partial participation / fault injection (`participation`, `poison` —
    same contract as fedavg.fedavg_round), a non-divisible client count
    (padded with masked-out dummies), TrainConfig.max_update_norm > 0, or
    on_overflow="exclude" route through the masked engine: dropped or
    sanitized-out clients' ciphertext limbs are zeroed (a `where` select,
    not a skipped collective — the SPMD program shape stays static) BEFORE
    the psum, and the return gains a `RoundMeta` (inserted after
    `encode_overflow`) whose `surviving` count is the public metadata
    `decrypt_average` needs for its decode denominator. An all-ones mask
    with no poison and no sanitization knobs takes the historical fast
    path: bit-identical ciphertexts, same compiled program.

    `num_real_clients` (with xs/ys pre-padded by `fedavg.pad_federated`)
    hoists the per-round padding gather out of the round — the same
    contract as `fedavg_round`.

    `packing` (a `ckks.packing.PackedSpec`) routes the upload through the
    quantized bit-interleaved encoder (`encrypt_stack_packed`): k-fold
    fewer ciphertext rows through the identical encrypt/mask/psum program
    structure, `encode_overflow` reporting quantizer saturation instead of
    encoder saturation, and `decrypt_average(..., packing=, base_params=)`
    on the owner side. packing=None is the historical float path,
    bit-for-bit (same compiled programs).
    """
    if packing is not None and packing.clients < (
        num_real_clients or int(xs.shape[0])
    ):
        raise ValueError(
            f"packing spec sized for {packing.clients} clients cannot hold "
            f"a carry-free sum over {num_real_clients or int(xs.shape[0])} "
            "— rebuild PackedSpec.for_params with the experiment's count"
        )
    n_dev = client_mesh_size(mesh)
    num_clients, pad_idx, prepadded = _round_geometry(
        xs, n_dev, num_real_clients
    )
    sanitizing = cfg.on_overflow == "exclude" or cfg.max_update_norm > 0
    explicit = participation is not None or poison is not None
    masked = masked_mode(cfg, num_clients, n_dev, explicit, secure=True)
    trivial = (
        masked
        and pad_idx is None
        and not sanitizing
        and _trivial_mask(participation, poison)
    )
    # dp=None keeps the historical 2-way split so existing seeds reproduce.
    if dp is None:
        k_train, k_enc = jax.random.split(key)
    else:
        k_train, k_enc, k_dp = jax.random.split(key, 3)
    train_keys = jax.random.split(k_train, num_clients)
    enc_keys = jax.random.split(k_enc, num_clients)
    dp_keys = jax.random.split(k_dp, num_clients) if dp is not None else None
    # Canonicalize the replicated-global-params sharding so round 1 (params
    # now a decrypt_average output) reuses round 0's executable — see
    # fedavg.replicate_on.
    gp = replicate_on(mesh, global_params)
    # Passing packing ONLY when enabled keeps the historical factory cache
    # keys (and so the compiled-program reuse) bit-for-bit untouched.
    pk_kw = {} if packing is None else {"packing": packing}
    if not masked or trivial:
        # Historical program (also the all-ones/no-poison masked call: the
        # mask cannot change the sum, so reuse the legacy executable and
        # synthesize the full-participation metadata).
        if dp is None:
            # Keep the historical 5-arg cache key: dp-off rounds of any
            # client count share one compiled program per configuration.
            fn = _build_secure_round_fn(
                module, cfg, mesh, ctx, with_plain_reference, **pk_kw
            )
            outs = fn(gp, pk, xs, ys, train_keys, enc_keys)
        else:
            fn = _build_secure_round_fn(
                module, cfg, mesh, ctx, with_plain_reference, dp, num_clients,
                **pk_kw,
            )
            outs = fn(gp, pk, xs, ys, train_keys, enc_keys, dp_keys)
        if not masked:
            return outs
        meta = RoundMeta.full_participation(num_clients)
        return outs[:3] + (meta,) + outs[3:]
    part, pois = _mask_inputs(num_clients, participation, poison, pad_idx)
    if pad_idx is not None:
        train_keys, enc_keys = train_keys[pad_idx], enc_keys[pad_idx]
        if dp_keys is not None:
            dp_keys = dp_keys[pad_idx]
        if not prepadded:
            xs, ys = xs[pad_idx], ys[pad_idx]
    fn = _build_secure_round_fn(
        module, cfg, mesh, ctx, with_plain_reference, dp, num_clients,
        masked=True, **pk_kw,
    )
    args = (gp, pk, xs, ys, train_keys, enc_keys)
    if dp is not None:
        args = args + (dp_keys,)
    outs = fn(*args + (part, pois))
    ct_sum, mets, overflow, bits = outs[:4]
    meta = RoundMeta.from_bits(np.asarray(bits)[:num_clients])
    if dp is not None and meta.surviving < calibration_clients(dp, num_clients):
        # fl.dp calibrates each client's noise share to sigma*C/sqrt(K_cal)
        # so any >= K_cal surviving shares sum to AT LEAST the central
        # mechanism's sigma*C (conservative over-noising under partial
        # participation; K_cal = num_clients when no floor is declared). A
        # round surviving BELOW the declared floor would carry less noise
        # than epsilon_spent accounts — the silently-weakened-guarantee
        # failure mode the dp path must never allow. Fail loudly instead.
        raise ValueError(
            f"dp round survived {meta.surviving} clients, below the "
            f"declared noise-calibration floor "
            f"{calibration_clients(dp, num_clients)} of {num_clients} "
            f"({meta.excluded}); the release would carry less noise than "
            "epsilon_spent accounts — raise DpConfig.min_surviving (more "
            "over-noising headroom) or reduce the fault pressure"
        )
    out = (ct_sum, mets[:num_clients], overflow[:num_clients], meta)
    return out + tuple(outs[4:])


def client_upload_body(
    module, cfg, backend, ctx, dp, dp_k, packing, want_bits,
    gp, pk, x_blk, y_blk, kt_blk, ke_blk,
    kd_blk=None, m_blk=None, po_blk=None,
    hhe_keys_blk=None, hhe_round=None, ct_shards: int = 1,
    streams_blk=None, ef_blk=None,
):
    """The per-client half of BOTH round programs: train -> dp sanitize
    (shares calibrated to dp_k) -> poison -> pack/encode/encrypt (+
    overflow count) -> exclusion predicates. ONE body shared by the
    batched secure round (`_build_secure_round_fn`, which adds the
    mask-and-psum tail) and the streaming upload producer
    (`fl.stream._build_upload_fn`, which ships the per-client rows to the
    host engine) — the streaming-vs-batched bitwise-equality gates only
    hold while the two programs trace the identical per-client ops, so
    that body must exist exactly once.

    `want_bits=False` (the unmasked legacy path) traces NO exclusion
    predicates — computing them would add ops to the historical program.
    `hhe_keys_blk` (uint32[cpd, 4] per-client symmetric master keys, with
    `hhe_round` the traced round counter) swaps the CKKS encrypt for the
    hybrid-HE symmetric cipher (`hhe_encrypt_stack`, streaming-only;
    requires `packing`): `cts` is then the (w_hi, w_lo) word-pair tuple
    the server-side transcipher consumes, everything else — training, dp,
    poison, saturation, exclusion bits — is traced identically, which is
    what makes the HHE-vs-direct parity gate hold by construction.
    `ct_shards > 1` (the 2-D ("clients", "ct") mesh, ISSUE 15) shards the
    CKKS encrypt core's ciphertext rows over the ``"ct"`` axis
    (`_ct_sharded_encrypt_core`) — bitwise-identical uploads, NTT work
    divided by the shard count; the HHE symmetric cipher has no NTTs, so
    its leg ignores the knob.

    `ef_blk` (f32[cpd, packing.total], ISSUE 19) is the per-client
    error-feedback residual block, REQUIRED when `packing.error_feedback`
    — the streaming engine owns the cross-round rows and threads them in;
    the batched one-shot round has nowhere to carry them, so an EF spec
    without an `ef_blk` refuses at trace time rather than silently
    quantizing without the residual.
    -> (cts, mets, overflow, bits | None, p_out, ef_out | None).
    """
    ef_on = packing is not None and getattr(packing, "error_feedback", False)
    if ef_on and ef_blk is None:
        raise ValueError(
            "PackingConfig.error_feedback needs the per-client residual "
            "rows (ef_blk), which only the STREAMING engine carries across "
            "rounds (fl.stream.StreamEngine) — the batched one-shot round "
            "has no cross-round state to hold them; run under a "
            "StreamConfig or drop error_feedback"
        )
    p_out, mets = train_block(
        module, cfg, gp, x_blk, y_blk, kt_blk, m_blk=m_blk, backend=backend,
        streams_blk=streams_blk,
    )
    if dp is not None:
        from hefl_tpu.fl.dp import dp_sanitize

        with jax.named_scope(obs_scopes.SANITIZE):
            # Shares calibrated to the declared surviving-cohort floor
            # (dp.min_surviving; = num_clients when none): conservative
            # over-noising so partial participation never under-noises.
            p_out, _ = jax.vmap(
                lambda k, t: dp_sanitize(k, gp, t, dp, dp_k)
            )(kd_blk, p_out)
    if po_blk is not None:
        # Fault injection corrupts the UPLOAD (after training and after
        # any DP sanitize — a poisoned client does not run its own
        # defenses); POISON_NONE is a pure where-select no-op.
        with jax.named_scope(obs_scopes.SANITIZE):
            p_out = jax.vmap(poison_tree)(p_out, po_blk)
    # Phase scope (obs): pack/encode/overflow-count + the encrypt core
    # are one hefl.encrypt trace bucket.
    ef_out = None
    with jax.named_scope(obs_scopes.ENCRYPT):
        if hhe_keys_blk is not None:
            # Hybrid-HE symmetric upload: one PRF sweep + add per slot,
            # no CKKS work on the client (the repo's cheapest upload).
            if ef_on:
                w_hi, w_lo, overflow, ef_out = hhe_encrypt_stack_ef(
                    p_out, gp, hhe_keys_blk, hhe_round, packing, ef_blk
                )
            else:
                w_hi, w_lo, overflow = hhe_encrypt_stack(
                    p_out, gp, hhe_keys_blk, hhe_round, packing
                )
            cts = (w_hi, w_lo)
        elif packing is not None:
            # Quantized bit-interleaved upload: k-fold fewer ciphertext
            # rows; `overflow` carries the quantizer saturation count
            # (same slot, same on_overflow machinery).
            if ef_on:
                cts, overflow, ef_out = encrypt_stack_packed_ef(
                    ctx, pk, p_out, gp, ke_blk, packing, ef_blk,
                    ct_shards=ct_shards,
                )
            else:
                cts, overflow = encrypt_stack_packed(
                    ctx, pk, p_out, gp, ke_blk, packing, ct_shards=ct_shards
                )                                      # [cpd, n_ct/k, ...]
        else:
            # Saturation diagnostic on exactly what gets encoded (the
            # packed blocks); XLA CSEs the duplicate pack with
            # encrypt_params' own.
            ov_one = lambda prm: encoding.encode_overflow_count(  # noqa: E731
                pack_pytree(prm, ctx.n), ctx.scale
            )
            overflow = jax.vmap(ov_one)(p_out)         # [cpd] int32
            cts = encrypt_stack(
                ctx, pk, p_out, ke_blk, ct_shards=ct_shards
            )                                          # [cpd, n_ct, L, N]
    bits = None
    if want_bits:
        with jax.named_scope(obs_scopes.SANITIZE):
            bits = exclusion_bits(cfg, gp, p_out, m_blk, overflow)
    return cts, mets, overflow, bits, p_out, ef_out


@functools.lru_cache(maxsize=32)
def _build_secure_round_fn(
    module, cfg: TrainConfig, mesh, ctx: CkksContext,
    with_plain_reference: bool = False,
    dp=None,
    num_clients: int = 0,
    masked: bool = False,
    packing: PackedSpec | None = None,
):
    """Compile-once factory for the encrypted round program (same rationale
    as fedavg._build_round_fn: one trace/compile per configuration, reused
    across all rounds). `pk` is a traced, mesh-replicated argument so key
    rotation does not retrigger compilation.

    `dp` (a frozen fl.dp.DpConfig, hashable, part of the cache key) turns
    on per-client clip-and-noise between training and encryption: the
    DP-FedAvg sanitizer runs inside this same SPMD program, so the
    plaintext clipped-but-unnoised update never leaves the device either.

    `masked` is the participation-masked engine (fl.faults): two extra
    int32[C] traced inputs (participation mask, poison codes) appended
    after the key blocks, and one extra output — the per-client exclusion
    bitmask — inserted after `encode_overflow`. A dropped or sanitized-out
    client's ciphertext limbs are ZEROED before the local lazy sum (a
    masked limb-select; zero residues are the additive identity mod p, so
    the psum-of-limbs collective and the whole SPMD program shape are
    untouched by who dropped). Masks are traced values: every round of a
    faulted run shares this one executable.
    """

    if packing is not None and getattr(packing, "error_feedback", False):
        # The batched round is ONE-SHOT: there is no cross-round state to
        # carry the quantizer residual in, so an EF spec here would
        # silently degenerate to plain low-bit quantization — exactly the
        # accuracy loss EF exists to prevent. The streaming engine owns
        # the residual rows (fl.stream.StreamEngine); refuse loudly.
        raise ValueError(
            "PackingConfig.error_feedback requires the streaming engine's "
            "cross-round residual state (fl.stream); the batched secure "
            "round cannot carry it — add a StreamConfig or drop "
            "error_feedback"
        )
    axes = client_axes(mesh)   # ("clients",) or ("hosts", "clients")
    n_dev = client_mesh_size(mesh)
    # In-round HE sharding (ISSUE 15): on a 2-D ("clients", "ct") mesh the
    # encrypt core's ciphertext rows split over the ct axis — bitwise the
    # replicated result (see _ct_sharded_encrypt_core); 1 elsewhere.
    from hefl_tpu.parallel import ct_shard_count

    ct_shards = ct_shard_count(mesh)
    # Cross-client backend resolved once per factory call (concrete
    # context; the auto micro-timing probe runs eagerly) — see
    # fedavg._build_round_fn.
    from hefl_tpu.fl.fusion import resolve_fusion_backend

    backend = resolve_fusion_backend(cfg.client_fusion, module)
    dp_k = calibration_clients(dp, num_clients) if dp is not None else 0
    # Hoisted shuffle streams (ISSUE 15): the permutation sort must lower
    # OUTSIDE the manual-sharding region — see client.epoch_index_streams.
    from hefl_tpu.fl.client import hoist_streams, hoisted_streams_jit

    hoist = hoist_streams(cfg, backend)
    from hefl_tpu.fl.fedavg import with_frozen_base
    from hefl_tpu.models.lm import is_token_model

    frozen = is_token_model(module)   # its base: the program's last argument

    def body(gp, pk, x_blk, y_blk, kt_blk, ke_blk, *rest):
        i = 0
        streams_blk = None
        if hoist:
            streams_blk, i = (rest[0], rest[1]), 2
        kd_blk = None
        if dp is not None:
            kd_blk, i = rest[i], i + 1
        m_blk, po_blk = (rest[i], rest[i + 1]) if masked else (None, None)
        cts, mets, overflow, bits, p_out, _ = client_upload_body(
            module.bind(rest[-1]) if frozen else module,
            cfg, backend, ctx, dp, dp_k, packing, masked,
            gp, pk, x_blk, y_blk, kt_blk, ke_blk,
            kd_blk=kd_blk, m_blk=m_blk, po_blk=po_blk,
            ct_shards=ct_shards, streams_blk=streams_blk,
        )
        with jax.named_scope(obs_scopes.PSUM_AGGREGATE):
            if masked:
                keep = bits == 0
                sel = keep.reshape((-1, 1, 1, 1))
                cts = Ciphertext(
                    c0=jnp.where(sel, cts.c0, jnp.uint32(0)),
                    c1=jnp.where(sel, cts.c1, jnp.uint32(0)),
                    scale=cts.scale,
                )
            local = aggregate_encrypted(ctx, cts)      # this device's clients
            p = jnp.asarray(ctx.ntt.p)
            # Per-device partials are canonical (< p < 2**27), so each stage
            # of the hierarchical reduce starts canonical: the fused XLA
            # all-reduce's lazy reduction is sound up to MAX_PSUM_CLIENTS
            # devices per axis (the ppermute ring lifts an axis past that),
            # and on a ("hosts", "clients") mesh the client axis reduces
            # over ICI before one cross-host (DCN) fold — see
            # hierarchical_psum_mod.
            outs = (
                Ciphertext(
                    c0=hierarchical_psum_mod(local.c0, p, axes),
                    c1=hierarchical_psum_mod(local.c1, p, axes),
                    scale=local.scale,
                ),
                mets,
                overflow,
            )
        if masked:
            outs = outs + (bits,)
        if with_plain_reference:
            with jax.named_scope(obs_scopes.AGGREGATE):
                if masked:
                    ref, _ = masked_mean_tree(
                        gp, p_out, keep, axes, n_dev * int(x_blk.shape[0])
                    )
                else:
                    local_mean = jax.tree_util.tree_map(
                        lambda t: jnp.mean(t, axis=0), p_out
                    )
                    ref = pmean_tree(local_mean, axes)
            outs = outs + (ref,)
        return outs

    out_specs = (P(), P(axes), P(axes))
    if masked:
        out_specs = out_specs + (P(axes),)
    if with_plain_reference:
        out_specs = out_specs + (P(),)
    in_specs = (P(), P(), P(axes), P(axes), P(axes), P(axes))
    if hoist:
        in_specs = in_specs + (P(axes), P(axes))  # hoisted shuffle streams
    if dp is not None:
        in_specs = in_specs + (P(axes),)   # per-client dp noise keys
    if masked:
        in_specs = in_specs + (P(axes), P(axes))  # participation, poison
    if frozen:
        in_specs = in_specs + (P(),)   # the frozen base, one copy a device
    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
    )
    # Streams derive from the train keys (arg 4) and insert after the
    # enc keys (arg 5) — one shared wrapper, see client.hoisted_streams_jit.
    return with_frozen_base(
        module,
        hoisted_streams_jit(fn, cfg, x_index=2, key_index=4, insert_after=5)
        if hoist else jax.jit(fn),
    )
