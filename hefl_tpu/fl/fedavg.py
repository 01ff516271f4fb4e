"""Plaintext FedAvg over the client mesh.

Reference flow (one round): `train_clients` sequentially fits each client
(/root/reference/FLPyfhelin.py:179-198), then the server averages uploads
(:366-390). Here the entire round — every client's local epochs AND the
aggregation — is one jit-compiled SPMD program: clients are laid out on the
``"clients"`` mesh axis (vmap simulates multiple clients per device when
num_clients > mesh size), and FedAvg is `pmean` over ICI.

The encrypted variant (fl.secure) swaps the pmean for CKKS
encrypt -> psum-of-limbs -> decrypt without touching this file's training
path — the two aggregators are drop-in alternatives, which is the
plaintext-vs-encrypted comparison the reference ships as notebook cell 6.
"""

from __future__ import annotations

import functools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from hefl_tpu.data.augment import rescale
from hefl_tpu.fl.client import local_train
from hefl_tpu.fl.config import TrainConfig
from hefl_tpu.fl.faults import RoundMeta, exclusion_bits, poison_tree
from hefl_tpu.models.lm import frozen_base, is_token_model, record_expert_load
from hefl_tpu.obs import scopes as obs_scopes
from hefl_tpu.parallel import (
    client_axes,
    client_mesh_size,
    pmean_tree,
    shard_map,
)


def vmapped_train(
    module, cfg: TrainConfig, gp, x_blk, y_blk, k_blk, streams_blk=None
):
    """Train one device's block of clients from the shared global weights.

    x_blk: [cpd, m, ...] — this device's clients; vmap trains them
    "concurrently" (XLA interleaves). The semantics REFERENCE backend of
    `train_block` (client_fusion="vmap"). `streams_blk` is the block's
    slice of the hoisted shuffle/augment streams
    (`client.epoch_index_streams`; the round factories always pass it on
    the flat layout so the shuffle sort never lowers inside the sharded
    region — see that docstring).
    -> (stacked weight trees [cpd, ...], metrics [cpd, E, 4]).
    """
    if streams_blk is None:
        train_one = lambda x, y, k: local_train(module, cfg, gp, x, y, k)  # noqa: E731
        return jax.vmap(train_one)(x_blk, y_blk, k_blk)
    train_one = lambda x, y, k, pm, ag: local_train(  # noqa: E731
        module, cfg, gp, x, y, k, streams=(pm, ag)
    )
    return jax.vmap(train_one)(x_blk, y_blk, k_blk, *streams_blk)


def train_block(
    module, cfg: TrainConfig, gp, x_blk, y_blk, k_blk,
    m_blk=None, backend: str | None = None, streams_blk=None,
):
    """Train one device's block of clients through the configured
    cross-client backend (TrainConfig.client_fusion; fl.fusion). The
    SINGLE training body shared by the plaintext round, the encrypted
    round, and the train_clients measurement hook — so "same keys => same
    trainings" holds across all three by construction.

    `m_blk` is the masked engine's traced participation block: the fused
    backend applies it as a per-step multiplicative update mask (a
    scheduled-out client's rows still flow through the fused GEMMs —
    static SPMD shape — but its shipped weights stay the round's global
    weights); the vmap reference trains everyone and leaves masking
    entirely to the aggregation, which is where exclusion is enforced on
    BOTH backends. `backend` lets a compile-once factory resolve the
    (possibly auto-selected) backend a single time outside the trace.
    -> (stacked weight trees [cpd, ...], metrics [cpd, E, 4]).
    """
    if backend is None:
        from hefl_tpu.fl.fusion import resolve_fusion_backend

        backend = resolve_fusion_backend(cfg.client_fusion, module)
    if backend == "fused":
        from hefl_tpu.fl.fusion import fused_train

        return fused_train(
            module, cfg, gp, x_blk, y_blk, k_blk, participation=m_blk,
            streams_blk=streams_blk,
        )
    if backend == "serial":
        return serial_train(
            module, cfg, gp, x_blk, y_blk, k_blk, streams_blk=streams_blk
        )
    return vmapped_train(
        module, cfg, gp, x_blk, y_blk, k_blk, streams_blk=streams_blk
    )


def serial_train(
    module, cfg: TrainConfig, gp, x_blk, y_blk, k_blk, streams_blk=None
):
    """`vmapped_train`'s contract with the device's clients trained one
    after another (`lax.map`): one client's activations live at a time, for
    a model whose single step already fills the chip (a token model: what
    `fl.fusion.resolve_fusion_backend` gives it).
    Same keys, same streams, same per-client program as the vmap backend."""
    return jax.lax.map(
        lambda a: local_train(
            module, cfg, gp, a[0], a[1], a[2], streams=tuple(a[3:]) or None
        ),
        (x_blk, y_blk, k_blk, *(streams_blk or ())),
    )


def masked_mean_tree(gp, p_out, keep, axes, total: int):
    """Participation-masked FedAvg aggregation of one device's stacked
    client trees — the shared masked-sum/surviving-count operator of BOTH
    aggregators (the plaintext round below; fl.secure's with_plain_reference
    output).

    keep: bool[cpd]. The formula is deliberately the legacy pmean's op
    sequence with a `where`-select and a final scale folded in:
    mean(where(keep, t, 0)) -> pmean -> * (total / psum(count)) — so an
    all-kept block degenerates BITWISE to the historical
    mean -> pmean (where(True, t, 0) selects t exactly, and total/count is
    exactly 1.0f). A round where nobody survives returns `gp` unchanged
    rather than a zero model. -> (aggregated tree, surviving count f32).
    """
    def mmean(t):
        k = keep.reshape((-1,) + (1,) * (t.ndim - 1))
        return jnp.mean(jnp.where(k, t, jnp.zeros((), t.dtype)), axis=0)

    summed = pmean_tree(jax.tree_util.tree_map(mmean, p_out), axes)
    count = jax.lax.psum(jnp.sum(keep.astype(jnp.float32)), axes)
    scale = jnp.where(count > 0, jnp.float32(total) / count, jnp.float32(0))
    out = jax.tree_util.tree_map(
        lambda t, g: jnp.where(count > 0, (t * scale).astype(t.dtype), g),
        summed, gp,
    )
    return out, count


@functools.lru_cache(maxsize=32)
def _build_round_fn(
    module, cfg: TrainConfig, mesh, stacked: bool = False, masked: bool = False
):
    """Compile-once factory: the jitted SPMD round program for one
    (module, cfg, mesh) triple. Cached so an R-round experiment traces and
    compiles the program a single time, not once per round.

    stacked=False -> (global mean, metrics): the FedAvg round.
    stacked=True  -> (per-client weight trees [C, ...], metrics): the
    train_clients measurement hook. One factory so the two programs can
    never drift apart in specs or training body.

    masked=True is the participation-masked engine (fl.faults): two extra
    int32[C] traced inputs (participation mask, poison codes) and a third
    output — the per-client exclusion bitmask. Masks are TRACED arguments,
    so every round of a faulted experiment, whatever its mask, reuses this
    one executable; the SPMD program shape never depends on who dropped."""

    axes = client_axes(mesh)   # ("clients",) or ("hosts", "clients")
    total = None if stacked else client_mesh_size(mesh)
    # Resolve the (possibly auto-selected) cross-client backend ONCE, here
    # in the factory — concrete context, so the micro-timing probe runs
    # eagerly — and bake it into the body: every round reuses the choice.
    from hefl_tpu.fl.fusion import resolve_fusion_backend

    backend = resolve_fusion_backend(cfg.client_fusion, module)
    # Hoisted shuffle streams (ISSUE 15, client.epoch_index_streams): the
    # per-client permutation sort must lower OUTSIDE the manual-sharding
    # region or XLA couples it across devices on some geometries.
    from hefl_tpu.fl.client import hoist_streams, hoisted_streams_jit

    hoist = hoist_streams(cfg, backend)
    frozen = is_token_model(module)   # its base: the program's last argument

    def body(gp, x_blk, y_blk, k_blk, *rest):
        i = 0
        streams_blk = None
        if hoist:
            streams_blk, i = (rest[0], rest[1]), 2
        m_blk, po_blk = (rest[i], rest[i + 1]) if masked else (None, None)
        p_out, mets = train_block(
            module.bind(rest[-1]) if frozen else module,
            cfg, gp, x_blk, y_blk, k_blk,
            m_blk=m_blk, backend=backend, streams_blk=streams_blk,
        )
        if stacked:
            return p_out, mets
        if not masked:
            # Phase scope (obs): the FedAvg mean + collective.
            with jax.named_scope(obs_scopes.AGGREGATE):
                local_mean = jax.tree_util.tree_map(
                    lambda t: jnp.mean(t, axis=0), p_out
                )
                return pmean_tree(local_mean, axes), mets
        with jax.named_scope(obs_scopes.SANITIZE):
            p_out = jax.vmap(poison_tree)(p_out, po_blk)
            bits = exclusion_bits(cfg, gp, p_out, m_blk)
        with jax.named_scope(obs_scopes.AGGREGATE):
            new_gp, _ = masked_mean_tree(
                gp, p_out, bits == 0, axes, total * int(x_blk.shape[0])
            )
        return new_gp, mets, bits

    in_specs = (P(), P(axes), P(axes), P(axes))
    if hoist:
        in_specs = in_specs + (P(axes), P(axes))
    out_specs = (P(axes) if stacked else P(), P(axes))
    if masked:
        in_specs = in_specs + (P(axes), P(axes))
        out_specs = out_specs + (P(axes),)
    if frozen:
        in_specs = in_specs + (P(),)   # the frozen base, one copy a device
    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
    )
    # Un-sharded region: per-client streams from per-client keys, the
    # sort lowered sanely, then fed into the manual region sharded
    # alongside the keys they derive from (one shared wrapper —
    # client.hoisted_streams_jit — so the factories cannot drift).
    return with_frozen_base(
        module,
        hoisted_streams_jit(fn, cfg, x_index=1, key_index=3)
        if hoist else jax.jit(fn),
    )


def with_frozen_base(module, program):
    """The round program as its callers call it. A model trained whole gets
    `program` itself. A model with a frozen base (models/lm/) gets the
    program with the base of the moment as its last argument: an argument,
    so it is no constant of the HLO; never donated; looked up at each call,
    so a check that plants its own seeded base reaches the next round."""
    if not is_token_model(module):
        return program
    return lambda *args: program(*args, frozen_base(module))


def cohort_bucket(cohort_size: int, num_clients: int, n_dev: int) -> int:
    """Client-slot count a cohort of `cohort_size` trains at (ISSUE 15).

    Cohort-only training gathers the sampled clients' slots before the
    fused GEMM stream, but tracing a fresh program per cohort size would
    void the no-new-compile guarantee — so cohorts pad up a small LADDER
    of power-of-two buckets (the PR-13 serving-batch idiom), each rounded
    to a multiple of the mesh's client axis so the SPMD shape stays even,
    and capped at the full registry's padded shape (a bucket can never
    cost more than the historical full-C program). Every cohort size
    inside one bucket reuses one executable; crossing a bucket compiles
    exactly once per bucket per process. An oversized cohort (more
    clients than registered) is a caller bug and fails loudly.

    Bitwise floor: when the full-C program trains >= 2 client slots per
    device, the bucket keeps >= 2 per device too. Per-client float math
    is identical at ANY per-device vmap width >= 2 (the conv batching
    rule lowers every width to the grouped form, whose per-group math is
    width-independent; the fused backend's client-batched dot_generals
    likewise) — but width 1 takes XLA's UNgrouped lowering, a different
    algorithm with different rounding. Pinning both sides of the
    cohort-vs-full gates to the grouped form is what makes "bitwise-equal
    to the full-C reference" a structural property, not a fluke
    (tests/test_cohort.py pins it on both backends).
    """
    if cohort_size < 1:
        raise ValueError(
            f"cohort_bucket: cohort_size={cohort_size} must be >= 1"
        )
    if cohort_size > num_clients:
        raise ValueError(
            f"cohort_bucket: cohort of {cohort_size} exceeds the "
            f"{num_clients} registered clients — the sampler cannot have "
            "produced this; refusing to train phantom slots"
        )
    bucket = 1 << (int(cohort_size) - 1).bit_length()   # next power of two
    bucket = -(-bucket // n_dev) * n_dev                # mesh-divisible
    full = -(-num_clients // n_dev) * n_dev             # full-C padded shape
    if full > n_dev:
        # Full-C width >= 2: keep the bucket in the grouped lowering too.
        bucket = max(bucket, 2 * n_dev)
    return min(bucket, full)


def cohort_gather_index(cohort, bucket: int) -> np.ndarray:
    """Gather index [bucket] into the REAL client rows: the sampled
    cohort first, then client 0's slot repeated for the bucket padding
    (padding slots are scheduled out of training and never fold — the
    same masked-dummy idiom as `pad_index`, so dummy padding and cohort
    padding share one masking story and cannot double-count in
    `RoundMeta.surviving`)."""
    cohort = np.asarray(cohort, dtype=np.int64)
    idx = np.zeros(int(bucket), np.int64)
    idx[: len(cohort)] = cohort
    return idx


def pad_index(num_clients: int, n_dev: int) -> np.ndarray | None:
    """Client-axis gather index that pads `num_clients` up to the next
    multiple of `n_dev` by repeating client 0's slot (the padding clients
    train on client 0's data with a recycled key and are masked OUT of
    aggregation — they exist only to keep the SPMD program shape even).
    None when no padding is needed."""
    pad = (-num_clients) % n_dev
    if pad == 0:
        return None
    return np.concatenate([np.arange(num_clients), np.zeros(pad, np.int64)])


def pad_federated(xs, ys, n_dev: int):
    """Pre-pad federated arrays ONCE per experiment: -> (xs, ys, num_real).

    The round wrappers accept `num_real_clients=num_real` alongside the
    padded arrays and skip their own per-round device-side `xs[pad_idx]`
    gather — an O(dataset) memcpy that otherwise reruns every round with
    the identical result. Host (numpy) or device arrays both work; a
    divisible client count returns the inputs untouched.
    """
    num = int(xs.shape[0])
    idx = pad_index(num, n_dev)
    if idx is None:
        return xs, ys, num
    return xs[idx], ys[idx], num


def _round_geometry(xs, n_dev: int, num_real_clients: int | None):
    """Shared round-entry geometry: -> (num_clients, pad_idx, prepadded).

    `num_real_clients` marks xs/ys as PRE-PADDED by `pad_federated` (the
    hoisted-gather contract): the wrapper then skips its own data gather
    and only pads the cheap per-client key/mask arrays. Shape mismatches
    fail loudly — silently averaging padding rows as real clients is the
    one outcome this contract must never allow."""
    if num_real_clients is None:
        num_clients = int(xs.shape[0])
        return num_clients, pad_index(num_clients, n_dev), False
    num_clients = int(num_real_clients)
    pad_idx = pad_index(num_clients, n_dev)
    want = num_clients if pad_idx is None else len(pad_idx)
    if int(xs.shape[0]) != want:
        raise ValueError(
            f"num_real_clients={num_clients} on a {n_dev}-device mesh "
            f"needs federated arrays pre-padded to {want} rows "
            f"(fedavg.pad_federated), got {int(xs.shape[0])}"
        )
    return num_clients, pad_idx, True


def _mask_inputs(num_clients: int, participation, poison, pad_idx):
    """Canonicalize (participation, poison) to padded int32 device arrays.
    Padding slots are scheduled OUT (mask 0) and unpoisoned."""
    part = (
        np.ones(num_clients, np.int32)
        if participation is None
        else np.asarray(participation).astype(np.int32).reshape(num_clients)
    )
    pois = (
        np.zeros(num_clients, np.int32)
        if poison is None
        else np.asarray(poison).astype(np.int32).reshape(num_clients)
    )
    if pad_idx is not None:
        pad = len(pad_idx) - num_clients
        part = np.concatenate([part, np.zeros(pad, np.int32)])
        pois = np.concatenate([pois, np.zeros(pad, np.int32)])
    return jnp.asarray(part), jnp.asarray(pois)


def replicate_on(mesh, tree):
    """Commit a pytree to the mesh with replicated (P()) sharding.

    Round programs take the global params replicated; an aval whose sharding
    differs between calls (fresh `create_model` output is SingleDeviceSharding,
    a decrypted aggregate is NamedSharding) would recompile the whole round
    program on round 1 (measured: a second full XLA compile, ~44 s on TPU at
    the flagship shape). Canonicalizing here makes every round hit the
    round-0 executable; a no-op when the sharding already matches.
    """
    rep = jax.sharding.NamedSharding(mesh, P())
    return jax.tree_util.tree_map(lambda t: jax.device_put(t, rep), tree)


def masked_mode(
    cfg: TrainConfig, num_clients: int, n_dev: int, explicit: bool,
    secure: bool = False,
) -> bool:
    """SINGLE source of the masked-engine routing predicate, shared by
    `fedavg_round`, `fl.secure.secure_fedavg_round`, and the experiment
    driver — the round functions' return arity (meta appended or not)
    follows this predicate, so encoding it once keeps the producers and
    the driver's unpack from ever drifting. `explicit` = the caller passed
    a participation mask or poison codes; `secure` enables the
    encrypted-path-only on_overflow signal."""
    sanitizing = cfg.max_update_norm > 0 or (
        secure and cfg.on_overflow == "exclude"
    )
    return explicit or num_clients % n_dev != 0 or sanitizing


def _trivial_mask(participation, poison) -> bool:
    """True when the caller's mask/poison cannot change the round's result:
    the all-ones / no-poison case routes to the legacy executable, so a
    robustness-enabled driver whose schedule happens to be clean this round
    reproduces historical seeds bit-for-bit AND compiles no extra program."""
    ok = participation is None or bool(np.all(np.asarray(participation) != 0))
    return ok and (poison is None or not np.any(np.asarray(poison)))


def fedavg_round(
    module,
    cfg: TrainConfig,
    mesh,
    global_params,
    xs: jax.Array,
    ys: jax.Array,
    key: jax.Array,
    participation=None,
    poison=None,
    num_real_clients: int | None = None,
):
    """One synchronous FedAvg round.

    xs: uint8[C, m, H, W, ch], ys: int32[C, m] federated arrays (C clients,
    axis 0 sharded over the mesh). -> (new_global_params, metrics[C, E, 4]).

    Partial participation (`participation`: int-like[C], 0 = scheduled
    out), fault injection (`poison`: fl.faults POISON_* codes[C]), a
    non-divisible client count (padded with masked-out dummy clients), or
    TrainConfig.max_update_norm > 0 route the round through the masked
    engine, which appends a third output: the round's `fl.faults.RoundMeta`
    (who aggregated, who was excluded and why). An all-ones mask with no
    poison and no sanitization knobs takes the historical fast path —
    bit-identical outputs, same compiled program, meta of all-zeros bits.

    `num_real_clients` (with xs/ys pre-padded by `pad_federated`) hoists
    the per-round padding gather out of the round: masks/keys/meta follow
    the real count, the data gather is skipped.
    """
    n_dev = client_mesh_size(mesh)
    num_clients, pad_idx, prepadded = _round_geometry(
        xs, n_dev, num_real_clients
    )
    explicit = participation is not None or poison is not None
    masked = masked_mode(cfg, num_clients, n_dev, explicit)
    client_keys = jax.random.split(key, num_clients)
    gp = replicate_on(mesh, global_params)
    if not masked:
        return _build_round_fn(module, cfg, mesh)(gp, xs, ys, client_keys)
    if (
        pad_idx is None
        and cfg.max_update_norm <= 0
        and _trivial_mask(participation, poison)
    ):
        new_p, mets = _build_round_fn(module, cfg, mesh)(gp, xs, ys, client_keys)
        return new_p, mets, RoundMeta.full_participation(num_clients)
    part, pois = _mask_inputs(num_clients, participation, poison, pad_idx)
    if pad_idx is not None:
        client_keys = client_keys[pad_idx]
        if not prepadded:
            xs, ys = xs[pad_idx], ys[pad_idx]
    new_p, mets, bits = _build_round_fn(module, cfg, mesh, masked=True)(
        gp, xs, ys, client_keys, part, pois
    )
    meta = RoundMeta.from_bits(np.asarray(bits)[:num_clients])
    return new_p, mets[:num_clients], meta


def train_clients(
    module,
    cfg: TrainConfig,
    mesh,
    global_params,
    xs: jax.Array,
    ys: jax.Array,
    key: jax.Array,
    num_real_clients: int | None = None,
):
    """Train every client from the global weights, returning the stacked
    per-client weight trees (leaves [C, ...]) and metrics [C, E, 4].

    Uses the same per-client key derivation as `fedavg_round` (split(key, C)),
    so `train_clients(..., k_train)` reproduces the trainings inside
    `secure_fedavg_round(..., key)` when `k_train, _ = jax.random.split(key)`.
    A client count that does not divide the mesh is padded (client 0's data,
    recycled key) and the padding rows sliced off the outputs;
    `num_real_clients` marks pre-padded inputs (see `fedavg_round`).
    """
    n_dev = client_mesh_size(mesh)
    num_clients, pad_idx, prepadded = _round_geometry(
        xs, n_dev, num_real_clients
    )
    client_keys = jax.random.split(key, num_clients)
    gp = replicate_on(mesh, global_params)
    if pad_idx is not None:
        client_keys = client_keys[pad_idx]
        if not prepadded:
            xs, ys = xs[pad_idx], ys[pad_idx]
    p_out, mets = _build_round_fn(module, cfg, mesh, stacked=True)(
        gp, xs, ys, client_keys
    )
    if pad_idx is not None:
        p_out = jax.tree_util.tree_map(lambda t: t[:num_clients], p_out)
        mets = mets[:num_clients]
    return p_out, mets


@partial(jax.jit, static_argnums=(0, 3))
def _predict_all(module, params, x_u8, batch_size: int):
    """Whole-dataset inference as ONE device program: a lax.scan over fixed
    batches, so the device pays a single dispatch + transfer
    instead of one host round-trip per batch."""
    nb = x_u8.shape[0] // batch_size
    xb = x_u8.reshape(nb, batch_size, *x_u8.shape[1:])

    def step(_, xc):
        # Phase scope (obs): test-set inference is the hefl.evaluate bucket.
        with jax.named_scope(obs_scopes.EVALUATE):
            return None, jax.nn.softmax(
                module.apply({"params": params}, rescale(xc))
            )

    _, probs = jax.lax.scan(step, None, xb)
    return probs.reshape(nb * batch_size, probs.shape[-1])


@partial(jax.jit, static_argnums=0)
def _token_metrics(module, params, base, tokens):
    """Held-out sequences through the model, one after another: -> (mean
    loss, mean next-token accuracy, every expert layer's load summed)."""
    bound = module.bind(base)

    def one(seq):
        with jax.named_scope(obs_scopes.EVALUATE):
            loss, (_, acc, loads) = bound.loss({"params": params}, seq[None])
            return loss, acc, loads

    loss, acc, loads = jax.lax.map(one, tokens)
    return jnp.mean(loss), jnp.mean(acc), jnp.sum(loads, axis=0)


def _evaluate_tokens(module, params, tokens) -> dict:
    """`evaluate` for a token model: next-token accuracy over every position
    of the held-out sequences. With one label a position and every position
    counted once, micro-averaged precision, recall and F1 all equal the
    accuracy, and that is what they report: no classes x classes confusion
    matrix (a vocabulary squared) is built. Also records the experts' load
    (gauge `moe.load_max_over_mean`)."""
    loss, acc, loads = _token_metrics(
        module, params, frozen_base(module), jnp.asarray(tokens)
    )
    record_expert_load(loads)
    acc = float(acc)
    return {"accuracy": acc, "precision": acc, "recall": acc, "f1": acc,
            "loss": float(loss)}


def evaluate(
    module,
    params,
    x: np.ndarray,
    y: np.ndarray,
    batch_size: int = 32,
    return_probs: bool = False,
):
    """Full-dataset inference + metrics — the `agg_model.predict(test_ds)`
    + sklearn step of notebook cell 3. Handles the ragged final batch by
    padding to the chunk size (static shapes for jit) and masking.

    -> dict with accuracy / weighted precision / recall / f1 (+ probs).
    """
    from hefl_tpu.fl.metrics import classification_metrics

    if is_token_model(module):
        return _evaluate_tokens(module, params, x)
    n = len(x)
    pad = (-n) % batch_size
    if isinstance(x, jax.Array):
        # Already device-resident (e.g. prefetched during training to hide
        # the host->device transfer): pad on device, no host round-trip.
        x_pad = jnp.concatenate([x, jnp.repeat(x[:1], pad, axis=0)]) if pad else x
    else:
        x_pad = np.concatenate([x, np.repeat(x[:1], pad, axis=0)]) if pad else x
        x_pad = jnp.asarray(x_pad)
    probs = np.asarray(_predict_all(module, params, x_pad, batch_size))[:n]
    out = classification_metrics(y, probs.argmax(-1))
    if return_probs:
        out["probs"] = probs
    return out
