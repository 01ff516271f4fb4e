"""Functional client-local training with Keras-callback semantics.

The reference's client loop is `model.fit(..., callbacks=[ModelCheckpoint,
EarlyStopping(patience=5, restore_best_weights=True),
ReduceLROnPlateau(patience=2, factor=0.3, min_lr=1e-6)])`
(/root/reference/FLPyfhelin.py:184-196). Keras callbacks are host-side
mutable objects; here the whole local-training run — SGD steps, validation,
early stopping, LR plateau, best-weight restore — is ONE pure function
`local_train` built from `lax.scan`, so it jits, vmaps across clients on a
device, and shard_maps across the mesh. Early stopping becomes masking
(a stopped client's state is frozen through remaining epochs — lockstep
cost, functional semantics), which is what lets 16 clients with different
stopping epochs share one compiled program.

Two scan layouts implement the identical math (`TrainConfig.flat_scan`):

  * flat (default) — ONE steps-major scan over all E*S SGD steps, with the
    per-epoch shuffles, augment keys, and the training labels' one-hot all
    precomputed OUTSIDE the step body; validation + callback logic runs
    under a `lax.cond` on the S-th step of each epoch. One scan body means
    XLA optimizes a single step program (no nested-loop prologue per
    epoch), and hoisting the index/one-hot work shrinks that body to the
    conv/GEMM core.
  * nested — the historical scan-over-epochs-of-scan-over-steps, kept so
    the equivalence is a regression test (tests/test_perf.py) rather than
    an article of faith.

`TrainConfig.accum_steps > 1` fuses that many micro-batches into each
optimizer step (one forward/backward over the union — the mean-loss
gradient equals the mean of per-micro-batch gradients), feeding the MXU
GEMMs `accum_steps`x larger without touching the Adam/decay update math.

Also fixes (knowingly — SURVEY.md §2.5) the reference's quirk of carrying
one model object across clients: every client here starts exactly from the
round's global weights.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from hefl_tpu.data.augment import random_augment, rescale
from hefl_tpu.fl.config import TrainConfig
from hefl_tpu.obs import scopes as obs_scopes
from hefl_tpu.fl.loss import accuracy, cross_entropy, loss_fn, token_loss_fn
from hefl_tpu.models.lm import is_token_model
from hefl_tpu.fl.optimizer import AdamState, adam_init, adam_update


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ClientState:
    params: object
    opt: AdamState
    lr_scale: jax.Array          # f32: ReduceLROnPlateau multiplier
    best_params: object          # ModelCheckpoint best-by-accuracy
    best_loss_params: object     # EarlyStopping best-by-val-loss (restore target)
    best_val_acc: jax.Array
    best_val_loss: jax.Array
    wait_es: jax.Array           # epochs since val-loss improvement (early stop)
    wait_plateau: jax.Array      # epochs since val-loss improvement (LR plateau)
    stopped: jax.Array           # bool


def _eval_metrics(module, params, x_u8, y_onehot):
    # Phase scope (obs): the per-epoch validation forward is its own trace
    # bucket, distinct from the surrounding SGD steps.
    if is_token_model(module):  # sequences label themselves: no one-hot
        with jax.named_scope(obs_scopes.VAL):
            return token_loss_fn(module, params, x_u8)[1]
    with jax.named_scope(obs_scopes.VAL):
        logits = module.apply({"params": params}, rescale(x_u8))
        return cross_entropy(logits, y_onehot), accuracy(logits, y_onehot)


def init_ef_residuals(template_params, num_clients: int) -> jnp.ndarray:
    """Fresh error-feedback residual state (ISSUE 19): one f32 row per
    REGISTERED client over the raveled parameter count, all zeros — the
    first EF round quantizes the bare update, exactly like the plain
    quantizer.

    The residual is deliberately NOT a `ClientState` field: ClientState is
    the carry of ONE round's local-training scan, rebuilt fresh at the
    round's global weights every round, while the residual must survive
    ACROSS rounds (it is the quantizer's memory, not the optimizer's).
    `fl.stream.StreamEngine` owns the rows as cross-round state and
    threads each cohort's slice through the upload program as a donated
    traced input — the same donation discipline `local_train_epochs_jit`
    applies to the optimizer state, for the same buffer-reuse reason.
    """
    from jax.flatten_util import ravel_pytree

    flat, _ = ravel_pytree(template_params)
    return jnp.zeros((int(num_clients), int(flat.size)), jnp.float32)


def init_client_state(global_params) -> ClientState:
    """Fresh per-client training state at the round's global weights — the
    carry of the pure epoch program (and the unit a chunk-resumable driver
    checkpoints between epochs)."""
    return ClientState(
        params=global_params,
        opt=adam_init(global_params),
        lr_scale=jnp.float32(1.0),
        best_params=global_params,
        best_loss_params=global_params,
        best_val_acc=jnp.float32(-jnp.inf),
        best_val_loss=jnp.float32(jnp.inf),
        wait_es=jnp.int32(0),
        wait_plateau=jnp.int32(0),
        stopped=jnp.bool_(False),
    )


@dataclasses.dataclass(frozen=True)
class _TrainSplit:
    """Static geometry + split views of one client's data (host-side)."""

    x_tr: jax.Array
    y_tr: jax.Array
    x_va: jax.Array
    onehot_va: jax.Array
    n_tr: int
    grp: int        # samples consumed per optimizer step (bs * accum)
    steps: int      # optimizer steps per epoch


def train_batch_geometry(cfg: TrainConfig, n_samples: int) -> tuple[int, int, int]:
    """Static geometry of one client's local-train scan at `n_samples`
    samples: -> (n_tr, grp, steps). `grp` is samples consumed per
    optimizer step (batch_size x clamped accum_steps), `steps` is
    optimizer steps per epoch. The SINGLE source shared by `_train_split`,
    the fused trainer and the benchmark's sample counts, so that none can
    drift from the geometry training actually runs. Returns (n_tr, 0, 0)
    when the client is too small to train (n_tr < 1) — `_train_split`
    raises on that, drivers should not feed it.
    """
    n_val = max(int(n_samples * cfg.val_fraction), 1) if cfg.val_fraction > 0 else 0
    n_tr = n_samples - n_val
    if n_tr < 1:
        return n_tr, 0, 0
    bs = min(cfg.batch_size, n_tr)
    # accum_steps fuses micro-batches into one optimizer step; clamp so a
    # small client still takes at least one step per epoch.
    accum = max(1, min(int(cfg.accum_steps), n_tr // bs))
    grp = bs * accum
    steps = max(n_tr // grp, 1)
    return n_tr, grp, steps


def _train_split(cfg: TrainConfig, x: jax.Array, y: jax.Array) -> _TrainSplit:
    m = int(x.shape[0])
    n_tr, grp, steps = train_batch_geometry(cfg, m)
    n_val = m - n_tr
    if n_tr < 1:
        raise ValueError(
            f"client has {m} sample(s); needs >= 2 to carve out a validation "
            "split (set val_fraction=0 to train on everything)"
        )
    # Keras validation_split semantics: HEAD fraction is validation
    # (data.partition.train_val_split documents the same convention).
    x_tr, y_tr = x[n_val:], y[n_val:]
    if n_val:
        x_va, y_va = x[:n_val], y[:n_val]
    else:  # degenerate config: validate on the train slice
        x_va, y_va = x_tr, y_tr
    onehot_va = jax.nn.one_hot(y_va, cfg.num_classes, dtype=jnp.float32)
    return _TrainSplit(
        x_tr=x_tr, y_tr=y_tr, x_va=x_va, onehot_va=onehot_va,
        n_tr=n_tr, grp=grp, steps=steps,
    )


def _epoch_update(
    cfg: TrainConfig,
    state: ClientState,
    params,
    opt,
    val_loss: jax.Array,
    val_acc: jax.Array,
    track_best_acc: bool,
):
    """The pure Keras-callback transition at an epoch boundary: given the
    end-of-epoch weights and validation metrics, produce the next
    ClientState and the epoch's metrics row [val_loss, val_acc, lr_scale,
    stopped]. Shared verbatim by the flat and nested scan layouts so their
    selection semantics (early-stop / plateau / restore) cannot drift."""
    frozen = state.stopped  # already stopped before this epoch
    loss_improved = val_loss < state.best_val_loss - cfg.min_delta
    acc_improved = val_acc > state.best_val_acc
    wait_es = jnp.where(loss_improved, 0, state.wait_es + 1)
    wait_pl = jnp.where(loss_improved, 0, state.wait_plateau + 1)
    plateau = wait_pl >= cfg.plateau_patience
    lr_floor = cfg.min_lr / cfg.lr if cfg.lr > 0 else 0.0
    lr_scale = jnp.where(
        plateau,
        jnp.maximum(state.lr_scale * cfg.plateau_factor, lr_floor),
        state.lr_scale,
    )
    wait_pl = jnp.where(plateau, 0, wait_pl)
    stopped_now = wait_es >= cfg.es_patience

    pick = lambda new, old: jax.tree_util.tree_map(  # noqa: E731
        lambda a, b: jnp.where(frozen, b, a), new, old
    )
    sel = lambda new, old: jnp.where(frozen, old, new)  # noqa: E731
    take_best = jnp.logical_and(acc_improved, jnp.logical_not(frozen))
    take_best_loss = jnp.logical_and(loss_improved, jnp.logical_not(frozen))
    new_state = ClientState(
        params=pick(params, state.params),
        opt=pick(opt, state.opt),
        lr_scale=sel(lr_scale, state.lr_scale),
        # best-by-accuracy (ModelCheckpoint) is only ever read by the
        # centralized train_server path; clients skip the per-epoch
        # full-tree select (track_best_acc=False -> XLA DCEs the copy).
        best_params=(
            jax.tree_util.tree_map(
                lambda a, b: jnp.where(take_best, a, b),
                params, state.best_params,
            )
            if track_best_acc
            else state.best_params
        ),
        best_loss_params=jax.tree_util.tree_map(
            lambda a, b: jnp.where(take_best_loss, a, b),
            params, state.best_loss_params,
        ),
        best_val_acc=sel(jnp.maximum(val_acc, state.best_val_acc), state.best_val_acc),
        best_val_loss=sel(
            jnp.minimum(val_loss, state.best_val_loss), state.best_val_loss
        ),
        wait_es=sel(wait_es, state.wait_es),
        wait_plateau=sel(wait_pl, state.wait_plateau),
        stopped=jnp.logical_or(frozen, stopped_now),
    )
    metrics = jnp.stack(
        [val_loss, val_acc, new_state.lr_scale, new_state.stopped.astype(jnp.float32)]
    )
    return new_state, metrics


def _make_train_step(module, cfg: TrainConfig, global_params, sp: _TrainSplit):
    """The SGD micro-step shared by both scan layouts: gather a batch by
    precomputed indices, augment, grad, Adam. `oh_tr` (the training
    labels' one-hot, materialized once outside the scan) is closed over so
    the step body gathers rows instead of re-encoding labels per step."""
    if is_token_model(module):
        return _make_token_train_step(module, cfg, global_params, sp)
    with jax.named_scope(obs_scopes.SGD_CORE):
        oh_tr = jax.nn.one_hot(sp.y_tr, cfg.num_classes, dtype=jnp.float32)

    def train_step(params, opt, lr_scale, idx, k_aug):
        # Phase scopes (obs): the SGD core is one trace bucket; the augment
        # warp nests its own deeper hefl.augment scope inside it and wins
        # attribution for its ops. Scopes wrap only this leaf step body —
        # the scan/while op at the call site stays scope-less on purpose
        # (obs.scopes docstring).
        with jax.named_scope(obs_scopes.SGD_CORE):
            with jax.named_scope(obs_scopes.BATCH):
                xb = rescale(sp.x_tr[idx])
            if cfg.augment:
                xb = random_augment(
                    k_aug, xb, shear=cfg.aug_shear, zoom=cfg.aug_zoom,
                    flip=cfg.aug_flip, backend=cfg.aug_backend,
                )
            with jax.named_scope(obs_scopes.BATCH):
                oh = oh_tr[idx]
            grads, (ce, acc) = jax.grad(
                lambda p: loss_fn(module, p, xb, oh, global_params, cfg.prox_mu),
                has_aux=True,
            )(params)
            with jax.named_scope(obs_scopes.ADAM):
                params, opt = adam_update(
                    grads, opt, params, cfg.lr, cfg.lr_decay, lr_scale,
                    warmup_steps=cfg.warmup_steps,
                )
        return params, opt, (ce, acc)

    return train_step


def _make_token_train_step(module, cfg: TrainConfig, global_params, sp: _TrainSplit):
    """The same micro-step for a token model: a batch is `grp` sequences x
    positions, gathered by the same precomputed indices; no rescale, no
    augment, no one-hot. The gradient is taken with respect to `params`, the
    trained subset, alone: the module's frozen base is no argument of it, so
    no weight gradient of a frozen matrix is formed."""

    def train_step(params, opt, lr_scale, idx, k_aug):
        with jax.named_scope(obs_scopes.SGD_CORE):
            with jax.named_scope(obs_scopes.BATCH):
                xb = sp.x_tr[idx]
            grads, (ce, acc) = jax.grad(
                lambda p: token_loss_fn(module, p, xb, global_params, cfg.prox_mu),
                has_aux=True,
            )(params)
            with jax.named_scope(obs_scopes.ADAM):
                params, opt = adam_update(
                    grads, opt, params, cfg.lr, cfg.lr_decay, lr_scale,
                    warmup_steps=cfg.warmup_steps,
                )
        return params, opt, (ce, acc)

    return train_step


def _epoch_streams(epoch_keys: jax.Array, sp: _TrainSplit):
    """Per-epoch shuffles + augment keys, derived EXACTLY as the nested
    layout derives them inside its epoch body (split -> permutation /
    per-step aug keys), but materialized up front: -> (perms [E, S, grp],
    aug_keys [E, S])."""
    ks = jax.vmap(jax.random.split)(epoch_keys)          # [E, 2]
    k_perm, k_aug = ks[:, 0], ks[:, 1]
    perms = jax.vmap(
        lambda k: jax.random.permutation(k, sp.n_tr)[
            : sp.steps * sp.grp
        ].reshape(sp.steps, sp.grp)
    )(k_perm)
    aug_keys = jax.vmap(lambda k: jax.random.split(k, sp.steps))(k_aug)
    return perms, aug_keys


def epoch_index_streams(cfg: TrainConfig, client_keys: jax.Array, n_samples: int):
    """Every client's flattened shuffle/augment streams for one round,
    derived OUTSIDE the sharded round program (ISSUE 15): -> (perms
    int32[C, E*S, grp], aug_keys key[C, E*S]).

    The derivation is bitwise `local_train`'s (split(key, epochs) ->
    `_epoch_streams`, vmapped over clients) — same keys => same streams.
    It is HOISTED to the un-sharded jit level because
    `jax.random.permutation`'s sort, lowered inside a `shard_map`
    (manual-sharding) region, partitions ACROSS devices on some
    geometries: XLA emits a cross-partition all-reduce over the sort
    keys (observed on the virtual CPU mesh at e.g. [C=8, n_tr=24]),
    silently coupling every client's shuffle to every other client's key
    — training then depends on which device a client lands on, which
    breaks per-client key isolation and with it every
    placement-independence property the cohort gather and the 2-D mesh
    rely on. Outside the manual region the sort lowers per row and each
    client's stream is a function of its own key alone. The round
    factories feed these streams in as sharded traced inputs; the
    in-body derivation remains for unsharded direct callers
    (`local_train`) and the nested semantics-reference layout.
    """
    import types

    n_tr, grp, steps = train_batch_geometry(cfg, int(n_samples))
    sp = types.SimpleNamespace(n_tr=n_tr, grp=grp, steps=steps)
    e = int(cfg.epochs)

    def one(k):
        epoch_keys = jax.random.split(k, e)
        perms, aug = _epoch_streams(epoch_keys, sp)
        return perms.reshape(e * steps, grp), aug.reshape(e * steps)

    return jax.vmap(one)(client_keys)


def hoist_streams(cfg: TrainConfig, backend: str) -> bool:
    """SINGLE source of the hoisted-shuffle-streams predicate shared by
    all three round factories (fedavg/secure/stream): the fused backend
    always runs the flat layout, the vmap backend hoists when the config
    does (the nested flat_scan=False layout keeps its in-body derivation
    as the unsharded semantics reference)."""
    return backend == "fused" or bool(cfg.flat_scan)


def hoisted_streams_jit(
    fn, cfg: TrainConfig, x_index: int, key_index: int,
    insert_after: int | None = None, donate_argnums=(),
):
    """Wrap a shard_map'd round body in the un-sharded stream hoist and
    jit it — the ONE wrapper all three round factories share, so the
    hoist's derivation point cannot drift between them (ISSUE 15).

    `fn`'s signature must accept the two stream arrays (perms, aug_keys)
    immediately AFTER argument `insert_after` (default: `key_index` —
    the per-client train-key block the streams derive from; the secure
    factories insert after their enc-key block instead); `x_index` names
    the federated data array whose axis 1 is the per-client sample
    count. `donate_argnums` indexes the OUTER signature (without the two
    inserted stream arrays) — used for pure carry buffers like the
    error-feedback residual rows (ISSUE 19).
    """
    if insert_after is None:
        insert_after = key_index

    def outer(*args):
        perms, aug = epoch_index_streams(
            cfg, args[key_index], args[x_index].shape[1]
        )
        head = args[: insert_after + 1]
        rest = args[insert_after + 1:]
        return fn(*head, perms, aug, *rest)

    return jax.jit(outer, donate_argnums=tuple(donate_argnums))


def _local_train_epochs_flat(
    module, cfg: TrainConfig, global_params, x, y,
    state: ClientState, epoch_keys, track_best_acc: bool,
    streams=None,
):
    """ONE steps-major scan over all E*S SGD steps. Validation + callback
    logic fires under a `lax.cond` on each epoch's final step (the cond
    predicate is an unbatched function of the step index, so it stays a
    real branch — no validation cost on interior steps — even under the
    cross-client vmap). `streams` (flat_perm [E*S, grp], flat_aug [E*S])
    swaps the in-body shuffle derivation for precomputed arrays — the
    hoisted round-program path (`epoch_index_streams`); the values are
    identical by construction, only the place the sort lowers changes."""
    sp = _train_split(cfg, x, y)
    e = int(epoch_keys.shape[0])
    with jax.named_scope(obs_scopes.SGD_CORE):
        if streams is None:
            # Shuffle/key prologue is SGD machinery: attribute it there.
            perms, aug_keys = _epoch_streams(epoch_keys, sp)
            flat_perm = perms.reshape(e * sp.steps, sp.grp)
            flat_aug = aug_keys.reshape(e * sp.steps)
        else:
            flat_perm, flat_aug = streams
        is_end = (jnp.arange(e * sp.steps) % sp.steps) == sp.steps - 1
    train_step = _make_train_step(module, cfg, global_params, sp)

    def flat_step(carry, inp):
        params_run, opt_run, st = carry
        idx, k_aug, end = inp
        params_run, opt_run, _ = train_step(
            params_run, opt_run, st.lr_scale, idx, k_aug
        )

        def boundary(p, o, s0):
            frozen = s0.stopped
            # Evaluate the params this epoch actually keeps: a stopped
            # client's phantom-trained weights are discarded by
            # _epoch_update, so its reported val metrics must come from
            # the frozen weights.
            eval_params = jax.tree_util.tree_map(
                lambda new, old: jnp.where(frozen, old, new), p, s0.params
            )
            val_loss, val_acc = _eval_metrics(
                module, eval_params, sp.x_va, sp.onehot_va
            )
            ns, mets = _epoch_update(
                cfg, s0, p, o, val_loss, val_acc, track_best_acc
            )
            # The next epoch's steps restart from the state the callbacks
            # kept (frozen weights for a stopped client) — exactly the
            # nested layout's "inner scan starts from state.params".
            return ns.params, ns.opt, ns, mets

        def interior(p, o, s0):
            return p, o, s0, jnp.zeros((4,), jnp.float32)

        # The cond IS the validation phase: its per-iteration trace event
        # covers only the executed branch (boundary = the val eval +
        # callback transition; interior = a tuple passthrough), so scoping
        # the cond attributes val cost without swallowing interior steps.
        with jax.named_scope(obs_scopes.VAL):
            params_run, opt_run, st, mets = jax.lax.cond(
                end, boundary, interior, params_run, opt_run, st
            )
        return (params_run, opt_run, st), mets

    (_, _, final), mets = jax.lax.scan(
        flat_step, (state.params, state.opt, state), (flat_perm, flat_aug, is_end)
    )
    return final, mets[sp.steps - 1 :: sp.steps]


def _local_train_epochs_nested(
    module, cfg: TrainConfig, global_params, x, y,
    state: ClientState, epoch_keys, track_best_acc: bool,
):
    """The historical nested layout: scan over epochs, each epoch scanning
    its steps and deriving its shuffle inside the body. Kept behind
    `flat_scan=False` as the semantics reference for the flat layout."""
    sp = _train_split(cfg, x, y)
    train_step = _make_train_step(module, cfg, global_params, sp)

    def scan_step(carry, inp):
        params, opt, lr_scale = carry
        idx, k_aug = inp
        params, opt, (ce, acc) = train_step(params, opt, lr_scale, idx, k_aug)
        return (params, opt, lr_scale), (ce, acc)

    def epoch_step(st: ClientState, k_epoch):
        with jax.named_scope(obs_scopes.SGD_CORE):
            k_perm, k_aug = jax.random.split(k_epoch)
            perm = jax.random.permutation(k_perm, sp.n_tr)[
                : sp.steps * sp.grp
            ].reshape(sp.steps, sp.grp)
            aug_keys = jax.random.split(k_aug, sp.steps)
        (params, opt, _), _ = jax.lax.scan(
            scan_step, (st.params, st.opt, st.lr_scale), (perm, aug_keys)
        )
        with jax.named_scope(obs_scopes.VAL):
            frozen = st.stopped
            eval_params = jax.tree_util.tree_map(
                lambda new, old: jnp.where(frozen, old, new), params, st.params
            )
            val_loss, val_acc = _eval_metrics(
                module, eval_params, sp.x_va, sp.onehot_va
            )
            return _epoch_update(cfg, st, params, opt, val_loss, val_acc,
                                 track_best_acc)

    return jax.lax.scan(epoch_step, state, epoch_keys)


def local_train_epochs(
    module,
    cfg: TrainConfig,
    global_params,
    x: jax.Array,
    y: jax.Array,
    state: ClientState,
    epoch_keys: jax.Array,
    track_best_acc: bool = True,
    streams=None,
):
    """Advance the client program by `len(epoch_keys)` epochs from `state`.

    The chunk-resume primitive (VERDICT r4 item 3): a driver that cannot
    afford the full `cfg.epochs` in one process slices the precomputed
    per-epoch key array, checkpoints the returned ClientState between
    invocations, and ends with exactly the same callback semantics
    (`client_shipped_params(state)` is the client-upload restore). Jit
    with the state donated (`local_train_epochs_jit`, or
    `donate_argnums` on your own wrapper) so the chunked driver holds ONE
    resident copy of the carry instead of input+output.
    -> (state, metrics f32[len(epoch_keys), 4]).
    """
    if cfg.flat_scan:
        return _local_train_epochs_flat(
            module, cfg, global_params, x, y, state, epoch_keys,
            track_best_acc, streams=streams,
        )
    if streams is not None:
        raise ValueError(
            "precomputed shuffle streams are a flat-scan feature; the "
            "nested semantics-reference layout derives its own in-body"
        )
    return _local_train_epochs_nested(
        module, cfg, global_params, x, y, state, epoch_keys, track_best_acc
    )


# Donated jitted entry for chunk-resume drivers: the incoming ClientState
# buffers are reused for the outgoing ones (on backends that support
# donation), halving the carry's resident footprint at flagship shapes.
local_train_epochs_jit = partial(
    jax.jit, static_argnums=(0, 1, 7), donate_argnums=(5,)
)(local_train_epochs)


def client_shipped_params(state: ClientState):
    """The weights a CLIENT uploads after `model.fit`, with the reference's
    exact callback semantics (FLPyfhelin.py:184-198): what gets encrypted
    is `save_weights(model)` AFTER fit — i.e. the live model, on which
    TF-2.x `EarlyStopping(restore_best_weights=True)` restores the
    best-val-LOSS weights ONLY when it actually stopped training early;
    a run that completes all epochs keeps its final-epoch weights. The
    per-client `ModelCheckpoint` (best-by-val-accuracy) writes a side
    .ckpt that the client upload path never reads — that checkpoint IS
    what the centralized `train_server` reloads (FLPyfhelin.py:169-174),
    hence `train_centralized` ships `state.best_params` instead.

    (Shipping best-by-accuracy here — r4 behavior — silently degrades the
    hardened flagship task: the 80-image val split saturates at accuracy
    1.0 within a few epochs and strict-improvement tracking then locks in
    those early, undertrained weights.)
    """
    return jax.tree_util.tree_map(
        lambda best, fin: jnp.where(state.stopped, best, fin),
        state.best_loss_params,
        state.params,
    )


def local_train(
    module,
    cfg: TrainConfig,
    global_params,
    x: jax.Array,
    y: jax.Array,
    key: jax.Array,
    streams=None,
):
    """Train one client from the global weights.

    x: uint8[m, H, W, C]; y: int32[m]; -> (shipped_params, metrics
    f32[E, 4]) with metrics columns (val_loss, val_acc, lr_scale,
    stopped). `shipped_params` follows `client_shipped_params`.
    `streams` is the hoisted shuffle/augment stream pair this client's
    round program precomputed (`epoch_index_streams` row; flat layout
    only) — same values as the in-body derivation, sort lowered outside
    the sharded region.
    """
    epoch_keys = jax.random.split(key, cfg.epochs)
    final, metrics = local_train_epochs(
        module, cfg, global_params, x, y,
        init_client_state(global_params), epoch_keys,
        track_best_acc=False,   # clients never read the ModelCheckpoint copy
        streams=streams,
    )
    return client_shipped_params(final), metrics


# Convenience jitted entry for single-client use (tests).
local_train_jit = partial(jax.jit, static_argnums=(0, 1))(local_train)


def _centralized(module, cfg: TrainConfig, params, x, y, key):
    epoch_keys = jax.random.split(key, cfg.epochs)
    final, metrics = local_train_epochs(
        module, cfg, params, x, y, init_client_state(params), epoch_keys
    )
    # train_server reloads its best-by-ACCURACY ModelCheckpoint after fit
    # (FLPyfhelin.py:169-174) — unlike the client upload path, which ships
    # the post-fit live model (see client_shipped_params).
    return final.best_params, metrics


_centralized_jit = partial(jax.jit, static_argnums=(0, 1))(_centralized)


def train_centralized(module, cfg: TrainConfig, params, x, y, key):
    """Centralized (non-federated) baseline trainer — `train_server`
    (FLPyfhelin.py:161-177): the whole dataset, one model, the same
    callback semantics (EarlyStopping / ReduceLROnPlateau / best-checkpoint
    restore-by-accuracy). The reference defines it but its notebook never
    calls it; it exists to measure what federation costs in accuracy.

    -> (best_params, metrics f32[E, 4]).
    """
    if is_token_model(module):
        raise NotImplementedError(
            "the centralized baseline trains a model whole; a token model's "
            "frozen base goes through the federated round programs only"
        )
    return _centralized_jit(module, cfg, params, x, y, key)
