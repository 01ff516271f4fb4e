"""Cross-client fused training: one GEMM stream for a device's whole block.

`fedavg.vmapped_train` trains a device's C clients by vmapping the whole
per-client program. JAX's batching rules keep that correct but shape the
per-layer ops badly for the MXU: a both-operands-batched conv folds the
client axis into `feature_group_count`, so every layer runs C feature
groups whose GEMMs each carry only ONE client's batch of rows — the
MFU~0.02 profile row the ROADMAP's "Cross-client GEMM batching" item names.

This module is the `TrainConfig.client_fusion="fused"` backend: the same
local-training program (identical math, identical RNG streams, identical
Keras-callback semantics) restructured so the client axis lives in the
BATCH dimension of every conv/dense — activations flow client-folded as
[C*B, ...] through `module.folded_apply` (models.folded: batch-grouped
convs, client-batched dense GEMMs), the augment warp runs once on the
folded batch, and the per-epoch validation evals run folded too. One
forward/backward per step for the whole block, effective batch C*B.

Per-client semantics are preserved exactly:

  * per-client params / Adam state / LR-plateau scale — stacked leaves
    (leading client axis); the optimizer update is elementwise, applied
    per client via vmap (no GEMMs there to fuse);
  * per-client shuffles and augment keys — the identical key derivation as
    the vmap path (`client._epoch_streams`, `augment.draw_affine_params`),
    so same keys => same batches => same affines;
  * per-client early stopping — the callback state machine
    (`client._epoch_update`) runs vmapped at epoch boundaries; a stopped
    client's micro-batch still flows through the fused GEMM, but its
    boundary update discards the phantom-trained weights (the same
    mask-not-branch lockstep the vmap path uses);
  * participation masks — a scheduled-out client's rows also keep flowing
    through the GEMM (static SPMD shape for the masked round engine), but
    its update is masked out each step, so its shipped weights are the
    round's unchanged global weights.

Backend selection (`TrainConfig.client_fusion`, `resolve_fusion_backend`):
"fused" | "vmap" pin a backend; "auto" (default) is "fused" for a model
whose client-folded forward packs the clients into the lanes (it says so:
`folded_lane_packed`, ResNet20) and "vmap" for every other image model
(MedCNN's polyphase stages were written for `vmap`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

from hefl_tpu.data.augment import (
    apply_affine,
    draw_affine_params,
    rescale,
    resolve_shift_backend,
)
from hefl_tpu.fl.client import (
    _epoch_streams,
    _epoch_update,
    _train_split,
    client_shipped_params,
    init_client_state,
    train_batch_geometry,
)
from hefl_tpu.fl.config import TrainConfig
from hefl_tpu.fl.optimizer import adam_update
from hefl_tpu.models.folded import fold_clients, stack_params, unfold_clients
from hefl_tpu.obs import scopes as obs_scopes

FUSION_BACKENDS = ("fused", "vmap")
# One client after another (fedavg.serial_train): no setting selects it. It
# is what a token model over a frozen base gets (models/lm/): one client's
# step of thousands of tokens fills the MXU by itself, and its grouped
# expert product (a Pallas call with scalar prefetch) has no batching over
# clients, so neither other backend can lower it.
SERIAL = "serial"


def supports_fusion(module) -> bool:
    """Does this model implement the client-folded forward?"""
    return hasattr(module, "folded_apply")


def _mask_select(keep: jax.Array, new_tree, old_tree):
    """Per-client tree select: keep[c] picks new over old for client c's
    slice of every stacked leaf."""
    def sel(a, b):
        k = keep.reshape((-1,) + (1,) * (a.ndim - 1))
        return jnp.where(k, a, b)

    return jax.tree_util.tree_map(sel, new_tree, old_tree)


def fused_train(
    module,
    cfg: TrainConfig,
    global_params,
    x_blk: jax.Array,
    y_blk: jax.Array,
    k_blk: jax.Array,
    participation: jax.Array | None = None,
    streams_blk=None,
):
    """Train one device's block of clients through the client-folded path.

    Same contract as `fedavg.vmapped_train` — x_blk: uint8[cpd, m, ...],
    y_blk: int32[cpd, m], k_blk: per-client keys [cpd] — plus an optional
    traced `participation` int[cpd] (the masked round engine's m_blk): a
    0-masked client's data still flows through every fused GEMM (static
    shape), but its parameter/optimizer/callback updates are masked to
    no-ops, so it ships the round's global weights unchanged.
    `streams_blk` ((perms [cpd, E*S, grp], aug_keys [cpd, E*S]) from
    `client.epoch_index_streams`) swaps the in-body shuffle derivation
    for the hoisted arrays — identical values, but the permutation sort
    lowers OUTSIDE the sharded round program (ISSUE 15; the round
    factories always pass it). With cohort-only training the gather that
    feeds this block — the sampled cohort's data/key/stream slots, padded
    to the power-of-two bucket — happened BEFORE this fused GEMM stream,
    so the [cpd*B] batches below are cohort-sized, not registry-sized.
    -> (shipped stacked weight trees [cpd, ...], metrics [cpd, E, 4]).
    """
    cpd = int(x_blk.shape[0])
    m = int(x_blk.shape[1])
    n_tr, grp, steps = train_batch_geometry(cfg, m)
    if n_tr < 1:
        raise ValueError(
            f"client has {m} sample(s); needs >= 2 to carve out a validation "
            "split (set val_fraction=0 to train on everything)"
        )
    n_val = m - n_tr
    x_tr, y_tr = x_blk[:, n_val:], y_blk[:, n_val:]
    if n_val:
        x_va, y_va = x_blk[:, :n_val], y_blk[:, :n_val]
    else:  # degenerate config: validate on the train slice
        x_va, y_va = x_tr, y_tr
    with jax.named_scope(obs_scopes.SGD_CORE):
        oh_tr = jax.nn.one_hot(y_tr, cfg.num_classes, dtype=jnp.float32)
    with jax.named_scope(obs_scopes.VAL):
        oh_va = jax.nn.one_hot(y_va, cfg.num_classes, dtype=jnp.float32)
        xva_folded = fold_clients(rescale(x_va))
    bk = resolve_shift_backend(cfg.aug_backend) if cfg.augment else None

    e = int(cfg.epochs)
    with jax.named_scope(obs_scopes.SGD_CORE):
        if streams_blk is None:
            epoch_keys = jax.vmap(lambda k: jax.random.split(k, e))(k_blk)  # [cpd, E]
            # Per-client shuffles + augment keys from the SAME derivation as
            # the vmap path (client._epoch_streams), vmapped over the block —
            # same keys => same index/augment streams by construction. The
            # split's static geometry is shared across clients, so client 0's
            # split describes the whole block (the throwaway one-hot it
            # builds is DCE'd).
            sp0 = _train_split(cfg, x_blk[0], y_blk[0])
            perms, aug_keys = jax.vmap(lambda ek: _epoch_streams(ek, sp0))(epoch_keys)
            flat_perm = perms.reshape(cpd, e * steps, grp).swapaxes(0, 1)  # [T,cpd,grp]
            flat_aug = aug_keys.reshape(cpd, e * steps).swapaxes(0, 1)     # [T,cpd]
        else:
            pm, ag = streams_blk          # [cpd, T, grp], [cpd, T]
            flat_perm = pm.swapaxes(0, 1)                          # [T,cpd,grp]
            flat_aug = ag.swapaxes(0, 1)                           # [T,cpd]
        is_end = (jnp.arange(e * steps) % steps) == steps - 1

    params0 = stack_params(global_params, cpd)
    st0 = jax.vmap(init_client_state)(params0)
    keep = None if participation is None else participation > 0

    def epoch_update_block(s0, p, o, vl, va):
        return jax.vmap(
            lambda s_, p_, o_, vl_, va_: _epoch_update(
                cfg, s_, p_, o_, vl_, va_, track_best_acc=False
            )
        )(s0, p, o, vl, va)

    def folded_metrics(p_stacked, xf, oh):
        """Per-client (ce, acc) of the folded batch xf under stacked
        params; oh: [cpd, b, K]."""
        logits = unfold_clients(
            module.folded_apply(p_stacked, xf, num_clients=cpd), cpd
        )
        ce = jnp.mean(optax.softmax_cross_entropy(logits, oh), axis=1)
        acc = jnp.mean(
            (jnp.argmax(logits, -1) == jnp.argmax(oh, -1)).astype(jnp.float32),
            axis=1,
        )
        return ce, acc

    def flat_step(carry, inp):
        params_run, opt_run, st = carry
        idx, k_aug, end = inp  # [cpd, grp], [cpd], scalar bool
        # Phase scopes (obs): the fused step carries the same hefl.sgd_core
        # / hefl.augment / hefl.val buckets as the vmap reference, so trace
        # attribution is backend-independent. Leaf regions only — the scan
        # at the bottom of fused_train stays scope-less.
        with jax.named_scope(obs_scopes.SGD_CORE), jax.named_scope(
                obs_scopes.BATCH):
            xb = jnp.take_along_axis(
                x_tr, idx[:, :, None, None, None], axis=1
            )                                      # [cpd, grp, H, W, ch]
            xb = fold_clients(rescale(xb))         # [cpd*grp, H, W, ch]
        if cfg.augment:
            # inside hefl.sgd_core, as the vmap step's warp is: the step's
            # device seconds mean the same under both lowerings
            with jax.named_scope(obs_scopes.SGD_CORE):
                with jax.named_scope(obs_scopes.AUGMENT):
                    s, zx, zy, f = jax.vmap(
                        lambda k: draw_affine_params(
                            k, grp, cfg.aug_shear, cfg.aug_zoom, cfg.aug_flip
                        )
                    )(k_aug)                       # each [cpd, grp]
                xb = apply_affine(
                    xb, s.reshape(-1), zx.reshape(-1), zy.reshape(-1),
                    f.reshape(-1), bk,
                )
        with jax.named_scope(obs_scopes.SGD_CORE), jax.named_scope(
                obs_scopes.BATCH):
            oh = jnp.take_along_axis(oh_tr, idx[:, :, None], axis=1)

        def block_loss(p):
            # Sum of per-client mean losses: client c's params only touch
            # client c's term, so ONE backward through the folded graph
            # yields every client's exact gradient.
            ce, _ = folded_metrics(p, xb, oh)
            loss = jnp.sum(ce)
            if cfg.prox_mu > 0.0:
                sq = jax.tree_util.tree_map(
                    lambda t, g: jnp.sum(jnp.square(t - g[None])),
                    p, global_params,
                )
                loss = loss + 0.5 * cfg.prox_mu * jax.tree_util.tree_reduce(
                    jnp.add, sq
                )
            return loss

        with jax.named_scope(obs_scopes.SGD_CORE):
            grads = jax.grad(block_loss)(params_run)
            with jax.named_scope(obs_scopes.ADAM):
                new_params, new_opt = jax.vmap(
                    lambda g, o, p, ls: adam_update(
                        g, o, p, cfg.lr, cfg.lr_decay, ls,
                        warmup_steps=cfg.warmup_steps,
                    )
                )(grads, opt_run, params_run, st.lr_scale)
            if keep is not None:
                # Scheduled-out clients flow through the GEMM but update
                # nothing — the multiplicative update mask of the fused step.
                new_params = _mask_select(keep, new_params, params_run)
                new_opt = _mask_select(keep, new_opt, opt_run)
            params_run, opt_run = new_params, new_opt

        def boundary(p, o, s0):
            frozen = s0.stopped
            eval_params = _mask_select(jnp.logical_not(frozen), p, s0.params)
            val_loss, val_acc = folded_metrics(eval_params, xva_folded, oh_va)
            ns, mets = epoch_update_block(s0, p, o, val_loss, val_acc)
            return ns.params, ns.opt, ns, mets

        def interior(p, o, s0):
            return p, o, s0, jnp.zeros((cpd, 4), jnp.float32)

        # Scoping the cond attributes the executed branch (the val eval on
        # boundary steps) to hefl.val — see fl.client's flat layout.
        with jax.named_scope(obs_scopes.VAL):
            params_run, opt_run, st, mets = jax.lax.cond(
                end, boundary, interior, params_run, opt_run, st
            )
        return (params_run, opt_run, st), mets

    (_, _, final), mets = jax.lax.scan(
        flat_step, (st0.params, st0.opt, st0), (flat_perm, flat_aug, is_end)
    )
    metrics = mets[steps - 1 :: steps].swapaxes(0, 1)  # [cpd, E, 4]
    return jax.vmap(client_shipped_params)(final), metrics


# --------------------------------------------------------------- selection


def resolve_fusion_backend(setting: str | None, module) -> str:
    """The training backend a round program traces with.

    "auto" (or None) is "fused" for a model whose `folded_apply` packs the
    clients into the lanes (the class says so beside the method:
    `folded_lane_packed`) and "vmap" otherwise; "fused" and "vmap" are
    taken as given, and "fused" on a model without a `folded_apply` is an
    error. A token model (models/lm/) is trained one client after
    another, and any pin is an error.
    """
    from hefl_tpu.models.lm import is_token_model

    requested = setting or "auto"
    if is_token_model(module):
        if requested != "auto":
            raise ValueError(
                f"client_fusion={setting!r} but {type(module).__name__} is "
                "a token model over a frozen base: its clients are trained "
                "one after another ('auto' resolves to 'serial')"
            )
        return SERIAL
    if requested not in FUSION_BACKENDS + ("auto",):
        raise ValueError(
            f"client fusion backend {requested!r}: expected one of "
            f"{FUSION_BACKENDS + ('auto',)}"
        )
    if requested == "fused" and not supports_fusion(module):
        raise ValueError(
            f"client_fusion='fused' but {type(module).__name__} has no "
            "folded_apply — implement the client-folded forward "
            "(models.folded) or use 'vmap'/'auto'"
        )
    if requested == "auto":
        packed = getattr(module, "folded_lane_packed", False)
        return "fused" if packed else "vmap"
    return requested


def fusion_report(setting: str | None, module) -> dict:
    """Which client-training backend the round programs of `module` trace
    with under TrainConfig.client_fusion `setting`."""
    return {
        "requested": setting or "auto",
        "backend": resolve_fusion_backend(setting, module),
    }
