"""Loss and batch metrics.

Categorical cross-entropy over softmax logits, matching the reference's
`loss='categorical_crossentropy'` + accuracy compile
(/root/reference/FLPyfhelin.py:140-141). The optional FedProx proximal
term mu/2 * ||w - w_global||^2 (Li et al. 2020) regularizes local training
toward the round's global weights — the standard non-IID stabilizer called
for by BASELINE.json config 4.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

from hefl_tpu.obs.scopes import DENSE


def cross_entropy(logits: jax.Array, onehot: jax.Array) -> jax.Array:
    return jnp.mean(optax.softmax_cross_entropy(logits, onehot))


def accuracy(logits: jax.Array, onehot: jax.Array) -> jax.Array:
    return jnp.mean(
        (jnp.argmax(logits, -1) == jnp.argmax(onehot, -1)).astype(jnp.float32)
    )


def prox_term(params, global_params, mu: float) -> jax.Array:
    if mu == 0.0:
        return jnp.float32(0.0)
    sq = jax.tree_util.tree_map(
        lambda p, g: jnp.sum((p - g) ** 2), params, global_params
    )
    return 0.5 * mu * jax.tree_util.tree_reduce(jnp.add, sq)


def token_loss_fn(module, params, tokens, global_params=None, prox_mu: float = 0.0):
    """-> (loss, (loss without the proximal term, next-token accuracy)) of a
    token model (`models/lm/`): `tokens` int[B, S + 2] label themselves at
    every position (token i + 1 for the main head, i + 2 for the prediction
    module), so there is no one-hot and no label argument. `params` is the
    trained subset; the module holds its frozen base."""
    loss, (_, acc, _) = module.loss({"params": params}, tokens)
    total = loss
    if prox_mu > 0.0 and global_params is not None:
        total = total + prox_term(params, global_params, prox_mu)
    return total, (loss, acc)


def loss_fn(module, params, x, onehot, global_params=None, prox_mu: float = 0.0):
    """-> (loss, (ce, acc)). `x` is float [B,H,W,C] in [0,1]."""
    logits = module.apply({"params": params}, x)
    with jax.named_scope(DENSE):  # the head's loss, with the head
        ce = cross_entropy(logits, onehot)
    loss = ce
    if prox_mu > 0.0 and global_params is not None:
        loss = loss + prox_term(params, global_params, prox_mu)
    with jax.named_scope(DENSE):
        return loss, (ce, accuracy(logits, onehot))
