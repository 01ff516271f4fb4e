"""Plain Adam as the reference's `model.compile(Adam(lr, decay))` states it
(FLPyfhelin.py:140): bias-corrected moments with Keras's defaults (0.9,
0.999, epsilon 1e-7 added to the root), the legacy time decay
lr_t = lr / (1 + decay * t), and the linear warm-up lr_t * min(1, t / warmup)
where a configuration assumes one. Float64 numpy on the host, one update
after the other; imports nothing of the program.
"""

from __future__ import annotations

import jax
import numpy as np

B1, B2, EPS = 0.9, 0.999, 1e-7
_map = jax.tree_util.tree_map


def steps(value_and_grad, params, batches, lr: float, decay: float,
          warmup_steps: int = 0):
    """Follow `len(batches)` optimizer steps from `params`.
    `value_and_grad(params_f32, x, onehot) -> ((loss, logits), grads)` is the
    model's plain reference. -> (the float64 parameters after every step,
    each step's loss before its update)."""
    p = _map(lambda a: np.asarray(a, np.float64), params)
    mu, nu = _map(np.zeros_like, p), _map(np.zeros_like, p)
    trail, losses = [], []
    for t, (x, onehot) in enumerate(batches, 1):
        (loss, _), g = value_and_grad(
            _map(lambda a: a.astype(np.float32), p), x, onehot)
        g = _map(lambda a: np.asarray(a, np.float64), g)
        lr_t = lr / (1.0 + decay * t)
        if warmup_steps > 0:
            lr_t *= min(1.0, t / float(warmup_steps))
        mu = _map(lambda m, a: B1 * m + (1 - B1) * a, mu, g)
        nu = _map(lambda v, a: B2 * v + (1 - B2) * a * a, nu, g)
        p = _map(lambda w, m, v: w - lr_t * (m / (1 - B1 ** t)) / (
            np.sqrt(v / (1 - B2 ** t)) + EPS), p, mu, nu)
        trail.append(p)
        losses.append(float(loss))
    return trail, losses
