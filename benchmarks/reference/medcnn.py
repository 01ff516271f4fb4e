"""Plain float32 reference of the MedCNN (FLPyfhelin.py:118-146
`create_model`): six [3x3 VALID conv -> ReLU -> 2x2 max-pool] stages of
32/32/32/64/64/128 filters, flatten, dense 128 ReLU, dense 64 ReLU, dense
`num_classes`; 222,722 parameters at 256x256x3 and two classes.

Straight `lax.conv_general_dilated` and `jnp.dot` at `highest` precision,
one image batch, no folding, no vmap, no bfloat16. Imports nothing of the
program; it makes its own weights from the seed, laid out under the names
the program's parameter tree uses so that both sides can be handed the same
arrays. Departure from the published description: the softmax is folded
into the loss (logits out), as every stable implementation does.

`quant`, where given, is applied to the inputs and weights of every conv
and dense layer: the benchmark's control computes the same network in a
precision below the configuration's bfloat16.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

FEATURES = (32, 32, 32, 64, 64, 128)
DENSE = (128, 64)
_HI = lax.Precision.HIGHEST


def _stage_sizes(h: int, w: int):
    for _ in FEATURES:
        h, w = h - 2, w - 2          # 3x3 VALID
        yield h, w
        h, w = h // 2, w // 2        # 2x2 pool, stride 2


def init(seed: int, input_shape, num_classes: int) -> dict:
    """He-normal kernels, small normal biases, from the seed (numpy)."""
    rng = np.random.default_rng([int(seed), 0x6D6564])
    h, w, cin = input_shape
    params = {}
    for i, (f, (ch, cw)) in enumerate(zip(FEATURES, _stage_sizes(h, w))):
        std = np.sqrt(2.0 / (9 * cin))
        params[f"Conv_{i}"] = {
            "kernel": rng.normal(0, std, (3, 3, cin, f)).astype(np.float32),
            "bias": rng.normal(0, 0.01, (f,)).astype(np.float32),
        }
        cin, h, w = f, ch // 2, cw // 2
    fan = h * w * cin
    for j, d in enumerate((*DENSE, num_classes)):
        params[f"Dense_{j}"] = {
            "kernel": rng.normal(0, np.sqrt(2.0 / fan), (fan, d)).astype(np.float32),
            "bias": rng.normal(0, 0.01, (d,)).astype(np.float32),
        }
        fan = d
    return params


def forward(params, x, quant=None):
    """x: float32[B, H, W, C] in [0, 1] -> logits float32[B, classes]."""
    q = quant or (lambda a: a)
    x = x.astype(jnp.float32)
    for i in range(len(FEATURES)):
        p = params[f"Conv_{i}"]
        x = lax.conv_general_dilated(
            q(x), q(p["kernel"]), (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=_HI,
        ) + p["bias"]
        x = jnp.maximum(x, 0.0)
        x = lax.reduce_window(
            x, -jnp.inf, lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
        )
    x = x.reshape(x.shape[0], -1)
    for j in range(len(DENSE) + 1):
        p = params[f"Dense_{j}"]
        x = jnp.dot(q(x), q(p["kernel"]), precision=_HI) + p["bias"]
        if j < len(DENSE):
            x = jnp.maximum(x, 0.0)
    return x


def loss(params, x, onehot, quant=None):
    """Mean categorical cross-entropy over softmax logits -> (loss, logits)."""
    logits = forward(params, x, quant)
    return -jnp.mean(jnp.sum(onehot * jax.nn.log_softmax(logits), -1)), logits


def forward_flops(input_shape, num_classes: int) -> int:
    """Multiply-adds x 2 of one image's forward pass, from the conv and
    dense shapes alone (0.51 GFLOP at 256x256x3)."""
    h, w, cin = input_shape
    total = 0
    for f, (ch, cw) in zip(FEATURES, _stage_sizes(h, w)):
        total += 2 * 9 * cin * f * ch * cw
        cin, h, w = f, ch // 2, cw // 2
    fan = h * w * cin
    for d in (*DENSE, num_classes):
        total += 2 * fan * d
        fan = d
    return total
