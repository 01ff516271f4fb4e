"""Plain float32 reference of the CIFAR-10 ResNet-20 (He et al. 2015,
arXiv:1512.03385, section 4.2): a 3x3 conv of 16 filters, then three stages
of three basic blocks with widths 16/32/64 (the first block of stages two
and three strides by 2), global average pool, a linear head; 0.27 M
parameters.

Departures from the paper, as `hefl_tpu/models/resnet.py` argues them for
federated averaging: GroupNorm with 8 groups (epsilon 1e-6, per-sample
statistics) stands where the paper has BatchNorm, and the shortcut across a
shape change is a strided 1x1 conv + GroupNorm (the paper's option B) where
its CIFAR nets pad with zeros (option A). Convs carry no bias.

Convolutions written out as shifted views times a matrix at `highest`
precision; imports nothing
of the program and makes its own weights from the seed under the names the
program's parameter tree uses. `quant` as in `medcnn.py`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

WIDTHS = (16, 32, 64)
BLOCKS = (3, 3, 3)
GROUPS = 8
EPS = 1e-6
_HI = lax.Precision.HIGHEST


def _plan():
    """(block index, width, stride, projection?) down the network."""
    cin, i = WIDTHS[0], 0
    for stage, (n, width) in enumerate(zip(BLOCKS, WIDTHS)):
        for b in range(n):
            stride = 2 if (stage > 0 and b == 0) else 1
            yield i, cin, width, stride, (stride != 1 or cin != width)
            cin, i = width, i + 1


def init(seed: int, input_shape, num_classes: int) -> dict:
    rng = np.random.default_rng([int(seed), 0x72736E])

    def conv(k, cin, cout):
        std = np.sqrt(2.0 / (k * k * cin))
        return {"kernel": rng.normal(0, std, (k, k, cin, cout)).astype(np.float32)}

    def gn(c):
        return {"scale": (1 + rng.normal(0, 0.1, (c,))).astype(np.float32),
                "bias": rng.normal(0, 0.1, (c,)).astype(np.float32)}

    params = {"Conv_0": conv(3, input_shape[-1], WIDTHS[0]),
              "GroupNorm_0": gn(WIDTHS[0])}
    for i, cin, width, _, proj in _plan():
        blk = {"Conv_0": conv(3, cin, width), "GroupNorm_0": gn(width),
               "Conv_1": conv(3, width, width), "GroupNorm_1": gn(width)}
        if proj:
            blk["Conv_2"] = conv(1, cin, width)
            blk["GroupNorm_2"] = gn(width)
        params[f"BasicBlock_{i}"] = blk
    params["Dense_0"] = {
        "kernel": rng.normal(
            0, np.sqrt(1.0 / WIDTHS[-1]), (WIDTHS[-1], num_classes)
        ).astype(np.float32),
        "bias": rng.normal(0, 0.01, (num_classes,)).astype(np.float32),
    }
    return params


def _conv(x, kernel, stride, q):
    """SAME convolution written out: the k x k shifted (strided) views of
    the padded input, side by side, times the kernel as a matrix. (The
    backward pass of `lax.conv_general_dilated` at `highest` precision does
    not get through the TPU compiler for this network in any useful time;
    a matmul at `highest` does, in seconds.)"""
    kh, kw, cin, cout = kernel.shape
    _, h, w, _ = x.shape
    ho, wo = -(-h // stride), -(-w // stride)
    ph = max((ho - 1) * stride + kh - h, 0)
    pw = max((wo - 1) * stride + kw - w, 0)
    x = jnp.pad(q(x), ((0, 0), (ph // 2, ph - ph // 2), (pw // 2, pw - pw // 2),
                       (0, 0)))
    views = [x[:, i:i + (ho - 1) * stride + 1:stride,
               j:j + (wo - 1) * stride + 1:stride, :]
             for i in range(kh) for j in range(kw)]
    return jnp.einsum("bhwk,ko->bhwo", jnp.concatenate(views, axis=-1),
                      q(kernel).reshape(kh * kw * cin, cout), precision=_HI)


def _group_norm(x, p):
    b, h, w, c = x.shape
    g = x.reshape(b, h, w, GROUPS, c // GROUPS)
    mean = jnp.mean(g, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean((g - mean) ** 2, axis=(1, 2, 4), keepdims=True)
    g = (g - mean) * lax.rsqrt(var + EPS)
    return g.reshape(b, h, w, c) * p["scale"] + p["bias"]


def forward(params, x, quant=None):
    q = quant or (lambda a: a)
    x = x.astype(jnp.float32)
    x = jnp.maximum(_group_norm(_conv(x, params["Conv_0"]["kernel"], 1, q),
                                params["GroupNorm_0"]), 0.0)
    for i, _, _, stride, proj in _plan():
        p = params[f"BasicBlock_{i}"]
        y = _conv(x, p["Conv_0"]["kernel"], stride, q)
        y = jnp.maximum(_group_norm(y, p["GroupNorm_0"]), 0.0)
        y = _group_norm(_conv(y, p["Conv_1"]["kernel"], 1, q), p["GroupNorm_1"])
        if proj:
            x = _group_norm(_conv(x, p["Conv_2"]["kernel"], stride, q),
                            p["GroupNorm_2"])
        x = jnp.maximum(y + x, 0.0)
    x = jnp.mean(x, axis=(1, 2))
    head = params["Dense_0"]
    return jnp.dot(q(x), q(head["kernel"]), precision=_HI) + head["bias"]


def loss(params, x, onehot, quant=None):
    logits = forward(params, x, quant)
    return -jnp.mean(jnp.sum(onehot * jax.nn.log_softmax(logits), -1)), logits


def forward_flops(input_shape, num_classes: int) -> int:
    """Multiply-adds x 2 of one image's forward pass from the conv and
    dense shapes (GroupNorm's elementwise work is not counted): 81.6 MFLOP
    at 32x32x3."""
    h, w, c = input_shape
    total = 2 * 9 * c * WIDTHS[0] * h * w
    for _, cin, width, stride, proj in _plan():
        h, w = -(-h // stride), -(-w // stride)
        total += 2 * 9 * cin * width * h * w + 2 * 9 * width * width * h * w
        if proj:
            total += 2 * cin * width * h * w
    return total + 2 * WIDTHS[-1] * num_classes
