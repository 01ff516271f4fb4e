"""CNN models matching the reference architecture exactly.

`MedCNN` reproduces `create_model` (/root/reference/FLPyfhelin.py:118-146):
six [Conv2D 3x3 VALID -> ReLU -> MaxPool 2x2] stages with filters
(32, 32, 32, 64, 64, 128), then Flatten -> Dense 128 ReLU -> Dense 64 ReLU
-> Dense num_classes softmax. At 256x256x3 input the feature maps run
254->127, 125->62, 60->30, 28->14, 12->6, 4->2 so flatten = 2*2*128 = 512
and the parameter count is exactly 222,722 in 18 weight tensors
(SURVEY.md §2.3) — the HE sizing contract for the encrypted FedAvg path.

TPU notes: convolutions and matmuls run in bfloat16 (MXU-native) with
float32 params and float32 accumulation. The softmax is NOT part of the
model by default (we return logits and fold it into the loss, the
numerically-stable JAX idiom); `apply_softmax=True` recovers the Keras
probs-output behavior for prediction parity.

What the chip's trace showed (PERF.md sections 5 and 6, PR 25): the training
step is bound by HBM bytes, not by the MXU (1 ms of multiply-adds in a 42 ms
step). XLA lays an activation out with its channels in a tile's 128 lanes,
so a 32-channel map fills a quarter of every tile, occupies and moves four
times its bytes, and the max-pool's backward (`select-and-scatter`) was the
longest op of the step. So the large early stages run in POLYPHASE form
(`_conv_stages`, `_polyphase_block` for the rule): the stage is computed on
a space-to-depth form of its input, with a kernel built from the 3x3
parameter by a constant 0/1 selection, so that the conv outputs of one
block sit side by side in the lanes and the pool is an elementwise maximum
of four lane groups. Same parameters, same products summed, the same
first-maximum pool gradient; at 256x256x3 the first stage takes 4x4 blocks
and hands its pooled map, still in 2x2 form, to the second (2x2 blocks),
and the step went 47.1 -> 14.4 ms on a TPU v5e, then 12.8 ms with the pool
as one pass over the stage's arrays each way (`_relu_pool4`, PR 32). In a
polyphase stage the bias is added inside that pool, not by the lowering, so
that its gradient is a sum over the pooled cotangent's n lanes and not over
the 4n-lane cotangent that is never written (PR 35; PERF.md section 6 has
what the step takes since).
"""

from __future__ import annotations

import itertools
from functools import partial
from typing import Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from hefl_tpu.models.folded import folded_conv, folded_dense
from hefl_tpu.obs import metrics as obs_metrics
from hefl_tpu.obs import scopes as obs_scopes


_LANES = 128  # the minor dimension of a TPU tile
# The smallest pooled map at which the form paid (PERF.md section 5: MedCNN's
# 127- and 62-wide stages gained on the chip, its 30-wide one lost).
_MIN_POLYPHASE_MAP = 48


def _polyphase_block(hw, ci: int, co: int) -> int:
    """THE rule for which [3x3 VALID conv -> ReLU -> 2x2/2 max-pool] stages
    run in polyphase form, read from the stage's own shapes (PERF.md section
    5 has what each stage read on the chip). -> 0 for the plain stage, else
    the block `s`: the stage takes its input in s x s space-to-depth form.

    A plain stage leaves `co` of a tile's 128 lanes in use and pays for a
    padded activation at every pass over it; the form pays where that map is
    large and its four pool phases fit the lanes (`4 * co <= 128`). Block 4
    makes 7.1x the stage's multiply-adds (block 2: 1.8x) on an MXU that the
    plain stage leaves idle, so it is taken where they are few: where the
    4 x 4 block of the input fits one tile of lanes (`16 * ci <= 128`: an
    image's 1 or 3 channels). Its pool groups are then whole tiles and its
    output is already the space-to-depth form a block-2 stage takes."""
    hp, wp = (hw[0] - 2) // 2, (hw[1] - 2) // 2
    if 4 * co > _LANES or min(hp, wp) < _MIN_POLYPHASE_MAP:
        return 0
    return 4 if 16 * ci <= _LANES else 2


def _phase_selection(s: int) -> np.ndarray:
    """sel[u, d, q, p, a] = 1 where a == s*u + d - (2q + p): tap `a` of a
    3-tap kernel, applied at position 2q + p of an output block (pool phase
    `p` of pooled position `q`), reads position `d` of input block `u`."""
    sel = np.zeros((2, s, s, 3), np.float32)
    for u, d, r in itertools.product(range(2), range(s), range(s)):
        if 0 <= s * u + d - r <= 2:
            sel[u, d, r, s * u + d - r] = 1.0
    return sel.reshape(2, s, s // 2, 2, 3)


_SEL = {s: _phase_selection(s) for s in (2, 4)}


def _to_depth(x, depth: int, hw, s: int, nb: int, mb: int):
    """Bring a map from d x d to s x s space-to-depth form with nb x mb
    blocks, zero-padded past the map (s = 1, nb x mb = hw: the plain map).

    x: [B, n, m, depth*depth*C], the `depth` form of a plain [B, *hw, C] map,
    channel order (dy, dx, c): x[b, i, j, (dy, dx, c)] = plain[b, depth*i +
    dy, depth*j + dx, c]. -> [B, nb, mb, s*s*C]."""
    if depth == s:
        return x[:, :nb, :mb]
    b, n, m, c = x.shape
    c //= depth * depth
    x = x.reshape(b, n, m, depth, depth, c).transpose(0, 1, 3, 2, 4, 5)
    x = x.reshape(b, n * depth, m * depth, c)[:, : min(hw[0], s * nb), : min(hw[1], s * mb)]
    x = jnp.pad(x, ((0, 0), (0, s * nb - x.shape[1]), (0, s * mb - x.shape[2]), (0, 0)))
    x = x.reshape(b, nb, s, mb, s, c).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, nb, mb, s * s * c)


@jax.custom_vjp
def _relu_pool4(y, b):
    """ReLU of the maximum over the four pool phases of `y + b`,
    y[..., (p, q, c)] + b[..., c] -> [..., (q, c)], as lane slices (whole
    tiles at block 4), with the gradient of `relu` then `reduce_window` max: a
    window's cotangent goes whole to its FIRST maximum in window order
    (0,0), (0,1), (1,0), (1,1), and nowhere if that maximum is not positive.
    `jnp.max` would split it between ties, which bf16 activations make
    common, and the training step would no longer be the reference's. The
    backward needs the winning phase (int8) and the pooled output (which the
    next stage keeps anyway), not the activation.

    y: [B, i, j, 4n], a conv output without its bias, lanes (pool phase,
    block position, channel); b: [..., c], the channels' bias in `y`'s
    dtype (c divides n), with whatever leading axes split `B` (none under
    `vmap`; the client axis of the `fused` lowering's client-folded batch,
    `_per_client`). The sum is the `conv -> + bias` that `_conv_bf16`
    states, rounded once in `y`'s dtype.

    The form is written for what XLA makes of it (PERF.md section 5, PRs 32
    and 35; `tests/test_tpu_compile.py` holds it), one pass over the stage's
    arrays each way. Forward, ONE comparison tree gives the maximum and the
    winning phase (ties to the lower index at every node), so they come out
    of one fusion that reads `y` once and `y` dies there. As the maximum and
    then four compares against it, XLA sank the phase's whole chain into the
    backward, kept `y` (520 MB a step at MedCNN's first stage) live until
    then and read it again. Backward, ONE select over the whole lane width
    against a constant lane-phase index, which XLA fuses into the operand
    of the convolutions that take the cotangent (kernel and input gradient),
    so the [..., 4n] cotangent is never written. As four selects and a
    concatenation it wrote four arrays and read them back twice.

    Why the bias is an argument (PR 35). Added to `y` by the lowering, its
    gradient was JAX's transpose of that broadcast add: a `reduce_sum` over
    the 4n-lane cotangent, which exists nowhere, so XLA made the select
    again in two reductions of their own, four times the lanes the
    information has, on the VPU: 12% of MedCNN's step on the chip. Every
    window sends its cotangent to one phase or to none, so the same sum is
    one over n lanes of the pooled cotangent where the window fired, and the
    pool returns it. The mask is `out > 0`, not `first < 4`, though they are
    the same array: with `first`, XLA pulled `first`'s producer into the
    backward and held `y` live again (7.42 GB a step by the described
    compile against the 6.905 before and the 6.58 of this form); behind
    `lax.optimization_barrier((out, first))` the forward split into two
    fusions that each read `y` (7.48). With `out`, the forward is what it
    was, the add in the convolution's epilogue, and the sum rides the
    input-gradient convolution that makes `g` as a second output. The bias
    is added to the whole lane width at once, tiled from its channels in
    one `jnp.tile` (a reshape to XLA; tiled in two steps it was a copy of
    its own): added to the four slices, XLA gave `y` another layout and the
    step read 7.6 GB."""
    return _relu_pool4_fwd(y, b)[0]


def _per_client(a, b):
    """`a` [C*B, ...] with the leading axes of the bias `b` [..., n] split
    off its batch: itself under `vmap` (b: [n]), [C, B, ...] for the
    client-folded batch of the `fused` lowering, whose bias is [C, n]."""
    return a.reshape(*b.shape[:-1], -1, *a.shape[1:])


def _relu_pool4_fwd(y, b):
    n = y.shape[-1] // 4
    yb = _per_client(y, b) + jnp.expand_dims(jnp.tile(b, 4 * n // b.shape[-1]), (-4, -3, -2))
    p0, p1, p2, p3 = (yb[..., p * n : (p + 1) * n] for p in range(4))
    m01, m23 = jnp.maximum(p0, p1), jnp.maximum(p2, p3)
    row0 = m01 >= m23  # ties to the lower index at every node: the FIRST maximum
    first = jnp.where(
        row0,
        jnp.where(p0 >= p1, jnp.int8(0), jnp.int8(1)),
        jnp.where(p2 >= p3, jnp.int8(2), jnp.int8(3)),
    )
    top = jnp.where(row0, m01, m23)
    pos = top > 0
    out = jnp.where(pos, top, 0).reshape(*y.shape[:-1], n)
    first = jnp.where(pos, first, jnp.int8(4)).reshape(out.shape)
    return out, (first, out, b)


def _relu_pool4_bwd(res, g):
    first, out, b = res
    n = g.shape[-1]
    phase = jnp.asarray(np.arange(4 * n) // n, jnp.int8)
    # four copies side by side, not `jnp.tile`: behind its broadcast and
    # reshape XLA fuses nothing and the step reads more than before PR 32
    first4, g4 = (jnp.concatenate([a] * 4, axis=-1) for a in (first, g))
    # `out > 0` is `first < 4`, read from the array the compiler keeps
    passed = _per_client(jnp.where(out > 0, g, 0), b)
    db = jnp.sum(passed, axis=(-4, -3, -2)).astype(b.dtype)
    db = db.reshape(*b.shape[:-1], -1, b.shape[-1]).sum(axis=-2)  # block positions
    return jnp.where(first4 == phase, g4, 0), db


_relu_pool4.defvjp(_relu_pool4_fwd, _relu_pool4_bwd)


def _polyphase_stage(conv, xs, kernel, bias, s: int):
    """One [3x3 VALID conv -> ReLU -> 2x2/2 max-pool] stage on the s x s
    space-to-depth form of its input (s = 2 or 4): the s*s conv outputs of a
    block sit side by side in the channel (lane) dimension, pool phase
    major, so the pool is an elementwise maximum of four lane groups. The
    same products summed as the plain stage (the zeros of `k2` aside).

    xs: [B, nb+1, mb+1, s*s*Ci] (`_to_depth`); kernel: [..., 3, 3, Ci, Co]
    and bias: [..., Co], with whatever leading axes `conv(x, kernel, bias)`
    takes. -> the pooled map in s/2 form, [B, nb, mb, (s/2)**2 * Co]: plain
    at s = 2, and at s = 4 the very form a following block-2 stage takes.

    The lowering is called WITHOUT the bias: the pool adds it, in the conv
    output's dtype, at every phase and block position, and returns its
    cotangent from the pooled cotangent. `_relu_pool4` says why.
    """
    ci, co = kernel.shape[-2:]
    # k2[u, v, (dy, dx, ci), (py, px, qy, qx, co)]
    #   = kernel[s*u + dy - (2*qy + py), s*v + dx - (2*qx + px), ci, co] or 0:
    # a constant 0/1 selection, so `kernel` stays the parameter and its
    # gradient comes back through the selection.
    k2 = jnp.einsum(
        "udqpa,vewzb,...abio->...uvdeipzqwo", _SEL[s], _SEL[s], kernel
    )
    k2 = k2.reshape(*kernel.shape[:-4], 2, 2, s * s * ci, s * s * co)
    # y[b, i, j, (py, px, qy, qx, co)] = the plain conv's output at
    # (s*i + 2*qy + py, s*j + 2*qx + px)
    y = conv(xs, k2, None)
    return _relu_pool4(y, bias.astype(y.dtype))


def _conv_stages(conv, x, layers):
    """The model's [conv -> ReLU -> max-pool] stack over `layers`' (kernel,
    bias) pairs through the lowering `conv(x, kernel, bias)`, each stage in
    the form `_polyphase_block` gives it. Between two stages the map stays
    in whatever space-to-depth form it has; rows and columns that the next
    stage's VALID pool would drop are not computed. Records how many stages
    took the polyphase form as the gauge `model.polyphase_stages` (set when
    the model is traced)."""
    depth, hw, taken = 1, x.shape[1:3], 0
    for i, (kernel, bias) in enumerate(layers):
        s = _polyphase_block(hw, *kernel.shape[-2:])
        pooled = [(n - 2) // 2 for n in hw]
        if not s:
            with jax.named_scope(obs_scopes.CONV):
                x = _to_depth(x, depth, hw, 1, *hw)
                x = nn.max_pool(
                    nn.relu(conv(x, kernel, bias)), (2, 2), strides=(2, 2))
            depth, hw = 1, pooled
            continue
        if i + 1 < len(layers):  # all the next stage reads of this one
            pooled = [min(n, 2 * ((n - 2) // 2) + 2) for n in pooled]
        nb, mb = (-(-n // (s // 2)) for n in pooled)
        with jax.named_scope(obs_scopes.CONV):
            x = _to_depth(x, depth, hw, s, nb + 1, mb + 1)
            x = _polyphase_stage(conv, x, kernel, bias, s)
        depth, hw, taken = s // 2, pooled, taken + 1
    obs_metrics.gauge("model.polyphase_stages").set(taken)
    with jax.named_scope(obs_scopes.CONV):
        return _to_depth(x, depth, hw, 1, *hw)


def _conv_bf16(x, kernel, bias):
    """flax.linen.Conv(dtype=bfloat16)'s computation: operands and bias
    rounded to bf16, one 3x3 VALID NHWC convolution, bias added in bf16.
    `bias=None` (as `folded_conv` takes it) leaves the sum to the caller: a
    polyphase stage adds the same bf16 bias to the same bf16 output inside
    `_relu_pool4`, which makes the bias gradient where it costs least."""
    y = lax.conv_general_dilated(
        x.astype(jnp.bfloat16), kernel.astype(jnp.bfloat16), (1, 1), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    return y if bias is None else y + bias.astype(jnp.bfloat16)


class _ConvParams(nn.Module):
    """The parameters of one 3x3 conv under flax.linen.Conv's names, shapes,
    dtypes and initialisers (the HE packing contract and the checkpoint
    format), declared apart from the computation so that the stage can
    choose its form."""

    features: int

    @nn.compact
    def __call__(self, in_features: int):
        kernel = self.param(
            "kernel", nn.linear.default_kernel_init,
            (3, 3, in_features, self.features), jnp.float32,
        )
        bias = self.param(
            "bias", nn.initializers.zeros_init(), (self.features,), jnp.float32
        )
        return kernel, bias


class MedCNN(nn.Module):
    """The reference's medical-image CNN (FLPyfhelin.py:118-141), 222,722
    params at 256x256x3 with the default fields.

    Fully parameterized: `features` sets the conv stack, `dense` the ReLU
    head widths — smaller variants (e.g. the 2-conv MNIST model) are just
    different field values.
    """

    num_classes: int = 2
    features: Sequence[int] = (32, 32, 32, 64, 64, 128)
    dense: Sequence[int] = (128, 64)
    apply_softmax: bool = False

    @nn.compact
    def __call__(self, x):
        widths = (x.shape[-1], *self.features)
        x = _conv_stages(_conv_bf16, x, [
            _ConvParams(f, name=f"Conv_{i}")(widths[i])
            for i, f in enumerate(self.features)
        ])
        with jax.named_scope(obs_scopes.DENSE):
            x = x.reshape((x.shape[0], -1))
            for d in self.dense:
                x = nn.Dense(d, dtype=jnp.bfloat16, param_dtype=jnp.float32)(x)
                x = nn.relu(x)
            x = nn.Dense(
                self.num_classes, dtype=jnp.bfloat16, param_dtype=jnp.float32)(x)
            x = x.astype(jnp.float32)
            return nn.softmax(x) if self.apply_softmax else x

    def folded_apply(self, stacked_params, x, *, num_clients: int):
        """The client-folded forward (`TrainConfig.client_fusion="fused"`):
        same architecture and compute dtypes as `__call__`, but over a
        client-folded batch with per-client weights.

        x: [C*B, H, W, ch] float activations, client c owning rows
        [c*B:(c+1)*B]; stacked_params: this module's param pytree with a
        leading client axis on every leaf (models.folded.stack_params
        layout). Every conv is ONE batch-grouped conv of batch C*B and
        every dense ONE client-batched GEMM — identical math /
        cost_analysis() FLOPs to `jax.vmap(self.apply)`, in one op per
        layer. -> logits (or probs) [C*B, num_classes] float32.
        """
        c = num_clients
        x = _conv_stages(partial(folded_conv, num_clients=c), x, [
            (stacked_params[f"Conv_{i}"]["kernel"], stacked_params[f"Conv_{i}"]["bias"])
            for i in range(len(self.features))
        ])
        b = x.shape[0] // c
        with jax.named_scope(obs_scopes.DENSE):
            x = x.reshape(c, b, -1)
            for j in range(len(self.dense)):
                lyr = stacked_params[f"Dense_{j}"]
                x = nn.relu(folded_dense(x, lyr["kernel"], lyr["bias"]))
            head = stacked_params[f"Dense_{len(self.dense)}"]
            x = folded_dense(x, head["kernel"], head["bias"])
            x = x.astype(jnp.float32).reshape(c * b, -1)
            return nn.softmax(x) if self.apply_softmax else x


class SmallCNN(MedCNN):
    """2-conv CNN for the MNIST baseline configs (BASELINE.json configs 1-2):
    MedCNN's architecture vocabulary scaled to 28x28x1."""

    num_classes: int = 10
    features: Sequence[int] = (32, 64)
    dense: Sequence[int] = (128,)


class LogReg(nn.Module):
    """Multinomial logistic regression (flatten -> one Dense): the standard
    large-cohort DP-FedAvg demonstrator. Central DP's per-coordinate noise
    on the released mean is sigma*C/K while a clipped update's per-coordinate
    signal is ~C/sqrt(d), so at fixed privacy the utility frontier is set by
    K/sqrt(d) — a low-d model is how a CPU-sized cohort (fl/dp.py cohort-size
    law) shows DP being useful AND private, where a 225k-param CNN at the
    same epsilon is buried in its own noise (RESULTS.md r4 DP rows)."""

    num_classes: int = 10
    apply_softmax: bool = False

    @nn.compact
    def __call__(self, x):
        x = x.reshape((x.shape[0], -1))
        x = nn.Dense(
            self.num_classes, dtype=jnp.bfloat16, param_dtype=jnp.float32
        )(x)
        x = x.astype(jnp.float32)
        return nn.softmax(x) if self.apply_softmax else x

    def folded_apply(self, stacked_params, x, *, num_clients: int):
        """Client-folded forward (see MedCNN.folded_apply): one batched
        GEMM for the whole cohort's logistic regression."""
        c = num_clients
        b = x.shape[0] // c
        x = x.reshape(c, b, -1)
        lyr = stacked_params["Dense_0"]
        x = folded_dense(x, lyr["kernel"], lyr["bias"])
        x = x.astype(jnp.float32).reshape(c * b, -1)
        return nn.softmax(x) if self.apply_softmax else x


def count_params(params) -> int:
    """Total scalar parameter count of a pytree (222,722 for MedCNN@256)."""
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
