"""Client-folded layer primitives: per-client weights, one GEMM stream.

The cross-client training backend (`TrainConfig.client_fusion="fused"`,
fl.fusion) trains a device's whole block of C clients through ONE forward/
backward per step instead of a vmap over clients. The layer math lives
here, and the key decision is how per-client convolutions lower:

  * What vmap emits: JAX's conv batching rule folds a both-operands-
    batched conv into GROUPED convolutions (`feature_group_count *= C`;
    see jax._src.lax.convolution._conv_general_dilated_batch_rule), and
    its autodiff transposes are grouped convs too. Grouped convs keep each
    client's GEMM separate — the MXU never sees a tile-filling batch, and
    XLA backends routinely hit slow paths on the grouped transpose forms
    (measured on XLA:CPU: the weight-gradient of one 13x13 conv layer at
    8 clients is ~440 ms as a grouped conv vs ~10 ms as the GEMM below).
  * What `folded_conv` emits: direct convolution by kernel-offset
    decomposition — for each of the kh*kw kernel taps, one
    client-batched `dot_general` ('cbpqi,cio->cbpqo') over the strided
    input window, accumulated in f32 and rounded once. Every stage of
    training — forward, input-gradient, weight-gradient — then lowers to
    the SAME shape of batched GEMM whose leading dimensions stream
    C*B*H'*W' rows through the MXU, with the client axis as the
    dot_general batch. Identical math, identical `cost_analysis()` FLOPs
    (kh*kw*C * 2*M*N*K is exactly the conv's count), no grouped convs
    anywhere.

  * What the `packed_*` primitives emit (ResNet20, PR 37): the clients in
    the LANES. At 16 / 32 / 64 channels a client-leading array fills 32 of
    128 lanes whichever of clients, images or channels the compiler makes
    minor-most; with `g = pack_size(C, width)` clients a pack the
    activations are `[C/g, B, H, W, g*width]`, each client's kernel sits on
    the diagonal of a `[C/g, kh, kw, g*in, g*out]` kernel
    (`block_diagonal`) and a convolution is one dense 128-wide convolution
    a pack; `packed_group_norm` makes the statistics a lane. `g` times the
    multiply-adds, a third of the step's time on the chip (PERF.md, PR 37).
    `folded_conv`'s tap form is for wide channels; at K = N = 16 it reads
    264 GB a ResNet-20 step by the compiler's count.

All primitives are mathematically exact per client (block-structured:
client c's outputs depend only on client c's inputs and weights — the
batched GEMM never mixes batch groups), so fused-vs-vmap equivalence is a
float-tolerance property, not an approximation (tests/test_perf.py pins
it).

Width stability (ISSUE 15; `folded_conv` / `folded_dense`, not the
`packed_*` primitives, whose pack size and so whose order of summation
follow the client count): at any client count >= 2 these primitives —
and the grouped-conv forms the vmap backend lowers to — produce BITWISE
identical per-client floats regardless of how many clients share the
batch (the per-group/per-batch-entry math is width-independent), while a
width of exactly 1 takes XLA's ungrouped lowering, a different algorithm
with different rounding. The cohort bucket ladder
(`fl.fedavg.cohort_bucket`) floors buckets at 2 slots per device so
cohort-only training and the full-C reference always sit on the same
side of that line — the structural half of the cohort-vs-full bitwise
equality gates (tests/test_cohort.py pins it on both backends).

Layout contract shared by every primitive:

  * folded activations: [C*B, ...] with client c owning the contiguous
    rows [c*B : (c+1)*B] (`fold_clients` / `unfold_clients` — pure
    reshapes, client-major order makes them free);
  * stacked params: the pytree of per-client weights with a leading client
    axis on every leaf (`stack_params`).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _conv_taps(xg, kb, strides, out_hw):
    """The kernel-offset GEMM core of `folded_conv` with an explicit VJP.

    Forward is the tap loop unchanged (kh*kw client-batched dot_generals
    on the compute-dtype operands, f32 partial-sum accumulation, ONE
    rounding to the compute dtype).

    The custom VJP exists for the PRECISION story, not the math: under
    plain autodiff the `preferred_element_type=f32` sticks to every
    transposed dot_general, so the backward pass materializes its
    input-gradients — the tensors handed BETWEEN layers — in float32,
    doubling backward activation-bandwidth over the bf16 forward. Here the
    backward mirrors the forward's dtype discipline exactly: every dgrad/
    wgrad GEMM runs on the bf16 residuals/cotangent with f32 ACCUMULATION
    (preferred_element_type), cross-tap partials accumulate in f32, and
    each result rounds ONCE to the operand's dtype — the input gradient to
    the activation dtype (inter-layer tensors are bf16, same bytes as the
    forward activations) and the weight gradient to the compute-dtype
    kernel view, which the `kernel.astype` transpose outside then upcasts.
    That one bf16 rounding on the wgrad is the HISTORICAL semantics: it is
    what both plain autodiff of this einsum form and the vmapped
    flax.linen.Conv(dtype=bf16) reference produce, and the fused-vs-vmap
    parity tests pin it.

    xg: [C, B, H, W, ch] compute-dtype activations; kb: [C, kh, kw, ch, f]
    compute-dtype filters. -> [C, B, H', W', f] in xg.dtype.
    """
    return _conv_taps_impl(xg, kb, strides, out_hw)


def _conv_taps_impl(xg, kb, strides, out_hw):
    sh, sw = strides
    ho, wo = out_hw
    c, b = xg.shape[0], xg.shape[1]
    ch = xg.shape[4]
    kh, kw = kb.shape[1], kb.shape[2]

    acc = None
    for i in range(kh):
        for j in range(kw):
            xs = lax.slice(
                xg,
                (0, 0, i, j, 0),
                (c, b, i + (ho - 1) * sh + 1, j + (wo - 1) * sw + 1, ch),
                (1, 1, sh, sw, 1),
            )
            t = jnp.einsum(
                "cbpqi,cio->cbpqo", xs, kb[:, i, j],
                preferred_element_type=jnp.float32,
            )
            acc = t if acc is None else acc + t
    return acc.astype(xg.dtype)


def _conv_taps_fwd(xg, kb, strides, out_hw):
    return _conv_taps_impl(xg, kb, strides, out_hw), (xg, kb)


def _conv_taps_bwd(strides, out_hw, res, g):
    # g arrives in the compute dtype (the forward output's aval): the
    # incoming cotangent is already bf16-sized. Both gradients are the
    # einsum transposes of the forward taps — still client-batched GEMMs,
    # never a grouped conv — with f32 accumulation and one final rounding
    # to the respective operand dtype (see _conv_taps' docstring for why
    # the wgrad rounding is the historical/flax-parity semantics).
    xg, kb = res
    sh, sw = strides
    ho, wo = out_hw
    c, b = xg.shape[0], xg.shape[1]
    ch = xg.shape[4]
    kh, kw = kb.shape[1], kb.shape[2]

    dxg = jnp.zeros(xg.shape, jnp.float32)
    dk_taps = []
    for i in range(kh):
        for j in range(kw):
            lo_h, hi_h = i, i + (ho - 1) * sh + 1
            lo_w, hi_w = j, j + (wo - 1) * sw + 1
            xs = lax.slice(
                xg, (0, 0, i, j, 0), (c, b, hi_h, hi_w, ch),
                (1, 1, sh, sw, 1),
            )
            dk_taps.append(jnp.einsum(
                "cbpqi,cbpqo->cio", xs, g,
                preferred_element_type=jnp.float32,
            ))
            dxs = jnp.einsum(
                "cbpqo,cio->cbpqi", g, kb[:, i, j],
                preferred_element_type=jnp.float32,
            )
            # Overlapping tap windows accumulate additively (in f32).
            dxg = dxg.at[:, :, lo_h:hi_h:sh, lo_w:hi_w:sw, :].add(dxs)
    dk = jnp.stack(dk_taps, axis=1).reshape(kb.shape).astype(kb.dtype)
    return dxg.astype(xg.dtype), dk


_conv_taps.defvjp(_conv_taps_fwd, _conv_taps_bwd)


def fold_clients(x: jax.Array) -> jax.Array:
    """[C, B, ...] -> [C*B, ...] (client-major, contiguous per client)."""
    return x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])


def unfold_clients(x: jax.Array, num_clients: int) -> jax.Array:
    """[C*B, ...] -> [C, B, ...]."""
    return x.reshape((num_clients, x.shape[0] // num_clients) + x.shape[1:])


def stack_params(params, num_clients: int):
    """Broadcast one parameter pytree to the stacked per-client layout
    (leaves gain a leading client axis). The fused trainer's round entry:
    every client starts from the round's global weights."""
    return jax.tree_util.tree_map(
        lambda t: jnp.broadcast_to(t[None], (num_clients,) + t.shape), params
    )


def folded_conv(
    x: jax.Array,
    kernel: jax.Array,
    bias: jax.Array | None,
    *,
    num_clients: int,
    strides: tuple[int, int] = (1, 1),
    padding: str = "VALID",
    dtype=jnp.bfloat16,
) -> jax.Array:
    """Per-client 2-D convolution as kh*kw client-batched GEMMs.

    x: [C*B, H, W, ch] folded activations; kernel: [C, kh, kw, ch, f]
    stacked per-client filters; bias: [C, f] or None. -> [C*B, H', W', f].

    Direct convolution by kernel-offset decomposition (module docstring):
    each kernel tap contributes one `dot_general` with the client axis as
    the GEMM batch, partials accumulate in f32 (XLA's own conv
    accumulation dtype) and round ONCE to `dtype` — matching
    flax.linen.Conv(dtype=bf16, param_dtype=f32) numerics at equal
    inputs. Autodiff of this form stays in the same GEMM family: the
    weight- and input-gradients are the einsum transposes (`_conv_taps`'
    custom VJP), never a grouped-conv slow path — and the backward keeps
    the forward's dtype discipline: inter-layer gradient tensors are
    `dtype` (bf16), f32 only inside GEMM accumulation and the cross-tap
    partial sums, halving backward activation bandwidth vs the plain-
    autodiff f32 cotangents.
    """
    c = num_clients
    kh, kw, ch, f = kernel.shape[1:]
    xb = x.astype(dtype)
    k = kernel.astype(dtype)
    cb, h, w = x.shape[0], x.shape[1], x.shape[2]
    b = cb // c
    sh, sw = strides
    if padding == "SAME":
        ph = max((math.ceil(h / sh) - 1) * sh + kh - h, 0)
        pw = max((math.ceil(w / sw) - 1) * sw + kw - w, 0)
        xb = jnp.pad(
            xb, ((0, 0), (ph // 2, ph - ph // 2), (pw // 2, pw - pw // 2), (0, 0))
        )
        h, w = xb.shape[1], xb.shape[2]
    elif padding != "VALID":
        raise ValueError(f"folded_conv: unsupported padding {padding!r}")
    ho = (h - kh) // sh + 1
    wo = (w - kw) // sw + 1
    xg = xb.reshape(c, b, h, w, ch)
    out = _conv_taps(xg, k, (sh, sw), (ho, wo))
    if bias is not None:
        out = out + bias.astype(dtype)[:, None, None, None, :]
    return out.reshape(cb, ho, wo, f)


def folded_dense(
    x: jax.Array,
    kernel: jax.Array,
    bias: jax.Array | None,
    *,
    dtype=jnp.bfloat16,
) -> jax.Array:
    """Per-client dense layer as ONE batched GEMM.

    x: [C, B, d_in]; kernel: [C, d_in, d_out]; bias: [C, d_out] or None.
    -> [C, B, d_out] in `dtype` (flax Dense compute-dtype semantics).
    """
    out = jnp.einsum(
        "cbi,cio->cbo", x.astype(dtype), kernel.astype(dtype)
    )
    if bias is not None:
        out = out + bias[:, None, :].astype(dtype)
    return out


LANES = 128  # a vreg's and an MXU tile's minor dimension


def pack_size(num_clients: int, width: int) -> int:
    """Clients a lane pack holds at `width` channels: the largest divisor
    of the client count not above 128 // width (1 where none divides: the
    plain per-client convolution)."""
    cap = max(LANES // width, 1)
    return max(g for g in range(1, cap + 1) if num_clients % g == 0)


def pack_clients(x: jax.Array, g: int) -> jax.Array:
    """[C, B, H, W, w] -> [C/g, B, H, W, g*w]: client p*g + j of pack p in
    lanes [j*w, (j+1)*w)."""
    c, b, h, w, ch = x.shape
    x = x.reshape(c // g, g, b, h, w, ch).transpose(0, 2, 3, 4, 1, 5)
    return x.reshape(c // g, b, h, w, g * ch)


def unpack_clients(x: jax.Array, g: int) -> jax.Array:
    """[C/g, B, ..., g*w] -> [C, B, ..., w], `pack_clients`' inverse (any
    number of axes between the batch and the lanes)."""
    p, b, *mid, lanes = x.shape
    x = x.reshape(p, b, *mid, g, lanes // g)
    x = jnp.moveaxis(x, -2, 1)
    return x.reshape(p * g, b, *mid, lanes // g)


def repack_clients(x: jax.Array, g_from: int, g_to: int) -> jax.Array:
    """Packs of `g_from` clients to packs of `g_to`. Where g_to divides
    g_from a pack's lanes are cut into g_from // g_to runs, each a pack of
    its own (the client order is kept, so no lane moves inside a run);
    written out because through `unpack_clients` and `pack_clients` XLA
    moves the lanes (17.95 against 14.35 GB a ResNet-20 step)."""
    if g_from == g_to:
        return x
    if g_from % g_to:
        return pack_clients(unpack_clients(x, g_from), g_to)
    p, b, h, w, lanes = x.shape
    k = g_from // g_to
    x = x.reshape(p, b, h, w, k, lanes // k)
    return jnp.moveaxis(x, 4, 1).reshape(p * k, b, h, w, lanes // k)


def block_diagonal(kernel: jax.Array, g: int, dtype=jnp.bfloat16) -> jax.Array:
    """[C, kh, kw, i, o] per-client kernels -> [C/g, kh, kw, g*i, g*o], each
    client's on the diagonal of its pack and exact zeros elsewhere. A
    select, so its transpose hands each client the gradient of its own
    block and drops the rest; call it inside the differentiated function."""
    c, kh, kw, i, o = kernel.shape
    k = kernel.astype(dtype).reshape(c // g, g, kh, kw, i, o)
    k = k.transpose(0, 2, 3, 1, 4, 5)[:, :, :, :, :, None, :]
    own = jnp.eye(g, dtype=bool)[:, None, :, None]  # [g, 1, g, 1]
    k = jnp.where(own, k, jnp.zeros((), dtype))     # [P, kh, kw, g, i, g, o]
    return k.reshape(c // g, kh, kw, g * i, g * o)


def packed_conv(
    x: jax.Array,
    kernel: jax.Array,
    g: int,
    *,
    strides: tuple[int, int] = (1, 1),
    dtype=jnp.bfloat16,
) -> jax.Array:
    """Per-client SAME convolution over lane packs: one dense convolution a
    pack, `g*i` lanes to `g*o`. x: [C/g, B, H, W, g*i]; kernel:
    [C, kh, kw, i, o] stacked per-client filters. -> [C/g, B, H', W', g*o]
    in `dtype`, as flax.linen.Conv(dtype=bf16, param_dtype=f32) rounds it
    (the product accumulates in float32 and is rounded once)."""
    conv = partial(
        lax.conv_general_dilated, window_strides=strides, padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    return jax.vmap(conv)(x.astype(dtype), block_diagonal(kernel, g, dtype))


def packed_group_norm(
    x: jax.Array,
    scale: jax.Array,
    bias: jax.Array,
    g: int,
    *,
    num_groups: int,
    eps: float = 1e-6,
) -> jax.Array:
    """flax.linen.GroupNorm(num_groups, dtype=float32) a client over lane
    packs. x: [C/g, B, H, W, g*w] (computed in float32); scale, bias:
    [C, w]. Statistics per (client, image, group), flax's fast-variance
    form: the sums of x and x*x over H and W are made a lane first, the
    w / num_groups lanes of a group are combined on that [C/g, B, g*w]
    array, and mean and rsqrt(var + eps) go back a lane; no full-size
    array is ever reshaped to groups. -> float32, x's shape."""
    p, b, h, w, lanes = x.shape
    per = lanes // g // num_groups  # lanes a group
    xf = x.astype(jnp.float32)

    def group_mean(t):  # [P, B, lanes] sums over H, W -> group means a lane
        t = t.reshape(p, b, lanes // per, per).sum(-1) / (h * w * per)
        return jnp.repeat(t, per, axis=-1)[:, :, None, None, :]

    mean = group_mean(jnp.sum(xf, axis=(2, 3)))
    mean2 = group_mean(jnp.sum(jnp.square(xf), axis=(2, 3)))
    var = jnp.maximum(mean2 - jnp.square(mean), 0.0)
    lane = lambda t: t.astype(jnp.float32).reshape(p, 1, 1, 1, lanes)  # noqa: E731
    return (xf - mean) * (lax.rsqrt(var + eps) * lane(scale)) + lane(bias)
