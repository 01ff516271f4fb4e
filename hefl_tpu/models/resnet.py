"""ResNet-20 (CIFAR-10 variant) for the 16-client baseline config.

BASELINE.json config 5: "16-client encrypted FedAvg of ResNet-20 on
CIFAR-10 (one client per TPU core)". The reference repo contains no ResNet;
this is the standard He et al. CIFAR depth-20 network: 3 stages of 3 basic
blocks with widths (16, 32, 64), stride-2 downsampling between stages,
global average pool, linear head — 0.27M params.

FL-specific design choice: normalization is GroupNorm, not BatchNorm.
BatchNorm's running statistics are client-local state that poisons FedAvg
(the classic non-IID failure mode) and adds non-parameter state to the
encrypted aggregation payload; GroupNorm keeps every learnable a plain
weight so the ciphertext packing covers the whole model.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from hefl_tpu.models.folded import (
    fold_clients,
    folded_dense,
    pack_clients,
    pack_size,
    packed_conv,
    packed_group_norm,
    repack_clients,
    unfold_clients,
    unpack_clients,
)
from hefl_tpu.obs import metrics as obs_metrics
from hefl_tpu.obs.scopes import CONV, DENSE, NORM


class BasicBlock(nn.Module):
    features: int
    stride: int = 1

    @nn.compact
    def __call__(self, x):
        residual = x
        with jax.named_scope(CONV):
            y = nn.Conv(
                self.features, (3, 3), strides=(self.stride, self.stride),
                padding="SAME", use_bias=False,
                dtype=jnp.bfloat16, param_dtype=jnp.float32,
            )(x)
        with jax.named_scope(NORM):
            y = nn.GroupNorm(num_groups=8, dtype=jnp.float32)(y)
        y = nn.relu(y)
        with jax.named_scope(CONV):
            y = nn.Conv(
                self.features, (3, 3), padding="SAME", use_bias=False,
                dtype=jnp.bfloat16, param_dtype=jnp.float32,
            )(y)
        with jax.named_scope(NORM):
            y = nn.GroupNorm(num_groups=8, dtype=jnp.float32)(y)
        if residual.shape != y.shape:
            with jax.named_scope(CONV):
                residual = nn.Conv(
                    self.features, (1, 1), strides=(self.stride, self.stride),
                    use_bias=False, dtype=jnp.bfloat16, param_dtype=jnp.float32,
                )(residual)
            with jax.named_scope(NORM):
                residual = nn.GroupNorm(num_groups=8, dtype=jnp.float32)(residual)
        return nn.relu(y + residual)


class ResNet20(nn.Module):
    num_classes: int = 10
    stage_sizes: tuple[int, ...] = (3, 3, 3)
    widths: tuple[int, ...] = (16, 32, 64)
    apply_softmax: bool = False

    @nn.compact
    def __call__(self, x):
        with jax.named_scope(CONV):
            x = nn.Conv(
                self.widths[0], (3, 3), padding="SAME", use_bias=False,
                dtype=jnp.bfloat16, param_dtype=jnp.float32,
            )(x)
        with jax.named_scope(NORM):
            x = nn.GroupNorm(num_groups=8, dtype=jnp.float32)(x)
        x = nn.relu(x)
        for stage, (blocks, width) in enumerate(zip(self.stage_sizes, self.widths)):
            for b in range(blocks):
                stride = 2 if (stage > 0 and b == 0) else 1
                x = BasicBlock(width, stride)(x)
        with jax.named_scope(DENSE):
            x = jnp.mean(x, axis=(1, 2))
            x = nn.Dense(
                self.num_classes, dtype=jnp.bfloat16, param_dtype=jnp.float32)(x)
            x = x.astype(jnp.float32)
            return nn.softmax(x) if self.apply_softmax else x

    # The client-folded forward below fills the 128 lanes with clients:
    # `fl/fusion.resolve_fusion_backend` sends such a model through `fused`.
    folded_lane_packed = True

    def folded_apply(self, stacked_params, x, *, num_clients: int):
        """Client-folded forward, clients packed into the lanes (the
        `fused` lowering, which `auto` resolves to for this model): with
        `g = pack_size(C, width)` clients a pack (8, 4, 2 at widths 16, 32,
        64 where they divide C) activations live as [C/g, B, H, W, g*width]
        through the whole network, every convolution is one dense bfloat16
        convolution a pack over a block-diagonal kernel
        (`models.folded.packed_conv`) and GroupNorm works a lane
        (`packed_group_norm`). A stage's first block convolves at the old
        `g` and cuts the doubled lanes into the new packs. The same
        mathematics as `vmap` of `__call__` to the order of summation: the
        off-diagonal blocks multiply exact zeros. x: [C*B, H, W, ch];
        stacked_params: this module's params with a leading client axis.
        -> [C*B, num_classes] float32.
        """
        c = num_clients
        packed = 0

        def gn(p, h, g):
            with jax.named_scope(NORM):
                return packed_group_norm(
                    h, p["scale"], p["bias"], g, num_groups=8)

        def conv(h, kernel, g, g_out, stride=1):
            nonlocal packed
            packed += g > 1
            with jax.named_scope(CONV):
                y = packed_conv(h, kernel, g, strides=(stride, stride))
                return repack_clients(y, g, g_out)

        def block(p, h, g_in, g, stride):
            y = conv(h, p["Conv_0"]["kernel"], g_in, g, stride)
            y = nn.relu(gn(p["GroupNorm_0"], y, g))
            y = conv(y, p["Conv_1"]["kernel"], g, g)
            y = gn(p["GroupNorm_1"], y, g)
            residual = h
            if "Conv_2" in p:  # projection shortcut (shape change)
                residual = conv(h, p["Conv_2"]["kernel"], g_in, g, stride)
                residual = gn(p["GroupNorm_2"], residual, g)
            return nn.relu(y + residual)

        g = pack_size(c, self.widths[0])
        with jax.named_scope(CONV):
            x = pack_clients(unfold_clients(x, c), g)
        x = conv(x, stacked_params["Conv_0"]["kernel"], g, g)
        x = nn.relu(gn(stacked_params["GroupNorm_0"], x, g))
        i = 0
        for stage, (blocks, width) in enumerate(zip(self.stage_sizes, self.widths)):
            for b_idx in range(blocks):
                stride = 2 if (stage > 0 and b_idx == 0) else 1
                g_in, g = g, pack_size(c, width)
                x = block(stacked_params[f"BasicBlock_{i}"], x, g_in, g, stride)
                i += 1
        obs_metrics.gauge("model.packed_conv_layers").set(packed)
        with jax.named_scope(DENSE):
            x = unpack_clients(jnp.mean(x, axis=(2, 3)), g)  # [C, B, width]
            head = stacked_params["Dense_0"]
            x = folded_dense(x, head["kernel"], head["bias"])
            x = fold_clients(x.astype(jnp.float32))
            return nn.softmax(x) if self.apply_softmax else x
