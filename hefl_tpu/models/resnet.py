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
    folded_conv,
    folded_dense,
    folded_group_norm,
)
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

    def folded_apply(self, stacked_params, x, *, num_clients: int):
        """Client-folded forward (`TrainConfig.client_fusion="fused"`; see
        models.folded and MedCNN.folded_apply): the same depth-20 network
        over a client-folded batch with per-client weights — every conv one
        batch-grouped conv of batch C*B, GroupNorm per-sample (folding-
        invariant) with per-client affines. x: [C*B, H, W, ch];
        stacked_params: this module's params with a leading client axis.
        -> [C*B, num_classes] float32.
        """
        c = num_clients

        def gn(p, h):
            with jax.named_scope(NORM):
                return folded_group_norm(
                    h, p["scale"], p["bias"], num_clients=c, num_groups=8
                )

        def conv(h, kernel, **kw):
            with jax.named_scope(CONV):
                return folded_conv(
                    h, kernel, None, num_clients=c, padding="SAME", **kw)

        def block(p, h, stride):
            y = conv(h, p["Conv_0"]["kernel"], strides=(stride, stride))
            y = nn.relu(gn(p["GroupNorm_0"], y))
            y = conv(y, p["Conv_1"]["kernel"])
            y = gn(p["GroupNorm_1"], y)
            residual = h
            if "Conv_2" in p:  # projection shortcut (shape change)
                residual = conv(h, p["Conv_2"]["kernel"], strides=(stride, stride))
                residual = gn(p["GroupNorm_2"], residual)
            return nn.relu(y + residual)

        x = conv(x, stacked_params["Conv_0"]["kernel"])
        x = nn.relu(gn(stacked_params["GroupNorm_0"], x))
        i = 0
        for stage, blocks in enumerate(self.stage_sizes):
            for b_idx in range(blocks):
                stride = 2 if (stage > 0 and b_idx == 0) else 1
                x = block(stacked_params[f"BasicBlock_{i}"], x, stride)
                i += 1
        with jax.named_scope(DENSE):
            x = jnp.mean(x, axis=(1, 2))
            b = x.shape[0] // c
            head = stacked_params["Dense_0"]
            x = folded_dense(x.reshape(c, b, -1), head["kernel"], head["bias"])
            x = x.astype(jnp.float32).reshape(c * b, -1)
            return nn.softmax(x) if self.apply_softmax else x
