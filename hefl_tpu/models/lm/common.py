"""What every module of the token models shares: the architecture's keys
(`LMArch`, `PRESETS`), the float32 pieces of a layer (`rms_norm`, `rope` at
plain or YaRN frequencies, `softmax_scale`), the products' precision (`_mm`:
bfloat16 operands, float32 accumulation; the residual stream, the norms, the
router, the selection and the softmax are float32) and where Pallas runs
interpreted (`_interpret`). It imports no module of the package.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

F32, BF16 = jnp.float32, jnp.bfloat16
HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class LMArch:
    """Widths and counts: the published ones, and the share held here."""

    hidden: int = 2048
    heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate: int = 7168          # the dense layer's MLP
    moe_intermediate: int = 768
    n_experts: int = 256              # the router's width, as published
    experts_per_tok: int = 8
    routed_scaling: float = 2.5
    rope_theta: float = 32_000_000.0
    eps: float = 1e-6
    dense_layers: int = 1             # first_k_dense_replace
    expert_layers: int = 4            # of the published 39
    held_start: int = 0               # this chip's experts: [start, start+held)
    held_experts: int = 128           # of n_experts
    mtp_weight: float = 0.1           # assumed: V3's final value
    init_std: float = 0.02            # assumed: V3's initializer_range
    q_block: int = 1024               # attention's query and key block
    loss_chunk: int = 2048            # tokens a slice of the head's logits
    # group-limited routing: the experts in `n_group` groups, a token's
    # experts taken from its `topk_group` best groups (1, 1: no groups)
    n_group: int = 1
    topk_group: int = 1
    # YaRN: (factor, original positions, beta_fast, beta_slow,
    # mscale_all_dim), or None for plain RoPE
    rope_scaling: tuple | None = None
    # the indexer of learned sparse attention (0 heads: none, attention is
    # causal over every key): a query attends its `index_topk` best keys
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    index_block: int = 256            # queries a slice of the indexer's scores
    pair_block: int = 512             # rows a block of the held experts' pairs
    # rows of the sorted held pairs taken in one grouped product ahead of
    # the blocks, every row computed (`_held_front`; 0: blocks alone)
    pair_front: int = 0
    # grouped-query attention with no latents (`kv_heads` empty: latent
    # attention): a head is `qk_rope_head_dim` rotated dims (half-split
    # pairs) then `qk_nope_head_dim` that pass through. A layer is of kind
    # `layer_pattern[layer]`, 0 global (causal over every key) or 1 window
    # (the `window` last keys, the query's own among them); by kind its KV
    # heads, its RoPE base and whether a head has a trained sink
    kv_heads: tuple = ()
    rope_thetas: tuple = ()
    sinks: tuple = ()
    layer_pattern: tuple = ()
    window: int = 0
    value_scale: float = 1.0          # v is scaled by it
    shared_experts: int = 1           # 0: an expert layer is its routed part
    mtp_modules: int = 1              # 0: one head, next-token loss alone
    # delta-rule linear attention (`kda_head_dim` 0: none): a layer is of
    # kind `layer_pattern[layer]`, `LATENT` or `LINEAR`; a linear layer has
    # `heads` heads whose keys and values are `kda_head_dim` wide, a
    # depthwise causal convolution over the `kda_conv` last positions and a
    # decay a channel in (`kda_lower_bound`, 0); its recurrence runs in
    # chunks of `kda_chunk` positions whose triangular system is solved in
    # sub-blocks of `kda_block` (`kda_recurrence`)
    kda_head_dim: int = 0
    kda_conv: int = 4
    kda_lower_bound: float = -5.0
    kda_chunk: int = 64
    kda_block: int = 16
    # latent attention with `q_lora_rank` 0 makes its queries straight from
    # x (`q`, no `q_norm`); with `attn_gate` a head's output is scaled by
    # sigmoid(x W_gate) of that head before `o`
    attn_gate: bool = False


PRESETS = {
    # the benchmark's `joyai-llm-flash-l5e128`: every width as published
    "joyai_llm_flash": LMArch(),
    # the tests' size
    "joyai_llm_flash_tiny": LMArch(
        hidden=64, heads=4, q_lora_rank=48, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        intermediate=128, moe_intermediate=32, n_experts=8,
        experts_per_tok=2, expert_layers=2, held_start=0, held_experts=4,
        q_block=128, loss_chunk=16),
    # the benchmark's `deepseek-v32-exp-l5e8`: every width as published, a
    # chip's share of 32 that divide every expert layer (experts 0-7)
    "deepseek_v32": LMArch(
        hidden=7168, heads=128, intermediate=18432, moe_intermediate=2048,
        rope_theta=10_000.0, held_experts=8, n_group=8, topk_group=4,
        rope_scaling=(40.0, 4096, 32.0, 1.0, 1.0),
        index_heads=64, index_head_dim=128, index_topk=2048, q_block=512),
    "deepseek_v32_tiny": LMArch(
        hidden=64, heads=4, q_lora_rank=48, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        intermediate=128, moe_intermediate=32, n_experts=16,
        experts_per_tok=2, expert_layers=2, held_start=0, held_experts=4,
        rope_theta=10_000.0, n_group=4, topk_group=2,
        rope_scaling=(40.0, 16, 32.0, 1.0, 1.0),
        index_heads=4, index_head_dim=16, index_topk=8, index_block=16,
        pair_block=32, q_block=128, loss_chunk=16),
    # the benchmark's `mimo-v2-flash-l7e16`: every width as published, the
    # published layers 0-6 (the leading dense layer and one period of five
    # window layers to one global), a chip's share of 16 that divide every
    # expert layer (experts 0-15)
    "mimo_v2_flash": LMArch(
        hidden=4096, heads=64, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, intermediate=16384, moe_intermediate=2048,
        routed_scaling=1.0, eps=1e-5, expert_layers=6, held_experts=16,
        kv_heads=(4, 8), rope_thetas=(5_000_000.0, 10_000.0),
        sinks=(False, True), layer_pattern=(0, 1, 1, 1, 1, 0, 1), window=128,
        value_scale=0.707, shared_experts=0, mtp_modules=0, pair_front=24576),
    "mimo_v2_flash_tiny": LMArch(
        hidden=64, heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, intermediate=128, moe_intermediate=32, n_experts=8,
        experts_per_tok=2, routed_scaling=1.0, eps=1e-5, expert_layers=3,
        held_experts=4, kv_heads=(1, 2), rope_thetas=(5_000_000.0, 10_000.0),
        sinks=(False, True), layer_pattern=(0, 1, 1, 0), window=8,
        value_scale=0.707, shared_experts=0, mtp_modules=0,
        pair_block=32, q_block=128, loss_chunk=16),
    # the benchmark's `ling-3-flash-l6e128`: every width as published, the
    # published layers 1-6 (one leading dense layer and a whole period: five
    # linear layers to one latent layer), a chip's share of 4 that divide
    # every expert layer (experts 0-127: groups 0 and 1 of 8)
    "ling_3_flash": LMArch(
        hidden=2560, heads=32, q_lora_rank=0, intermediate=6144,
        n_experts=512, rope_theta=6_000_000.0, expert_layers=5, n_group=8,
        topk_group=4, layer_pattern=(1, 1, 1, 1, 0, 1), kda_head_dim=128,
        attn_gate=True, mtp_modules=0, pair_front=32768),
    "ling_3_flash_tiny": LMArch(
        hidden=64, heads=4, q_lora_rank=0, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        intermediate=128, moe_intermediate=32, n_experts=16,
        experts_per_tok=2, rope_theta=6_000_000.0, expert_layers=3,
        held_experts=4, n_group=4, topk_group=2, layer_pattern=(1, 1, 0, 1),
        kda_head_dim=16, kda_chunk=16, kda_block=4, attn_gate=True,
        mtp_modules=0, pair_block=32, pair_front=64, q_block=128,
        loss_chunk=16),
}

LATENT, LINEAR = 0, 1   # `layer_pattern`'s kinds where `kda_head_dim` is set


def is_token_model(module) -> bool:
    """A model whose samples are token sequences labelled at every position
    by the sequence itself (and which has a frozen base)."""
    return bool(getattr(module, "token_model", False))


def rms_norm(x, gain, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _mm(x, w):
    """bfloat16 operands, float32 accumulation."""
    return jnp.dot(x.astype(BF16), w, preferred_element_type=F32)


def yarn_ramp(d: int, theta: float, scaling: tuple):
    """YaRN's share of interpolation a frequency, f32[d / 2] (NumPy): 0
    where a pair turns more than `beta_fast` times over the original
    positions (kept), 1 where fewer than `beta_slow` times (divided by the
    factor), linear between the two pair indices."""
    import numpy as np

    _, positions, fast, slow, _ = scaling
    cd = lambda r: d * math.log(positions / (2 * math.pi * r)) / (  # noqa: E731
        2 * math.log(theta))
    low = max(math.floor(cd(fast)), 0)
    high = min(math.ceil(cd(slow)), d - 1)
    return np.clip((np.arange(d // 2, dtype=np.float32) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)


def rope(x, theta: float, scaling: tuple | None = None,
         interleaved: bool = True):
    """Rotary embedding. x: [B, S, H, d]; the position is the index along S.
    Pair i is (x_2i, x_2i+1) (`rope_interleave`) or, half-split, (x_i,
    x_i+d/2); it turns by position * theta^(-2i/d), with `scaling` at
    YaRN's frequencies (`yarn_ramp`; cos and sin are not scaled)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    if scaling is not None:
        ramp = yarn_ramp(d, theta, scaling)
        inv = inv / scaling[0] * ramp + inv * (1.0 - ramp)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    if not interleaved:
        a, b = x.astype(F32)[..., :d // 2], x.astype(F32)[..., d // 2:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)
    x = x.astype(F32).reshape(*x.shape[:-1], d // 2, 2)
    a, b = x[..., 0], x[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(
        *x.shape[:-2], d)


def softmax_scale(arch: LMArch) -> float:
    """1 / sqrt(d_qk), times YaRN's mscale squared where RoPE is scaled
    (m = 0.1 * mscale_all_dim * ln(factor) + 1)."""
    scale = 1.0 / math.sqrt(arch.qk_nope_head_dim + arch.qk_rope_head_dim)
    if arch.rope_scaling is not None:
        factor, _, _, _, all_dim = arch.rope_scaling
        scale *= (0.1 * all_dim * math.log(factor) + 1.0) ** 2
    return scale


def _interpret() -> bool:
    """Pallas runs interpreted wherever the backend is no TPU (the tests)."""
    return jax.default_backend() != "tpu"
