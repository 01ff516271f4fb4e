"""Expert language models as a frozen base and a trained subset.

One module class, `FrozenBaseLM`, over an `LMArch`; four published models,
two of them DeepSeek-V3-shaped (latent attention, one shared expert, a
prediction module), one with grouped-query attention of two kinds and one
whose layers carry a state along the sequence:

  * **JoyAI-LLM-Flash** (`PRESETS["joyai_llm_flash"]`; huggingface.co/
    jdopensource/JoyAI-LLM-Flash, config.json; every key a DeepSeek-V3 key,
    so the equations are those of arXiv:2412.19437 section 2): multi-head
    latent attention, one leading dense layer, expert layers with a sigmoid
    router (256 wide, 8 a token, one shared expert, `noaux_tc` selection
    over `s + bias`, weights normalised over the selected and scaled by 2.5)
    and one multi-token-prediction module.
  * **DeepSeek-V3.2-Exp** (`PRESETS["deepseek_v32"]`; huggingface.co/
    deepseek-ai/DeepSeek-V3.2-Exp, `model_type` `deepseek_v32`): the same
    layer at hidden 7168 and 128 heads, with what the first model lacks:
    **learned sparse attention** (a lightning indexer a layer, `select_keys`:
    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]) over 64 indexer heads,
    a query keeps its 2,048 keys of largest I, and the main attention's
    softmax runs over those alone, `selected_attention`), **group-limited
    routing** (`route`: 8 groups, the experts of the 4 best), **YaRN**
    frequencies and softmax scale (`rope`, `softmax_scale`), and an expert
    layer that holds 8 of 256 experts and costs what its held pairs cost
    (`held_experts` in blocks). Its indexer is frozen with the base and its
    products are bfloat16 (published: FP8 after a Hadamard turn, trained by
    an alignment loss): `benchmarks/configs/deepseek-v32-exp-l5e8.json`
    lists each departure.
  * **MiMo-V2-Flash** (`PRESETS["mimo_v2_flash"]`; huggingface.co/XiaomiMiMo/
    MiMo-V2-Flash, `model_type` `mimo_v2_flash`): **grouped-query attention
    with no latents** (`grouped_attention`: 64 query heads of 192 over 4 or
    8 KV heads, values of 128 scaled by 0.707; K and V stay as many heads
    wide as they are made), **layers of two kinds in one model**
    (`layer_pattern`: five window layers of 128 keys to one global layer,
    each kind with its own KV heads, RoPE base and leaf shapes), **a
    trained sink a head** in the window layers' softmax (it takes mass and
    gives no value), **RoPE over the first 64 dims of a head**, **no shared
    expert and no prediction module** (`shared_experts`, `mtp_modules` 0:
    the loss is the next-token cross-entropy alone), 16 of 256 experts held:
    the expert layer is an eighth of its round and more as the routers
    train, so its held pairs go through one grouped product of a fixed
    number of rows with gathers around it (`_held_front`: the same work
    whatever the routers do) and only what is behind `pair_front` in blocks.
    `benchmarks/configs/mimo-v2-flash-l7e16.json` lists what is assumed.
  * **Ling-3.0-flash** (`PRESETS["ling_3_flash"]`; huggingface.co/inclusionAI/
    Ling-3.0-flash, `model_type` `bailing_hybrid`): **five delta-rule
    linear-attention layers to one latent layer** (`layer_pattern`: `LINEAR`
    where (i + 1) % 6 != 0). A linear layer is Kimi Delta Attention
    (arXiv:2510.26692; `kda_layer`): q, k, v = SiLU(conv4(x W)) (a depthwise
    causal convolution over the 4 last positions), q and k L2-normalised a
    head, a decay a channel g_t = -5 sigmoid(exp(A_log) (x_t W_f + dt_bias))
    and beta_t = sigmoid(x_t W_beta) a head, then a head's 128 x 128 float32
    state: S' = Diag(exp g_t) S_{t-1}; S_t = S' + beta_t k_t (v_t - S'^T
    k_t)^T; o_t = S_t^T q_t; y = (RMSNorm_head(o_t) * sigmoid(x_t W_g)) W_o.
    It runs in chunks of 64 positions whose triangular system is solved in
    sub-blocks of 16, the state carried from chunk to chunk by a `lax.scan`
    (`kda_recurrence`: XLA operations, its gradient by differentiation of
    the checkpointed chunk body; no kernel for it here). **The layer's
    front is one pass over the projections' output each way** (PR 43): the
    short convolutions, SiLU, the heads' norms, the decay gate and the move
    into chunks are one Pallas kernel over `x W` f32[S, 5 H d] that writes
    q, k, v and g as the recurrence reads them ([chunks, B, H, 64, d]), and
    one over their cotangents that writes d(x W), under a `custom_vjp`
    (`_kda_front`); a model whose heads are no 128 lanes wide (the tests'
    preset) runs the same algorithm as XLA operations (`_kda_front_xla`),
    chosen by shape (`kda_front_kernel`), not by a switch. The latent layer
    is DeepSeek-V3's **without a query low-rank and with a sigmoid gate a
    head on attention's output** (`_attend` with `q_lora_rank` 0 and
    `attn_gate`). 512 routed experts in 8 groups of which 4 stay, 128 held,
    one shared expert, no prediction module (its published loss weight is
    0). **What its cold run forced** (`hybrid_layers`): the base stacked by
    kind of layer and the expert layers steps of one `lax.scan`, so that an
    expert layer and each kind of attention compile once whatever the
    depth; and in a step of that scan, where `held_experts` is told its
    layer's place among the stacked experts (`at`), an expert layer with no
    sort in it (`_held_counted`).
    `benchmarks/configs/ling-3-flash-l6e128.json` lists what is assumed.

Which attention a layer runs follows from its `arch` (`kv_heads` empty:
latent, or by `layer_pattern` linear where `kda_head_dim` is set). With
`index_topk` 0, `n_group` 1 and no `rope_scaling` the traced program is the
first model's, operation for operation; with `kv_heads` empty, one shared
expert and one prediction module it is the first two's.

What a federation can afford of such a model (PERF.md, PR 26-27: a trained
parameter costs a client 16 bytes for its step and 24 for its ciphertext, a
frozen one 2) decides the layout. The parameters are two pytrees:

  * the **base** (`init_base`): every matrix, bfloat16, made on the device
    leaf by leaf from the seed. It stays on the client: it is an argument of
    the round program (never a constant of it), is in no `ClientState`, no
    optimizer, no `PackSpec` and no ciphertext, and no gradient with respect
    to it is ever formed.
  * the **trained subset** (`init_trained`): every router matrix, every
    RMSNorm gain, every sink and every linear layer's `A_log` and `dt_bias`,
    float32. It is what `create_model` returns
    as `params`: what is stepped, encrypted, summed and decrypted.

The expert layer is told which experts it holds (`held_start`,
`held_experts`): selection and normalisation run over all `n_experts`, the
held experts' terms are computed by a grouped matrix product
(`grouped_matmul`, every (token, held expert) pair whatever the
imbalance: no capacity, nothing dropped) and summed with the shared expert;
what absent experts would add is left out. Compute is bfloat16 with float32
accumulation; the residual stream, the norms, the router, the selection and
the softmax are float32. Attention is one fused Pallas kernel a layer
(splash attention, forward and gradient; a linear layer's attention is its
recurrence, XLA's, behind the front's kernel), causal (`causal_attention`), over
the indexer's selection (`selected_attention`) or grouped, causal or over a
window (`grouped_heads`): scores and
probabilities live a block at a time in the chip's fast memory and never in
HBM, blocks no query of which attends any key are skipped, and memory is
linear in the sequence. Every block is made again for the gradient (a
`jax.checkpoint` a layer); what is kept besides its input is `_kept`'s.

A module here is a frozen dataclass, hashable like a flax module, with the
same `apply({"params": ...}, x)`; `bind(base)` gives the module that the
client code sees inside a round program, its base the program's argument.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from hefl_tpu.obs import metrics as obs_metrics
from hefl_tpu.obs import scopes as obs_scopes

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


# --------------------------------------------------------------------------
# layers
# --------------------------------------------------------------------------


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


ATTN_SAVED = "mla_saved"   # what a layer's checkpoint keeps of attention


@functools.lru_cache(maxsize=None)
def _attention_kernel(seq: int, heads: int, block: int, interpret: bool):
    """The fused causal attention over `seq` positions (a multiple of
    `block`) of `heads` heads: splash attention of
    `jax.experimental.pallas.ops.tpu`, q and kv blocks of `block` (scores
    made 512 keys at a time), its gradient one kernel more (`dkv`, which
    also forms `dq`, a key block's part at a time). Its output and
    log-sum-exp carry the name `ATTN_SAVED`, so a `jax.checkpoint` whose
    policy saves that name does not run the forward kernel again. The mask
    is processed in NumPy here on the host, once a shape: every layer and
    every trace reuses the object, which holds NumPy arrays only (constants
    of whatever program calls it)."""
    import importlib

    import numpy as np

    splash = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.splash_attention")
    step = min(block, 512)
    sizes = splash.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=step,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=step,
        use_fused_bwd_kernel=True)
    mask = splash.MultiHeadMask([splash.CausalMask((seq, seq))] * heads)
    with jax.ensure_compile_time_eval():
        kernel = splash.make_splash_mha(
            mask, block_sizes=sizes, head_shards=1, q_seq_shards=1,
            residual_checkpoint_name=ATTN_SAVED, interpret=interpret)
    return jax.tree_util.tree_map(np.asarray, kernel)


def causal_attention(q, k, v, q_block: int, scale: float | None = None):
    """softmax(q k^T / sqrt(d)) v, causal (`scale` in place of 1 / sqrt(d)).
    q, k: [B, S, H, dq]; v: [B, S, H, dv] -> f32[B, S, H, dv]. One fused
    Pallas kernel (`_attention_kernel`) and one more for its gradient:
    bfloat16 operands, float32 scores, a
    float32 running maximum, sum and accumulator over the keys, the
    probabilities narrowed to bfloat16 for the product with v, the division
    at the end, the output narrowed to bfloat16 (as the product that takes
    it would). No score or probability block reaches HBM, forward or
    backward; the blocks above the diagonal are skipped; every key up to the
    query's own position counts. q is scaled in float32 before it is
    narrowed (the kernel does not scale). The kernel wants blocks that are
    multiples of 128: `q_block` is rounded up to one, and a sequence that is
    no multiple of the block is padded at its end. Padded keys lie behind
    every real query, so the causal mask removes them; padded query rows are
    cut off."""
    _, s, h, dq = q.shape
    up = lambda n, m: -(-n // m) * m  # noqa: E731
    blk = min(up(q_block, 128), up(s, 128))
    pad = up(s, blk) - s
    heads_first = lambda x: jnp.pad(  # noqa: E731
        x.astype(BF16), ((0, 0), (0, pad), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    q = q.astype(F32) * (1.0 / math.sqrt(dq) if scale is None else scale)
    kernel = _attention_kernel(s + pad, h, blk, _interpret())
    o = jax.vmap(kernel)(heads_first(q), heads_first(k), heads_first(v))
    return o.transpose(0, 2, 1, 3)[:, :s].astype(F32)


@functools.lru_cache(maxsize=None)
def _grouped_kernel(seq: int, heads: int, window: int, block: int,
                    interpret: bool):
    """The fused attention of `heads` query heads over ONE shared head of
    keys and values (splash attention's multi-query form; `grouped_heads`
    runs it a KV head at a time), over `seq` positions (a multiple of
    `block`) -> (the kernel, the (query, key) pairs inside the blocks it
    computes over the pairs its mask allows). `window` 0: causal over every
    key, blocks and fused gradient as `_attention_kernel`'s. Otherwise a
    query attends the `window` last keys, its own among them (`LocalMask`):
    q and kv blocks of `block` both ways, so that a window of 128 pays for
    256 keys and not for 1,024, and the gradient in two kernels (`dq`,
    `dkv`), each over the blocks the window touches alone (the fused one
    would keep a float32 dq a key block a head: 64 of them). Both take
    `sinks`, a float32 scalar a head inside the softmax's denominator, and
    return its gradient. Nothing of it is kept for the gradient (`_kept`)."""
    import importlib

    import numpy as np

    splash = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.splash_attention")
    if window:
        sizes = splash.BlockSizes(
            block_q=block, block_kv=block, block_kv_compute=block,
            block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
            block_q_dq=block, block_kv_dq=block)
        one = splash.LocalMask((seq, seq), (window - 1, 0), 0)
    else:
        step = min(block, 512)
        sizes = splash.BlockSizes(
            block_q=block, block_kv=block, block_kv_compute=step,
            block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=step,
            use_fused_bwd_kernel=True)
        one = splash.CausalMask((seq, seq))
    with jax.ensure_compile_time_eval():
        kernel = splash.make_splash_mqa(
            splash.MultiHeadMask([one] * heads), block_sizes=sizes,
            head_shards=1, q_seq_shards=1, interpret=interpret)
    kernel = jax.tree_util.tree_map(np.asarray, kernel)
    # the block table: 0 skipped, 1 partly masked, 2 whole (one head's: the
    # heads share a mask)
    table = np.asarray(kernel.fwd_mask_info.block_mask)
    computed = np.count_nonzero(table) / table.shape[0] * block * block
    full = min(seq, window) if window else seq
    allowed = full * (full + 1) // 2 + (seq - full) * window
    return kernel, float(computed / allowed)


def grouped_heads(q, k, v, sinks, window: int, q_block: int, scale: float):
    """Grouped-query attention: softmax(q k^T * scale) v, query head j
    reading KV head j // (H / G), causal (`window` 0) or over the `window`
    last keys. q: [B, S, H, dq]; k: [B, S, G, dq]; v: [B, S, G, dv]; `sinks`
    f32[H] or None: with them p[t, u] = exp(s[t, u]) / (exp(sink) + sum_u'
    exp(s[t, u'])) -> f32[B, S, H, dv]. One KV head at a time (a loop of G
    steps) through `_grouped_kernel` with that head's H / G query heads, so K
    and V are never repeated and stay G heads wide in HBM, and the global
    kind's fused gradient keeps one group's float32 dq at a time.
    Arithmetic, padding and what reaches HBM are `causal_attention`'s: no
    score block, forward or backward, and blocks no query of which attends
    any key are skipped (all but two a query block, under a window)."""
    b, s, h, dq = q.shape
    g, dv = k.shape[2], v.shape[-1]
    up = lambda n, m: -(-n // m) * m  # noqa: E731
    blk = min(up(q_block, 128), up(s, 128))
    if window:
        blk = min(blk, up(window, 128))
    pad = up(s, blk) - s
    kernel, ratio = _grouped_kernel(s + pad, h // g, window, blk, _interpret())
    if window:
        obs_metrics.gauge("swa.block_pairs_over_window_pairs").set(ratio)
    heads_first = lambda x: jnp.pad(  # noqa: E731
        x.astype(BF16), ((0, 0), (0, pad), (0, 0), (0, 0)))
    qs = heads_first(q.astype(F32) * scale).reshape(
        b, s + pad, g, h // g, dq).transpose(2, 0, 3, 1, 4)  # [G, B, H/G, S, dq]
    ks, vs = (heads_first(x).transpose(2, 0, 1, 3) for x in (k, v))

    def one(group):                   # a KV head and its query heads
        qg, kg, vg, sink = group
        call = kernel if sink is None else functools.partial(kernel, sinks=sink)
        with (jax.named_scope(obs_scopes.SWA_ATTEND) if window
              else contextlib.nullcontext()):
            return jax.vmap(call)(qg, kg, vg)

    o = jax.lax.map(one, (qs, ks, vs, None if sinks is None
                          else sinks.astype(F32).reshape(g, h // g)))
    return o.transpose(1, 3, 0, 2, 4).reshape(b, s + pad, h, dv)[:, :s].astype(F32)


def grouped_attention(arch: LMArch, kind: int, w, g, x):
    """Grouped-query attention with no latents, of layer kind `kind` (0
    global, 1 window). w: the block's frozen matrices (`q`, `k`, `v`, `o`),
    g: its trained leaves (`sink` where the kind has one), x: [B, S, D]
    (already normed). The first `qk_rope_head_dim` dims of every head of q
    and k turn, half-split, at the kind's base; v is scaled."""
    with jax.named_scope(obs_scopes.GQA):
        b, s, _ = x.shape
        h, dr, dv = arch.heads, arch.qk_rope_head_dim, arch.v_head_dim
        dq, kv = arch.qk_nope_head_dim + dr, arch.kv_heads[kind]
        turn = lambda t: jnp.concatenate(  # noqa: E731
            [rope(t[..., :dr], arch.rope_thetas[kind], None, False),
             t[..., dr:]], -1)
        q = turn(_mm(x, w["q"]).reshape(b, s, h, dq))
        k = turn(_mm(x, w["k"]).reshape(b, s, kv, dq))
        v = arch.value_scale * _mm(x, w["v"]).reshape(b, s, kv, dv)
        o = grouped_heads(q, k, v, g.get("sink"), arch.window if kind else 0,
                          arch.q_block, softmax_scale(arch))
        return _mm(o.reshape(b, s, h * dv), w["o"])


DSA_PICKED = "dsa_picked"   # an indexer's selection, packed (`pack_selection`)


def _kept_names(arch: LMArch) -> tuple:
    """The checkpoint names a layer's `jax.checkpoint` keeps for the gradient
    besides the layer's input (`_kept`), from what the architecture has."""
    if arch.kv_heads or arch.kda_head_dim:
        return ()
    return (DSA_PICKED, ATTN_SAVED) if arch.index_topk else (ATTN_SAVED,)


def _kept(arch: LMArch):
    """What a layer's checkpoint keeps for the gradient besides the layer's
    input: attention's output and log-sum-exp (`ATTN_SAVED`), so the forward
    kernel does not run again. Where an indexer picks the keys, its
    selection too (`DSA_PICKED`), packed: `select_keys` has no gradient and
    its inputs are detached, so the gradient's copy of the layer unpacks the
    kept bits and runs neither the indexer's scores nor the 32 counting
    passes. At 8,192 positions and 128 heads that is, a layer, 8.4 MB of
    selection (uint32[1, 256, 8192]) and 0.272 GB of attention (the kernel's
    output bf16[128, 8192, 128] and log-sum-exp f32[128, 8192], as the
    groups' calls write them and as the gradient kernel reads them: no other
    form of either is kept), 1.68 GB over the six layers. Sized against the
    15.75 GiB = 16.91e9 bytes a v5e lets a program use, by the described
    compile of `loss`'s gradient at the published widths (PERF.md, PR 42):
    temporaries 5.30e9 bytes with nothing kept, 5.83e9 with the selection,
    8.51e9 with both by `memory_analysis()`, which holds a kept array twice
    where a `lax.map` stacked it; the round program 14.9e9 in all by the
    compiler's own total (13.5e9 with nothing kept), and it runs.
    Nothing where attention is grouped (64 heads of 8,192
    positions in each of seven layers held 1.7 GB of the step's temporaries
    by the compiler's count, and put the round over 14 GB). Nothing either
    where a model has linear layers: its layers are steps of one scan, and a
    name kept in a step is kept for every step. Of a linear layer's
    recurrence the state at each chunk's edge is kept while that layer's
    gradient is made, by the scan over its chunks itself (128 x [32, 128,
    128] float32, 0.27 GB, for the one layer), and nothing from layer to
    layer: with the base at 8.6 GB the step has 6.4 GB."""
    names = _kept_names(arch)
    if not names:
        return jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint_policies.save_only_these_names(*names)


HEADS_A_CALL = 16   # the fused gradient keeps a float32 dq a key block a head


def selected_attention(heads_of, xs, picked, q_block: int):
    """softmax(q k^T) v over the keys `picked` bool[B, S, S] names for each
    query (every head the same ones), `HEADS_A_CALL` heads at a time:
    `heads_of(x)`, for x a slice of `xs` along its leading (group) axis,
    gives that group's (q, k, v), [B, S, heads, d] each, q already scaled;
    -> bf16[B, S, all heads, dv]. The kernels and the arithmetic are
    `causal_attention`'s (splash attention, forward and gradient), given the
    mask as an array: it is laid out in blocks once (and once transposed for
    the gradient), every group runs against that one layout, and a block no
    query of which picks any key is skipped (above the diagonal, all of
    them). No score block reaches HBM, and no array of all heads' queries,
    keys or values exists: a group's are made from `xs` when it runs and
    made again for its gradient. What the gradient is given instead of a
    second forward kernel is each group call's output bf16[heads, 1, S, dv]
    and log-sum-exp f32[heads, 1, S], named `ATTN_SAVED` by the kernel and
    stacked over the groups by the `lax.map` as the calls write them
    (0.268 GB + 4 MB a layer at 128 heads of 8,192 positions): kept through
    the group's own checkpoint here and, where the layer's says so
    (`_kept`), from the layer's forward to its gradient, which then runs
    the `dkv` kernel alone. Query blocks are half the key blocks (a
    mask block lies in the chip's fast memory as 32-bit words); a padded
    query picks key 0, a padded key is picked by none. No gradient reaches
    `picked`."""
    import importlib

    splash = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.splash_attention")
    b, s, _ = picked.shape
    up = lambda n, m: -(-n // m) * m  # noqa: E731
    bq = min(up(q_block, 128), up(s, 128))
    pad = up(s, bq) - s
    bk = 2 * bq if (s + pad) % (2 * bq) == 0 else bq
    step = min(bk, 512)
    sizes = splash.BlockSizes(
        block_q=bq, block_kv=bk, block_kv_compute=step, block_q_dkv=bq,
        block_kv_dkv=bk, block_kv_dkv_compute=step, use_fused_bwd_kernel=True)
    picked = jnp.pad(picked, ((0, 0), (0, pad), (0, pad)))
    picked = picked.at[:, s:, 0].set(True)
    kernels = [splash.make_splash_mha(
        picked[i][None], block_sizes=sizes, head_shards=1, q_seq_shards=1,
        residual_checkpoint_name=ATTN_SAVED, interpret=_interpret())
        for i in range(b)]
    one_head = lambda x: jnp.pad(  # noqa: E731
        x.astype(BF16), ((0, pad), (0, 0), (0, 0))).transpose(1, 0, 2)[:, None]

    @functools.partial(
        jax.checkpoint,
        policy=jax.checkpoint_policies.save_only_these_names(ATTN_SAVED))
    def group(x):
        q, k, v = heads_of(x)
        o = jnp.stack([jax.vmap(kernels[i])(
            one_head(q[i]), one_head(k[i]), one_head(v[i]))
            for i in range(b)])                     # [B, heads, 1, S, dv]
        return o[:, :, 0, :s].transpose(0, 2, 1, 3)

    o = jax.lax.map(group, xs)                      # [groups, B, S, heads, dv]
    g, _, _, n, dv = o.shape
    return o.transpose(1, 2, 0, 3, 4).reshape(b, s, g * n, dv)


def pack_selection(picked):
    """bool[..., Q, K] -> uint32[..., W, K], W = ceil(Q / 32): bit j of word
    (w, k) is query j * W + w's pick of key k (a query behind Q reads 0).
    The words run along the queries, whole rows of keys at a time: both
    directions are shifts of [W, K] planes that lie one behind the other in
    memory, and the keys stay in the lanes as they were (packed along the
    keys, 32 to a lane, the unpack fused into what reads the selection and
    cost 21 ms a layer pass on the chip: PERF.md, PR 42)."""
    q, k = picked.shape[-2:]
    w = -(-q // 32)
    planes = jnp.pad(picked, ((0, 0),) * (picked.ndim - 2)
                     + ((0, 32 * w - q), (0, 0))).reshape(
                         *picked.shape[:-2], 32, w, k).astype(jnp.uint32)
    return jnp.sum(planes << jnp.arange(32, dtype=jnp.uint32)[:, None, None],
                   -3, dtype=jnp.uint32)


def unpack_selection(bits, q: int):
    """`pack_selection`'s inverse: uint32[..., W, K] -> bool[..., q, K], as
    an array of its own (what reads a selection reads it as `select_keys`
    wrote it, and does not make it again from the words)."""
    planes = (bits[..., None, :, :]
              >> jnp.arange(32, dtype=jnp.uint32)[:, None, None]) & 1
    return jax.lax.optimization_barrier(planes.reshape(
        *bits.shape[:-2], -1, bits.shape[-1])[..., :q, :].astype(bool))


def kth_largest_mask(scores, k: int):
    """bool[r, n]: the `k` entries a row of f32[r, n] that `jax.lax.top_k`
    returns (the largest, the lower index first among equals), found without
    a sort: the k-th largest value a bit at a time over an order-preserving
    integer image of the floats (32 counting passes), then the first of its
    equals by a running count."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    key = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))

    def grow(i, prefix):
        cand = prefix | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(key >= cand[:, None], -1) >= k
        return jnp.where(enough, cand, prefix)

    kth = jax.lax.fori_loop(0, 32, grow, jnp.zeros(key.shape[0], jnp.uint32))
    above, equal = key > kth[:, None], key == kth[:, None]
    need = k - jnp.sum(above, -1)
    return above | (equal & (jnp.cumsum(equal, -1) <= need[:, None]))


def layer_norm(x, gain, bias, eps: float):
    x = x.astype(F32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * gain + bias


def index_scores(arch: LMArch, w, x, c_q):
    """The indexer's inputs to its score: (q bf16[B, S, Hi, di], k bf16[B, S,
    di], weights f32[B, S, Hi]). q from the main queries' normed latent,
    k = LayerNorm(x W_k) one shared head; the first `qk_rope_head_dim`
    dims of both turned half-split at the main frequencies."""
    b, s, _ = x.shape
    hi, di, dr = arch.index_heads, arch.index_head_dim, arch.qk_rope_head_dim
    turn = lambda t: jnp.concatenate(  # noqa: E731
        [rope(t[..., :dr], arch.rope_theta, arch.rope_scaling, False),
         t[..., dr:]], -1)
    q = turn(_mm(c_q, w["q"]).reshape(b, s, hi, di))
    k = turn(layer_norm(_mm(x, w["k"]), w["k_gain"], w["k_bias"],
                        arch.eps)[:, :, None, :])[:, :, 0]
    weights = _mm(x, w["w"]) * (hi ** -0.5 * di ** -0.5)
    return q.astype(BF16), k.astype(BF16), weights


def select_keys(arch: LMArch, w, x, c_q):
    """The lightning indexer and its selection: bool[B, S, S], true where
    query t attends key s. I[t, s] = sum_j w[t, j] relu(q[t, j] . k[s]) over
    the indexer's heads, for s <= t; a query keeps its `index_topk` keys of
    largest I (all of them while it has no more), ties by `top_k`'s rule.
    Scores and selection are made `index_block` queries at a time: a slice's
    [queries, heads, S] products are summed over the heads where they are
    made, and neither they nor I [S, S] exist whole anywhere. x and c_q are
    detached, as published: no gradient reaches the indexer or passes
    through the selection."""
    with jax.named_scope(obs_scopes.DSA_INDEX):
        q, k, weights = index_scores(
            arch, w, jax.lax.stop_gradient(x), jax.lax.stop_gradient(c_q))
        b, s, hi, di = q.shape
        rows = min(arch.index_block, s)
        pad = (-s) % rows
        by_rows = lambda t: jnp.pad(  # noqa: E731
            t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)).reshape(
                b, (s + pad) // rows, rows, *t.shape[2:])
        first = jnp.arange(0, s + pad, rows)

        def one(keys):                    # a sequence's keys [S, di]
            def picked(part):             # `rows` queries from position lo
                lo, qs, ws = part
                sc = jnp.einsum("qjd,sd->qjs", qs, keys,
                                preferred_element_type=F32)
                score = jnp.sum(jax.nn.relu(sc) * ws[:, :, None], axis=1)
                causal = ((lo + jnp.arange(rows))[:, None]
                          >= jnp.arange(s)[None, :])
                return causal & kth_largest_mask(
                    jnp.where(causal, score, -jnp.inf), arch.index_topk)
            return picked

        out = jnp.stack([
            jax.lax.map(one(k[i]), (first, by_rows(q)[i], by_rows(weights)[i]))
            for i in range(b)])
        return out.reshape(b, s + pad, s)[:, :s]


def latent_attention(arch: LMArch, w, g, x):
    """Multi-head latent attention. w: the block's frozen matrices, g: its
    trained gains (`q_norm`, `kv_norm`), x: [B, S, D] (already normed)."""
    return _attend(arch, w, g, x)[0]


def _attend(arch: LMArch, w, g, x):
    """-> (`latent_attention`'s output, the (query, key) pairs its indexer
    picked, int32, or None for a model without one)."""
    with jax.named_scope(obs_scopes.MLA):
        b, s, _ = x.shape
        h, dn, dr, dv = (arch.heads, arch.qk_nope_head_dim,
                         arch.qk_rope_head_dim, arch.v_head_dim)
        if arch.q_lora_rank:
            c_q = rms_norm(_mm(x, w["q_a"]), g["q_norm"], arch.eps)
            if arch.index_topk:
                return _attend_selected(arch, w, g, x, c_q)
            q = _mm(c_q, w["q_b"])
        else:                             # no query low-rank, no `q_norm`
            q = _mm(x, w["q"])
        q = q.reshape(b, s, h, dn + dr)
        kv_a = _mm(x, w["kv_a"])
        c_kv, k_r = kv_a[..., :arch.kv_lora_rank], kv_a[..., arch.kv_lora_rank:]
        kv = _mm(rms_norm(c_kv, g["kv_norm"], arch.eps), w["kv_b"]).reshape(
            b, s, h, dn + dv)
        q_r = rope(q[..., dn:], arch.rope_theta, arch.rope_scaling)
        k_r = rope(k_r[:, :, None, :], arch.rope_theta,
                   arch.rope_scaling)                          # one shared head
        q = jnp.concatenate([q[..., :dn], q_r], -1)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_r, (b, s, h, dr))], -1)
        o = causal_attention(q, k, kv[..., dn:], arch.q_block,
                             softmax_scale(arch))
        if arch.attn_gate:                # one scalar a head, from x
            o = o * jax.nn.sigmoid(_mm(x, w["gate"]))[..., None]
        return _mm(o.reshape(b, s, h * dv), w["o"]), None


def _attend_selected(arch: LMArch, w, g, x, c_q):
    """`_attend` where an indexer picks each query's keys: the same
    projections, made `HEADS_A_CALL` heads at a time inside
    `selected_attention` (at 128 heads of 8,192 positions all heads' q, k
    and v are 1.1 GB in bfloat16, twice that in float32)."""
    b, s, _ = x.shape
    h, dn, dr, dv = (arch.heads, arch.qk_nope_head_dim,
                     arch.qk_rope_head_dim, arch.v_head_dim)
    picked = select_keys(arch, w["index"], x, c_q)
    with jax.named_scope(obs_scopes.DSA_INDEX):   # kept packed (`_kept`)
        picked = unpack_selection(
            checkpoint_name(pack_selection(picked), DSA_PICKED), s)
    kv_a = _mm(x, w["kv_a"])
    c_kv = rms_norm(kv_a[..., :arch.kv_lora_rank], g["kv_norm"], arch.eps)
    k_r = rope(kv_a[..., arch.kv_lora_rank:][:, :, None, :], arch.rope_theta,
               arch.rope_scaling)                              # one shared head
    grp = next(n for n in range(min(h, HEADS_A_CALL), 0, -1) if h % n == 0)

    def heads(ws):                    # the heads these columns make
        q = _mm(c_q, ws[0]).reshape(b, s, grp, dn + dr)
        kv = _mm(c_kv, ws[1]).reshape(b, s, grp, dn + dv)
        q_r = rope(q[..., dn:], arch.rope_theta, arch.rope_scaling)
        q = jnp.concatenate([q[..., :dn], q_r], -1) * softmax_scale(arch)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_r, (b, s, grp, dr))], -1)
        return q, k, kv[..., dn:]

    by_group = lambda m: m.reshape(  # noqa: E731
        m.shape[0], h // grp, -1).swapaxes(0, 1)
    with jax.named_scope(obs_scopes.DSA_ATTEND):
        o = selected_attention(heads, (by_group(w["q_b"]), by_group(w["kv_b"])),
                               picked, arch.q_block)
    return (_mm(o.reshape(b, s, h * dv), w["o"]),
            jnp.sum(picked, dtype=jnp.int32))


# --------------------------------------------------------------------------
# delta-rule linear attention (Kimi Delta Attention, arXiv:2510.26692)
# --------------------------------------------------------------------------


def short_conv(z, w):
    """Depthwise causal convolution over the last K positions: y[t, c] =
    sum_j w[c, j] z[t - (K - 1) + j, c] (w[:, K - 1] weighs position t
    itself; positions before the sequence read 0). z: f32[B, S, C], w:
    [C, K] -> f32[B, S, C], as K shifted multiply-adds."""
    k = w.shape[1]
    w = w.astype(F32)
    padded = jnp.pad(z, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, j:j + z.shape[1]] * w[:, j] for j in range(k))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _unit_lower_inverse(n, block: int):
    """(I + n)^-1 for n f32[..., C, C] strictly lower triangular, solved in
    sub-blocks of `block`, every step a [C, C] product in float32 at the
    highest precision: with n = d + l, d its diagonal sub-blocks and l what
    is below them, (I + d)^-1 is the product (I - d)(I + d^2)(I + d^4)... of
    the nilpotent d (d^block = 0: exact, and block-diagonal like d); then
    I + n = (I + d)(I + m), m = (I + d)^-1 l, and m is nilpotent by blocks
    (m^(C / block) = 0), so (I + m)^-1 is the same product over m: the
    substitution down the block rows, as products. Its gradient is the
    inverse's own: -T^T dT T^T."""
    c = n.shape[-1]
    mm = functools.partial(jnp.matmul, precision=HIGHEST)
    eye = jnp.eye(c, dtype=F32)

    def nilpotent_inverse(x, order: int):     # (I + x)^-1, x^order = 0
        inv, power = eye - x, x
        for _ in range(max((order - 1).bit_length() - 1, 0)):
            power = mm(power, power)
            inv = mm(inv, eye + power)
        return inv

    at = jnp.arange(c) // block
    d = jnp.where(at[:, None] == at[None, :], n, 0.0)
    d_inv = nilpotent_inverse(d, block)
    return mm(nilpotent_inverse(mm(d_inv, n - d), c // block), d_inv)


def _unit_lower_inverse_fwd(n, block):
    inv = _unit_lower_inverse(n, block)
    return inv, inv


def _unit_lower_inverse_bwd(block, inv, d_inv):
    mm = functools.partial(jnp.matmul, precision=HIGHEST)
    t = jnp.swapaxes(inv, -1, -2)
    return (-mm(t, mm(d_inv, t)),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


KDA_GROUP = 16   # chunks whose inside is made together (and again for the gradient)


def kda_recurrence(q, k, v, g, beta, chunk: int = 64, block: int = 16,
                   bound: float = 5.0, carry=True, operands=BF16,
                   chunked: bool = False):
    """The gated delta rule, a head at a time: S' = Diag(exp(g_t)) S_{t-1};
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T; o_t = S_t^T q_t, S_0 = 0.
    q, k, g: f32[B, S, H, dk] (0 >= g >= -`bound`), v: [B, S, H, dv], beta:
    [B, S, H] -> o f32[B, S, H, dv]. With `chunked` the operands are by
    chunk already, q, k, v, g [n, B, H, chunk, d] and beta [n, B, H, chunk]
    (as `_kda_front`'s kernel writes them: rows behind the sequence's end
    hold 0) and o has all their n * chunk positions; the other entry moves
    its operands into that form first (`by_chunk`, transposes) and is the
    tests' and the XLA front's.

    The chunked form. Inside a chunk of `chunk` positions, with G the
    running sum of g from the chunk's first position and S the state the
    chunk starts from, the deltas u_t = v_t - S'^T k_t solve the unit lower
    triangular system (I + A Diag(beta)) U = V - (K exp G) S, A[t, r] = sum_c
    k_t[c] k_r[c] exp(G_t[c] - G_r[c]) for r < t, and O = (Q exp G) S + (B
    Diag(beta)) U with B the same product of q_t and k_r for r <= t. A and B
    are made a sub-block of `block` rows at a time with the exponent split at
    the sub-block's first position, exp(G_t - G_b) exp(G_b - G_r): the first
    factor's exponent lies in [-block * bound, 0] and the second's below 0
    for an earlier sub-block, and in (0, block * bound] inside the row's own
    (16 x 5 = 80 < 88: float32 and bfloat16 hold it; a later sub-block's
    positions, masked for every row, are given no exponent). The system is
    solved in the same sub-blocks (`_unit_lower_inverse`). Chunks follow one
    another in a `lax.scan` that
    carries S f32[B, H, dk, dv]: U = U^ - W S, O, then S <- Diag(exp G_C) S
    + (K exp(G_C - G))^T (beta U). Matrix products take bfloat16 operands
    and accumulate in float32 (the inverse's small ones: float32); g, its
    sums, every decay and S are float32. What does not depend on S is made
    `KDA_GROUP` chunks at a time ahead of the scan; it and the chunk body
    are made again for the gradient (`jax.checkpoint`): the scan keeps the
    state at each chunk's edge and nothing else of a chunk (`_kept`). In a
    layer's gradient the forward of both loops therefore runs three times
    (the layer's own pass, the layer checkpoint's second one, and these
    inner checkpoints' inside the two backward loops). A sequence that is no
    multiple of `chunk` is padded at its end with positions that write
    nothing (k, v, beta, g = 0). Without `carry` every chunk starts from S =
    0 (what a form that drops the state at a chunk's edge computes: the
    tests' and the check's control). With `operands` float32 every product
    takes float32 operands at the highest precision (the tests' witness of
    what the bfloat16 operands cost the decay's gradient; no model runs
    it)."""
    if chunked:
        n, b, h, chunk, dk = q.shape
        s, pad = n * chunk, 0
    else:
        b, s, h, dk = q.shape
        pad = (-s) % chunk
    dv = v.shape[-1]
    n, m = (s + pad) // chunk, chunk // block
    group = next(c for c in range(min(n, KDA_GROUP), 0, -1) if n % c == 0)

    def by_chunk(t):          # [B, S, H, ...] -> [n / group, group, B, H, chunk, ...]
        if not chunked:
            t = jnp.pad(t.astype(F32),
                        ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            t = jnp.moveaxis(
                t.reshape(b, n, chunk, h, *t.shape[3:]), (1, 3), (0, 2))
        return t.reshape(n // group, group, *t.shape[1:])

    bmm = lambda eq, x, y: jnp.einsum(  # noqa: E731
        eq, x.astype(operands), y.astype(operands), preferred_element_type=F32,
        precision=HIGHEST if jnp.dtype(operands) == jnp.dtype(F32) else None)

    @jax.checkpoint
    def within(part):                 # `group` chunks, each by itself
        with jax.named_scope(obs_scopes.KDA_SCAN):
            q, k, v, g, beta = part                        # [group, B, H, chunk, .]
            run = jnp.cumsum(g, axis=-2)                   # G, inclusive
            by_block = lambda t: t.reshape(  # noqa: E731
                *t.shape[:-2], m, block, t.shape[-1])
            # G just before each sub-block's first position
            start = jnp.concatenate(
                [jnp.zeros_like(run[..., :1, :]),
                 run[..., block - 1:chunk - 1:block, :]], -2)   # [..., m, dk]
            near = jnp.exp(by_block(run) - start[..., None, :])
            # (a later sub-block's positions are masked for all of this
            # one's rows, and their exponent, up to chunk * bound, is left out)
            ahead = (jnp.arange(chunk) // block)[None, :] > jnp.arange(m)[:, None]
            far = jnp.exp(jnp.where(
                ahead[:, :, None], 0.0,
                start[..., :, None, :] - run[..., None, :, :]))
            k_far = k[..., None, :, :] * far               # [..., m, chunk, dk]
            pairs = lambda rows: bmm(  # noqa: E731
                "...mic,...mjc->...mij", by_block(rows) * near, k_far).reshape(
                    *rows.shape[:-2], chunk, chunk)
            t_pos = jnp.arange(chunk)
            a = jnp.where(t_pos[:, None] > t_pos[None, :], pairs(k), 0.0)
            b_qk = jnp.where(t_pos[:, None] >= t_pos[None, :], pairs(q), 0.0)
            solve = _unit_lower_inverse(a * beta[..., None, :], block)
            solve = solve * beta[..., :, None]             # beta U, not U
            last = run[..., -1:, :]
            # (what only a product reads is kept as the product takes it)
            return (bmm("...tr,...rd->...td", solve, v),
                    bmm("...tr,...rc->...tc", solve,
                        k * jnp.exp(run)).astype(operands),
                    (q * jnp.exp(run)).astype(operands),
                    (k * jnp.exp(last - run)).astype(operands),
                    b_qk.astype(operands),
                    jnp.exp(last[..., 0, :]))              # [group, B, H, dk]

    @jax.checkpoint
    def one(state, part):             # a chunk, from the state before it
        u_hat, w_in, q_in, k_out, b_qk, decay = part
        with jax.named_scope(obs_scopes.KDA_SCAN):
            u = u_hat - bmm("bhtc,bhcd->bhtd", w_in, state)
            o = bmm("bhtc,bhcd->bhtd", q_in, state) + bmm(
                "bhtr,bhrd->bhtd", b_qk, u)
            new = decay[..., None] * state + bmm("bhtc,bhtd->bhcd", k_out, u)
        return (new if carry else state), o

    parts = jax.lax.map(within, tuple(by_chunk(t) for t in (q, k, v, g, beta)))
    _, o = jax.lax.scan(one, jnp.zeros((b, h, dk, dv), F32), tuple(
        t.reshape(n, *t.shape[2:]) for t in parts))
    # [n, B, H, chunk, dv] -> [B, S, H, dv]
    return o.transpose(1, 0, 3, 2, 4).reshape(b, n * chunk, h, dv)[:, :s]


KDA_FRONT_HEADS = 4    # heads, and
KDA_FRONT_CHUNKS = 2   # chunks, a grid step of the front's kernels takes
KDA_HALO = 8           # rows of `made` a step reads before (and after) its own


def kda_front_kernel(arch: LMArch) -> bool:
    """Whether a linear layer's front is the kernel pair (`_kda_front`): a
    head fills whole lanes, a chunk whole sublanes and the convolution's
    reach lies inside the halo. The tests' preset (heads of 16) keeps the
    XLA form, which is the kernels' reference."""
    return (arch.kda_head_dim > 0 and arch.kda_head_dim % 128 == 0
            and arch.kda_chunk % 8 == 0 and arch.kda_conv - 1 <= KDA_HALO)


def _conv_rows(z, w_ref, i, lanes, back: bool = False):
    """The short convolution of stream i down the rows of z [R, d], y[p] =
    sum_t w[t] z[p - (K - 1) + t] in `short_conv`'s order, or with `back`
    its transpose, sum_t w[t] z[p + (K - 1) - t]. Rows wrap: the first (or
    with `back` the last) K - 1 rows of the result are no one's."""
    from jax.experimental.pallas import tpu as pltpu

    taps, y = w_ref.shape[1], None
    for t in range(taps):
        shift = (taps - 1 - t) if not back else (z.shape[0] - (taps - 1 - t))
        term = (pltpu.roll(z, shift, 0) if shift % z.shape[0] else z) * w_ref[
            i, t:t + 1, lanes]
        y = term if y is None else y + term
    return y


def _rows_inside(rows: int, d: int, seq: int, before: int, after: int):
    """Whether each row a step holds is a position of the sequence, bool
    [before + rows + after, d]: the rows before position 0 read zeros and
    the rows behind the end write nothing, whatever the blocks there hold."""
    from jax.experimental import pallas as pl

    row = pl.program_id(1) * rows - before + jax.lax.broadcasted_iota(
        jnp.int32, (before + rows + after, d), 0)
    return (row >= 0) & (row < seq)


def _kda_front_fwd_kernel(q_ref, k_ref, v_ref, f_ref, qh_ref, kh_ref, vh_ref,
                          w_ref, ab_ref, oq_ref, ok_ref, ov_ref, og_ref, *,
                          seq, eps, lower):
    """`KDA_FRONT_CHUNKS` chunks of `made`'s rows and a few heads' lanes of
    its q, k, v and decay columns (`*_ref` [rows, L]; `*h_ref`: the
    `KDA_HALO` rows before them) -> those chunks of q, k, v, g, a head at a
    time (`o*_ref` [chunks, heads, chunk, d]). w_ref: the taps [3, K, L];
    ab_ref [2, L]: exp(A_log) a lane, and dt_bias."""
    chunks, heads, chunk, d = oq_ref.shape
    inside = _rows_inside(chunks * chunk, d, seq, KDA_HALO, 0)
    here = inside[KDA_HALO:]

    def write(out, j, rows):
        rows = jnp.where(here, rows, 0.0)
        for c in range(chunks):
            out[c, j] = rows[c * chunk:(c + 1) * chunk]

    for j in range(heads):
        lanes = slice(j * d, (j + 1) * d)
        for i, (cur, halo, out) in enumerate((
                (q_ref, qh_ref, oq_ref), (k_ref, kh_ref, ok_ref),
                (v_ref, vh_ref, ov_ref))):
            z = jnp.where(inside, jnp.concatenate(
                [halo[:, lanes], cur[:, lanes]], 0), 0.0)
            y = _conv_rows(z, w_ref, i, lanes)[KDA_HALO:]
            a = y * jax.nn.sigmoid(y)
            if i < 2:
                a = a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + eps)
            write(out, j, a * d ** -0.5 if i == 0 else a)
        u = ab_ref[0:1, lanes] * (f_ref[:, lanes] + ab_ref[1:2, lanes])
        write(og_ref, j, lower * jax.nn.sigmoid(u))


def _kda_front_bwd_kernel(q_ref, k_ref, v_ref, f_ref, qb_ref, kb_ref, vb_ref,
                          qa_ref, ka_ref, va_ref, w_ref, ab_ref, cq_ref,
                          ck_ref, cv_ref, cg_ref, cqa_ref, cka_ref, cva_ref,
                          gate_ref, d_ref, part_ref, *, seq, eps, lower):
    """The gradient's side of `_kda_front_fwd_kernel`, one column block of
    d_made a step (the grid's last axis: q, k, v, the decay, the output
    gate's, which is its cotangent passed on): the forward's intermediates
    made again from the same rows of `made` with a halo on both sides
    (`*b_ref` before, `*a_ref` after), the cotangents `c*_ref` [chunks,
    heads, chunk, d] with the `KDA_HALO` rows after them (`c*a_ref` [heads,
    8, d]: the convolution's transpose reads K - 1 later rows). part_ref
    [2, L]: the step's sums down the rows for dt_bias's gradient and, a
    lane, for exp(A_log)'s."""
    from jax.experimental import pallas as pl

    chunks, heads, chunk, d = cq_ref.shape
    stream = pl.program_id(3)
    inside = _rows_inside(chunks * chunk, d, seq, KDA_HALO, KDA_HALO)
    later = inside[KDA_HALO:]                       # rows + the halo after
    here = inside[KDA_HALO:KDA_HALO + chunks * chunk]
    rows = lambda ref, j: jnp.concatenate(  # noqa: E731
        [ref[c, j] for c in range(chunks)], 0)

    def conv_stream(i, cur, before, after, cot, cot_after):
        for j in range(heads):
            lanes = slice(j * d, (j + 1) * d)
            z = jnp.where(inside, jnp.concatenate(
                [before[:, lanes], cur[:, lanes], after[:, lanes]], 0), 0.0)
            y = _conv_rows(z, w_ref, i, lanes)[KDA_HALO:]
            c = jnp.concatenate([rows(cot, j), cot_after[j]], 0)
            gate = jax.nn.sigmoid(y)
            a = y * gate
            if i < 2:       # through a = a r (Sum a^2 + eps)^-1/2, q's scaled
                r = jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + eps)
                c = (r * d ** -0.5 if i == 0 else r) * (
                    c - a * (r * r) * jnp.sum(c * a, -1, keepdims=True))
            c = jnp.where(later, c * gate * (1.0 + y * (1.0 - gate)), 0.0)
            d_ref[:, lanes] = _conv_rows(c, w_ref, i, lanes, back=True)[
                :chunks * chunk]

    for i, refs in enumerate(((q_ref, qb_ref, qa_ref, cq_ref, cqa_ref),
                              (k_ref, kb_ref, ka_ref, ck_ref, cka_ref),
                              (v_ref, vb_ref, va_ref, cv_ref, cva_ref))):
        pl.when(stream == i)(functools.partial(conv_stream, i, *refs))

    @pl.when(stream == 3)
    def _():
        for j in range(heads):
            lanes = slice(j * d, (j + 1) * d)
            a_lane = ab_ref[0:1, lanes]
            biased = jnp.where(here, f_ref[:, lanes] + ab_ref[1:2, lanes], 0.0)
            gate = jax.nn.sigmoid(a_lane * biased)
            d_u = jnp.where(here, rows(cg_ref, j), 0.0) * (
                lower * gate * (1.0 - gate))
            d_ref[:, lanes] = d_u * a_lane
            part_ref[0:1, lanes] = jnp.sum(d_u * a_lane, 0, keepdims=True)
            part_ref[1:2, lanes] = jnp.sum(d_u * biased, 0, keepdims=True)

    @pl.when(stream == 4)
    def _():
        d_ref[...] = gate_ref[...]


def _kda_front_call(arch: LMArch, made, conv, a_log, dt_bias, cots=None):
    """The front's kernel over `made` f32[B, S, 5 H d]: forward -> q, k, v,
    g [n, B, H, chunk, d]; with `cots` (their cotangents and the output
    gate's [B, S, H d]) the gradient's -> (d_made, d_A_log, d_dt_bias)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, _ = made.shape
    h, d, chunk, taps = arch.heads, arch.kda_head_dim, arch.kda_chunk, arch.kda_conv
    n = -(-s // chunk)
    heads = next(c for c in range(min(h, KDA_FRONT_HEADS), 0, -1) if h % c == 0)
    chunks = next(c for c in range(min(n, KDA_FRONT_CHUNKS), 0, -1) if n % c == 0)
    rows, lanes, across = chunks * chunk, heads * d, h // heads
    halos = rows // KDA_HALO                       # halo blocks a step's rows
    last = -(-s // KDA_HALO) - 1                   # the last halo block there is
    w = conv.astype(F32).reshape(3, h * d, taps).swapaxes(1, 2)
    ab = jnp.stack([jnp.repeat(jnp.exp(a_log), d), dt_bias]).astype(F32)
    # (the grid: batch, step of `chunks` chunks, step of `heads` heads[, stream])
    cur = lambda i: pl.BlockSpec(  # noqa: E731
        (None, rows, lanes), lambda b, c, j, *_: (b, c, i * across + j))
    before = lambda i: pl.BlockSpec(  # noqa: E731
        (None, KDA_HALO, lanes),
        lambda b, c, j, *_: (b, jnp.maximum(c * halos - 1, 0), i * across + j))
    after = lambda i: pl.BlockSpec(  # noqa: E731
        (None, KDA_HALO, lanes),
        lambda b, c, j, *_: (b, jnp.minimum((c + 1) * halos, last),
                             i * across + j))
    taps_spec = pl.BlockSpec((3, taps, lanes), lambda b, c, j, *_: (0, 0, j))
    ab_spec = pl.BlockSpec((2, lanes), lambda b, c, j, *_: (0, j))
    by_chunk = pl.BlockSpec((chunks, None, heads, chunk, d),
                            lambda b, c, j, *_: (c, b, j, 0, 0))
    kw = dict(seq=s, eps=arch.eps, lower=arch.kda_lower_bound)
    if cots is None:
        out = jax.ShapeDtypeStruct((n, b, h, chunk, d), F32)
        return pl.pallas_call(
            functools.partial(_kda_front_fwd_kernel, **kw),
            grid=(b, n // chunks, across),
            in_specs=[cur(0), cur(1), cur(2), cur(3), before(0), before(1),
                      before(2), taps_spec, ab_spec],
            out_specs=[by_chunk] * 4, out_shape=[out] * 4,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",) * 3),
            interpret=_interpret(), name="kda_front_fwd",
        )(*(made,) * 7, w, ab)
    *by, gate = cots
    chunk_after = pl.BlockSpec(
        (None, None, heads, KDA_HALO, d),
        lambda b, c, j, *_: (jnp.minimum((c + 1) * chunks, n - 1), b, j, 0, 0))
    d_made, parts = pl.pallas_call(
        functools.partial(_kda_front_bwd_kernel, **kw),
        grid=(b, n // chunks, across, 5),
        in_specs=[cur(0), cur(1), cur(2), cur(3), before(0), before(1),
                  before(2), after(0), after(1), after(2), taps_spec, ab_spec,
                  by_chunk, by_chunk, by_chunk, by_chunk, chunk_after,
                  chunk_after, chunk_after,
                  pl.BlockSpec((None, rows, lanes), lambda b, c, j, i: (b, c, j))],
        out_specs=[
            pl.BlockSpec((None, rows, lanes),
                         lambda b, c, j, i: (b, c, i * across + j)),
            pl.BlockSpec((None, None, 2, lanes), lambda b, c, j, i: (b, c, 0, j))],
        out_shape=[jax.ShapeDtypeStruct(made.shape, F32),
                   jax.ShapeDtypeStruct((b, n // chunks, 2, h * d), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3 + ("arbitrary",)),
        interpret=_interpret(), name="kda_front_bwd",
    )(*(made,) * 10, w, ab, *by, *by[:3], gate)
    d_bias, d_a = jnp.sum(parts, (0, 1))
    return (d_made,
            (jnp.exp(a_log) * jnp.sum(d_a.reshape(h, d), -1)).astype(a_log.dtype),
            d_bias.astype(dt_bias.dtype))


def _kda_front_xla(arch: LMArch, made, conv, a_log, dt_bias):
    """The front in XLA's operations, a pass each: `made` f32[B, S, 5 H d]
    -> q, k, v, g f32[B, S, H, d]. What a model whose heads are no whole
    lanes runs (the tests' preset), and what the kernel pair `_kda_front`
    is held to."""
    b, s, _ = made.shape
    h, d = arch.heads, arch.kda_head_dim
    n = h * d
    heads = lambda t: t.reshape(b, s, h, d)  # noqa: E731
    q, k, v = (heads(jax.nn.silu(short_conv(
        made[..., i * n:(i + 1) * n], conv[i * n:(i + 1) * n])))
        for i in range(3))
    unit = lambda t: t * jax.lax.rsqrt(  # noqa: E731
        jnp.sum(t * t, -1, keepdims=True) + arch.eps)
    q, k = unit(q) * d ** -0.5, unit(k)
    return q, k, v, arch.kda_lower_bound * jax.nn.sigmoid(
        jnp.exp(a_log)[:, None] * heads(made[..., 3 * n:4 * n] + dt_bias))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _kda_front(arch: LMArch, made, conv, a_log, dt_bias):
    """Everything of a linear layer between its projections' one product
    `made` f32[B, S, 5 H d] and the recurrence's operands, in one pass over
    `made` each way: q, k, v = silu(short_conv(.)) of their columns, q and k
    L2-normalised a head (q times d^-1/2), the decay g = lower_bound *
    sigmoid(exp(A_log) (f + dt_bias)), all float32 and all written by chunk
    ([n, B, H, chunk, d]: the move into chunks is the output's index map,
    and rows behind the sequence's end hold 0), and the output gate's
    columns as they are ([B, S, H d]: a slice, its cotangent goes through
    the gradient's kernel into d_made's fifth block). What `_kda_front_xla`
    computes, operation for operation (the tests hold the pair to it);
    `conv` is frozen and gets no gradient."""
    n = arch.heads * arch.kda_head_dim
    return (*_kda_front_call(arch, made, conv, a_log, dt_bias),
            made[..., 4 * n:])


def _kda_front_fwd(arch, made, conv, a_log, dt_bias):
    return (_kda_front(arch, made, conv, a_log, dt_bias),
            (made, conv, a_log, dt_bias))


def _kda_front_bwd(arch, kept, cots):
    d_made, d_a_log, d_bias = _kda_front_call(arch, *kept, cots=cots)
    return d_made, jnp.zeros_like(kept[1]), d_a_log, d_bias


_kda_front.defvjp(_kda_front_fwd, _kda_front_bwd)


def kda_layer(arch: LMArch, w, g, x):
    """One linear-attention layer. w: the block's frozen matrices: `in` [D,
    5 H d], the projections W_q, W_k, W_v, W_f (the decay's) and W_g (the
    output gate's) side by side as `gate_up` keeps two (one product makes all
    five: one to compile, in each direction), `beta` [D, H], `conv` [3 H d,
    K] the short convolutions of q, k and v, `o` [H d, D]; g: its trained
    leaves (`A_log` [H], `dt_bias` [H d], `o_norm` [d]); x: [B, S, D]
    (already normed).
    q, k, v = silu(conv(x W)); q and k L2-normalised a head, q scaled by
    d^-1/2; the decay a channel g_t = lower_bound * sigmoid(exp(A_log) *
    (x W_f + dt_bias)); beta = sigmoid(x W_beta) a head; the recurrence
    (`kda_recurrence`); y = (RMSNorm_head(o) * sigmoid(x W_g)) W_o.
    The front, from the product `made` to the recurrence's operands, has two
    lowerings of the one algorithm, chosen by shape (`kda_front_kernel`):
    where a head is whole lanes wide (the published preset) one Pallas
    kernel over `made` each way that writes q, k, v and the decay by chunk
    (`_kda_front`), and the recurrence takes them as they are; elsewhere
    (the tests' preset) XLA's operations (`_kda_front_xla`), which are what
    the kernels are held to, and the recurrence's own move into chunks."""
    with jax.named_scope(obs_scopes.KDA):
        b, s, _ = x.shape
        h, d = arch.heads, arch.kda_head_dim
        n = h * d
        heads = lambda t: t.reshape(b, s, h, d)  # noqa: E731
        made = _mm(x, w["in"])
        leaves = (w["conv"], g["A_log"], g["dt_bias"])
        if kda_front_kernel(arch):
            chunk = arch.kda_chunk
            *front, gate = _kda_front(arch, made, *leaves)
            beta = jnp.pad(jax.nn.sigmoid(_mm(x, w["beta"])),
                           ((0, 0), (0, (-s) % chunk), (0, 0))).reshape(
                b, -1, chunk, h).transpose(1, 0, 3, 2)     # [n, B, H, chunk]
            o = kda_recurrence(*front, beta, chunk, arch.kda_block,
                               -arch.kda_lower_bound, chunked=True)[:, :s]
        else:
            front = _kda_front_xla(arch, made, *leaves)
            beta = jax.nn.sigmoid(_mm(x, w["beta"]))
            o = kda_recurrence(*front, beta, arch.kda_chunk, arch.kda_block,
                               -arch.kda_lower_bound)
            gate = None     # sliced below, where this form's program has it
        o = rms_norm(o, g["o_norm"], arch.eps) * jax.nn.sigmoid(heads(
            made[..., 4 * n:5 * n] if gate is None else gate))
        return _mm(o.reshape(b, s, n), w["o"])


def glu(w, x):
    """W_down (silu(W_gate x) * W_up x); gate and up side by side in one
    matrix (`gate_up`: the first half of its columns is the gate)."""
    gu = _mm(x, w["gate_up"])
    f = gu.shape[-1] // 2
    return _mm(jax.nn.silu(gu[..., :f]) * gu[..., f:], w["down"])


STREAM_BYTES = 2 ** 27   # a float32 [tokens, hidden] array above this is large
GLU_BYTES = 2 ** 29   # the most a float32 [tokens, gate and up] array may hold


def glu_by_parts(w, x):
    """`glu` over x [B, S, D] with its tokens in as few equal parts (a power
    of two) as keep the float32 gate-and-up array under `GLU_BYTES`, each
    part made again for the gradient; one part is `glu` itself."""
    b, s, d = x.shape
    parts = 1
    while (b * s * w["gate_up"].shape[-1] * 4 > parts * GLU_BYTES
           and (b * s) % (2 * parts) == 0):
        parts *= 2
    if parts == 1:
        return glu(w, x)
    return jax.lax.map(jax.checkpoint(lambda part: glu(w, part)),
                       x.reshape(parts, -1, d)).reshape(b, s, d)


def route(arch: LMArch, router, bias, x):
    """The published router, float32: s = sigmoid(W_r x) over all experts,
    the `experts_per_tok` largest of s + bias, weights routed_scaling * s_k
    / sum of the selected s. With `n_group` groups of experts the choice is
    group-limited: a group scores the sum of its two largest s + bias, and
    only the experts of the `topk_group` best groups can be chosen. x: [T,
    D] -> (experts int32[T, k], weights f32[T, k])."""
    with jax.named_scope(obs_scopes.MOE_ROUTE):
        s = jax.nn.sigmoid(jnp.dot(x.astype(F32), router.T, precision=HIGHEST,
                                   preferred_element_type=F32))
        choice = s + bias
        if arch.n_group > 1:
            t, n = choice.shape
            groups = choice.reshape(t, arch.n_group, n // arch.n_group)
            best = jnp.sum(jax.lax.top_k(groups, 2)[0], -1)
            _, keep = jax.lax.top_k(best, arch.topk_group)
            kept = jnp.zeros((t, arch.n_group), bool).at[
                jnp.arange(t)[:, None], keep].set(True)
            choice = jnp.where(kept[:, :, None], groups, -jnp.inf).reshape(t, n)
        _, idx = jax.lax.top_k(choice, arch.experts_per_tok)
        w = jnp.take_along_axis(s, idx, axis=-1)
        w = arch.routed_scaling * w / jnp.sum(w, -1, keepdims=True)
        return idx.astype(jnp.int32), w


# --------------------------------------------------------------------------
# the grouped matrix product of the held experts
# --------------------------------------------------------------------------

GMM_ROWS = 256   # rows a tile: a held expert's mean load at the benchmark's batch


def _gmm_tiles(k: int, n: int) -> tuple[int, int]:
    """(tk, tn): whole dimensions up to 1024, so an expert's matrix is read
    in one or two strips a row tile."""
    fit = lambda d: d if d <= 1024 else next(  # noqa: E731
        t for t in (1024, 768, 512, 384, 256, 128) if d % t == 0)
    return fit(k), fit(n)


def _gmm_call(x, w, sizes, transpose: bool, rows: int = GMM_ROWS):
    """out[rows of group g] = x[rows of group g] @ w[g] (w[g].T if
    `transpose`), rows sorted by group, `sizes` rows a group, `rows` rows a
    tile. The Pallas
    grouped product of `jax.experimental.pallas.ops.tpu.megablox` (the
    expert's matrix is found through scalar prefetch and, transposed, read as
    it lies: no copy of a frozen matrix in any layout). Interpreted off the
    TPU. Rows behind the last group are not written: the caller masks them."""
    import importlib

    gmm = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm").gmm
    m = x.shape[0]
    n = w.shape[1] if transpose else w.shape[2]
    tm = min(rows, -(-m // 8) * 8)
    pad = (-m) % tm
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, x.shape[1]), x.dtype)])
    out = gmm(x, w, sizes, preferred_element_type=F32,
              tiling=(tm, *_gmm_tiles(x.shape[1], n)), transpose_rhs=transpose,
              interpret=_interpret())
    return out[:m] if pad else out


@jax.custom_vjp
def grouped_matmul(x, w, sizes):
    """x bf16[m, k] (rows sorted by group) times the group's matrix of w
    bf16[g, k, n] -> f32[m, n]; rows behind the last group read 0. Its
    gradient is taken with respect to x alone: w is frozen, and no
    [g, k, n] cotangent is ever formed."""
    return _masked_rows(_gmm_call(x, w, sizes, False), sizes)


def _masked_rows(out, sizes):
    return jnp.where((jnp.arange(out.shape[0]) < jnp.sum(sizes))[:, None], out, 0.0)


def _grouped_fwd(x, w, sizes):
    return grouped_matmul(x, w, sizes), (w, sizes)


def _grouped_bwd(res, dy):
    w, sizes = res
    dx = _masked_rows(_gmm_call(dy.astype(BF16), w, sizes, True), sizes)
    return dx.astype(BF16), None, None


grouped_matmul.defvjp(_grouped_fwd, _grouped_bwd)


def pair_blocks(arch: LMArch, pairs: int) -> tuple[int, int]:
    """(blocks, rows a block) the held experts' sorted pairs are computed
    in. A chip that holds half of the layer's experts or more takes every
    pair in one grouped product; one that holds fewer takes the held pairs,
    which sort first, in blocks of `pair_block` rows, as many as hold them."""
    if 2 * arch.held_experts >= arch.n_experts or pairs <= arch.pair_block:
        return 1, pairs
    return -(-pairs // arch.pair_block), arch.pair_block


FRONT_ROWS = 512   # rows a tile of the front's grouped products
SUM_LEAD = 4       # pairs a token `_sum_by_token` gathers for every token
SUM_BLOCK = 128    # pairs a block of the rest


def front_pairs(arch: LMArch, pairs: int) -> int:
    """The sorted held pairs `_held_front` takes ahead of the blocks: whole
    blocks, `pair_front` at most; 0 where the pairs are one block."""
    blocks, rows = pair_blocks(arch, pairs)
    return min(arch.pair_front, pairs) // rows * rows if blocks > 1 else 0


def held_experts(arch: LMArch, w, x, idx, weights, at=None):
    """The held experts' part of the layer: sum over the selected experts
    that live here of weight * E(x). Every (token, held expert) pair is
    computed, sorted by expert into a grouped matrix product; pairs of
    absent experts sort behind the last group and add nothing. Where the
    chip holds few of the experts (`pair_blocks`) the product runs over the
    held pairs' blocks alone, as many as hold them (`_held_blocks`), behind
    a front of `pair_front` sorted pairs in one product of that many rows
    (`_held_front`: a fixed capacity, the same work whatever the routers
    do): still every pair, whatever the imbalance. With `at` (a step of
    `hybrid_layers`' scan), w's matrices hold several layers' experts,
    layer-major ([layers * held, ...]), this layer's are those from `at *
    held` on, and the pairs are ordered by counting (`_held_counted`: the
    form that knows of other layers' groups, and the one a scan's step, made
    in both directions, compiles quickly).
    -> (y f32[T, D], load int32[held]: pairs a held expert computed)."""
    with jax.named_scope(obs_scopes.MOE_EXPERTS):
        t, k = idx.shape
        held = arch.held_experts
        local = idx - arch.held_start
        here = (local >= 0) & (local < held)
        key = jnp.where(here, local, held).reshape(t * k)
        if at is not None:
            return _held_counted(arch, w, x, key, jnp.where(here, weights, 0.0),
                                 at)
        order = jnp.argsort(key, stable=True)
        load = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
        blocks, rows = pair_blocks(arch, t * k)
        if blocks > 1:
            # (padded to whole blocks: a pair behind the held ones, of token 0)
            front = front_pairs(arch, t * k)
            xf, padded, pair_w = (
                x.astype(F32), jnp.pad(order, (0, blocks * rows - t * k)),
                jnp.where(here, weights, 0.0).reshape(t * k))
            y = _held_blocks(w, xf, padded, load, pair_w, k, rows, front // rows)
            if front:
                y = y + _held_front(w, xf, order, load, pair_w, k, front)
            return y, load
        return _held_whole(w, x.astype(F32), order, load,
                           jnp.where(here, weights, 0.0)), load


def _pair_block(w, x, order, load, pair_w, k: int, rows: int, i):
    """Block i of the sorted pairs: (their tokens, their places in `pair_w`,
    and the function (the tokens' rows of x, the pairs' weights) -> the
    pairs' weighted outputs f32[rows, D]). Its groups are the parts of the
    experts' runs that fall inside it; rows behind the last held pair read
    0."""
    lo = i * rows
    mine = jax.lax.dynamic_slice(order, (lo,), (rows,))
    ends = jnp.cumsum(load)
    sizes = (jnp.clip(ends, lo, lo + rows)
             - jnp.clip(ends - load, lo, lo + rows)).astype(jnp.int32)

    def outputs(xs, ws):
        with jax.named_scope(obs_scopes.MOE_GMM):
            gu = grouped_matmul(xs.astype(BF16), w["gate_up"], sizes)
        f = gu.shape[-1] // 2
        hid = (jax.nn.silu(gu[:, :f]) * gu[:, f:]).astype(BF16)
        with jax.named_scope(obs_scopes.MOE_GMM):
            return grouped_matmul(hid, w["down"], sizes) * ws[:, None]

    return mine // k, mine, outputs


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _held_blocks(w, x, order, load, pair_w, k: int, rows: int, first: int):
    """`held_experts` a block of `rows` sorted pairs at a time (`order`
    padded to whole blocks), from block `first` on, for as many blocks as
    hold a held pair (a loop whose trip count is data: the blocks behind the
    last held pair are never entered). Its gradient, with respect to x and
    the pairs' weights, walks the same blocks and makes each one's rows
    again. -> y f32[T, D]."""

    def add(i, y):
        tokens, mine, outputs = _pair_block(w, x, order, load, pair_w, k, rows, i)
        return y.at[tokens].add(outputs(x[tokens], pair_w[mine]))

    y0 = jnp.zeros((x.shape[0], w["down"].shape[-1]), F32)
    return jax.lax.fori_loop(first, -(-jnp.sum(load) // rows), add, y0)


def _held_blocks_fwd(w, x, order, load, pair_w, k, rows, first):
    return (_held_blocks(w, x, order, load, pair_w, k, rows, first),
            (w, x, order, load, pair_w))


def _held_blocks_bwd(k, rows, first, res, dy):
    w, x, order, load, pair_w = res

    def back(i, carry):
        dx, dw = carry
        tokens, mine, outputs = _pair_block(w, x, order, load, pair_w, k, rows, i)
        _, vjp = jax.vjp(outputs, x[tokens], pair_w[mine])
        dxs, dws = vjp(dy[tokens])
        return dx.at[tokens].add(dxs), dw.at[mine].add(dws)

    dx, dw = jax.lax.fori_loop(
        first, -(-jnp.sum(load) // rows), back,
        (jnp.zeros_like(x), jnp.zeros_like(pair_w)))
    return None, dx, None, None, dw


_held_blocks.defvjp(_held_blocks_fwd, _held_blocks_bwd)


def _front_rows(order, load, pair_w, k: int, front: int):
    """The front's rows: the first `front` sorted pairs and one tile behind
    them that is in no group and reads 0. -> (the rows' tokens, their pairs'
    weights, rows a matrix is given, which rows hold a held pair, pos
    int32[T * k]: the row a pair sorted to, or the last row where it is not
    among them). The rows behind the last held pair, pairs of absent
    experts with weight 0, are given to the last held expert: every row of
    the front is computed, so the products cost the same whatever the
    routers do, in every round and at every seed, until the held pairs
    outgrow the front."""
    mine = jnp.pad(order[:front], (0, FRONT_ROWS))
    ends = jnp.cumsum(load)
    sizes = (jnp.clip(ends, 0, front)
             - jnp.clip(ends - load, 0, front)).astype(jnp.int32)
    n = jnp.sum(sizes)
    place = jnp.argsort(order).astype(jnp.int32)             # the inverse
    return (mine // k, pair_w[mine], sizes.at[-1].add(front - n),
            jnp.arange(mine.shape[0]) < n,
            jnp.where(place < n, place, mine.shape[0] - 1))  # held sort first


def _sum_by_token(rows, pos, k: int):
    """out[t] = sum over token t's k pairs of rows[pos[t * k + j]], the last
    row of `rows` reading 0: the un-sort as gathers (a scatter-added row
    costs the chip six times a gathered one). A token's `SUM_LEAD` first
    rows that are not the last are gathered for every token; the few pairs
    of tokens with more, sorted ahead of the rest, are added a block at a
    time, for as many blocks as hold one."""
    dummy = rows.shape[0] - 1
    cols = jnp.sort(pos.reshape(-1, k), axis=1)    # the last row sorts behind
    lead = min(k, SUM_LEAD)
    y = rows[cols[:, :lead]].sum(1)
    if lead == k:
        return y
    late = cols[:, lead:].reshape(-1)
    late = jnp.pad(late, (0, (-late.shape[0]) % SUM_BLOCK),
                   constant_values=dummy)
    order = jnp.argsort(late)
    last = y.shape[0] - 1

    def add(i, y):
        at = jax.lax.dynamic_slice(order, (i * SUM_BLOCK,), (SUM_BLOCK,))
        return y.at[jnp.minimum(at // (k - lead), last)].add(rows[late[at]])

    return jax.lax.fori_loop(
        0, -(-jnp.sum(late < dummy) // SUM_BLOCK), add, y)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _held_front(w, x, order, load, pair_w, k: int, front: int):
    """`held_experts` over the first `front` sorted pairs (`order` as it is
    sorted, unpadded) in one grouped product a matrix, every row of it
    computed (`_front_rows`): a fixed capacity with nothing dropped, since
    the pairs behind it keep the blocks. The products, the rows' gather, the
    activation and the un-sort cost the same whatever the routers do. Its
    gradient, with respect to x and the pairs' weights, makes the rows
    again. -> y f32[T, D]."""
    return _held_front_fwd(w, x, order, load, pair_w, k, front)[0]


def _held_front_fwd(w, x, order, load, pair_w, k, front):
    tokens, ws, sizes, live, pos = _front_rows(order, load, pair_w, k, front)
    with jax.named_scope(obs_scopes.MOE_GMM):
        gu = _gmm_call(x.astype(BF16)[tokens], w["gate_up"], sizes, False,
                       FRONT_ROWS)
    f = gu.shape[-1] // 2
    hid = (jax.nn.silu(gu[:, :f]) * gu[:, f:]).astype(BF16)
    with jax.named_scope(obs_scopes.MOE_GMM):
        ys = _gmm_call(hid, w["down"], sizes, False, FRONT_ROWS)
    # (rows behind the last held pair read 0: their weight is 0, and the
    # tile behind the front is not written)
    ys = jnp.where(live[:, None], ys * ws[:, None], 0.0)
    return _sum_by_token(ys, pos, k), (w, x, tokens, ws, sizes, live, pos)


def _held_front_bwd(k, front, res, dy):
    w, x, tokens, ws, sizes, live, pos = res
    with jax.named_scope(obs_scopes.MOE_GMM):
        gu = _gmm_call(x.astype(BF16)[tokens], w["gate_up"], sizes, False,
                       FRONT_ROWS)
        # dy @ down^T a row; the pair's weight comes in behind it
        dh = _gmm_call(dy.astype(BF16)[tokens], w["down"], sizes, True,
                       FRONT_ROWS)
    f = gu.shape[-1] // 2
    g, u = gu[:, :f], gu[:, f:]
    s = jax.nn.sigmoid(g)
    act = g * s
    dws = jnp.sum((act * u).astype(BF16).astype(F32) * dh, -1)
    dh = dh * ws[:, None]
    dgu = jnp.concatenate([dh * u * (s + act * (1.0 - s)), dh * act],
                          -1).astype(BF16)
    with jax.named_scope(obs_scopes.MOE_GMM):
        dxs = _gmm_call(dgu, w["gate_up"], sizes, True, FRONT_ROWS)
    dx = _sum_by_token(jnp.where(live[:, None], dxs, 0.0), pos, k)
    return None, dx, None, None, jnp.where(live, dws, 0.0)[pos]


_held_front.defvjp(_held_front_fwd, _held_front_bwd)


def _sum_held(rows, pos, ws=None):
    """out[t] = sum over token t's held pairs of (their weight ws [T, k]
    times, if given) the row of `rows` they sorted to (pos [T, k]; the row
    count, behind every row, for a pair of an absent expert): the un-sort as
    one gather a (token, slot). A slot of an absent expert is masked here,
    behind the gather: no row behind the last group, which the grouped
    product never wrote, is read into the sum."""
    live = pos < rows.shape[0]
    got = rows[jnp.where(live, pos, 0)]                      # [T, k, D]
    if ws is not None:
        got = got * ws[..., None]
    return jnp.sum(jnp.where(live[..., None], got, 0.0), 1)


@jax.custom_vjp
def _held_whole(w, x, order, load, pair_w):
    """`held_experts` with every sorted pair in one grouped product a matrix
    (a chip that holds half of the layer's experts or more; pair_w [T, k],
    0 for a pair of an absent expert). Rows move between token order and
    expert order once each way, as gathers: the tokens' rows by `order`, the
    pairs' outputs back by the place each held pair sorted to (`_sum_held`).
    The rows behind the last group, the absent experts' pairs, are never
    written, never masked and never read. Its gradient, with respect to x
    and the pairs' weights, keeps the gate and up products and gathers the
    same way. -> y f32[T, D]."""
    return _held_whole_fwd(w, x, order, load, pair_w)[0]


def _held_whole_fwd(w, x, order, load, pair_w):
    tokens = order // pair_w.shape[1]
    xs = x.astype(BF16)[tokens]                              # [T * k, D]
    with jax.named_scope(obs_scopes.MOE_GMM):
        gu = _gmm_call(xs, w["gate_up"], load, False)
    f = gu.shape[-1] // 2
    hid = (jax.nn.silu(gu[:, :f]) * gu[:, f:]).astype(BF16)
    with jax.named_scope(obs_scopes.MOE_GMM):
        ys = _gmm_call(hid, w["down"], load, False)
    place = jnp.argsort(order).astype(jnp.int32)             # the inverse
    pos = jnp.where(place < jnp.sum(load), place,            # held sort first
                    place.shape[0]).reshape(pair_w.shape)
    return _sum_held(ys, pos, pair_w), (
        w, gu, tokens, load, pair_w.reshape(-1)[order], pos)


def _held_whole_bwd(res, dy):
    w, gu, tokens, load, ws, pos = res
    dys = dy.astype(BF16)[tokens]
    with jax.named_scope(obs_scopes.MOE_GMM):
        # dy @ down^T a row; the pair's weight comes in behind it
        dh = _gmm_call(dys, w["down"], load, True)
    f = gu.shape[-1] // 2
    g, u = gu[:, :f], gu[:, f:]
    s = jax.nn.sigmoid(g)
    act = g * s
    dws = jnp.sum((act * u).astype(BF16).astype(F32) * dh, -1)
    dh = dh * ws[:, None]
    dgu = jnp.concatenate([dh * u * (s + act * (1.0 - s)), dh * act],
                          -1).astype(BF16)
    with jax.named_scope(obs_scopes.MOE_GMM):
        dxs = _gmm_call(dgu, w["gate_up"], load, True)
    live = pos < dws.shape[0]
    return (None, _sum_held(dxs, pos), None, None,
            jnp.where(live, dws[jnp.where(live, pos, 0)], 0.0))


_held_whole.defvjp(_held_whole_fwd, _held_whole_bwd)


COUNT_BLOCK = 512   # pairs a block of `_counted_order`'s running counts


def _counted_order(key, buckets: int):
    """The stable order of `key` int32[P] (values in [0, buckets)) without a
    sort -> (pos int32[P]: the place pair p sorts to, order int32[P]: its
    inverse, what `argsort(key, stable=True)` gives, counts int32[buckets]).
    A pair's place is the pairs of smaller keys plus the pairs of its own
    key before it: the running count a key, inside a block of `COUNT_BLOCK`
    pairs as one product with a triangle of ones (0 / 1 operands, float32
    sums: exact), across blocks as a running sum of the blocks' counts."""
    pairs = key.shape[0]
    blk = next(c for c in range(min(pairs, COUNT_BLOCK), 0, -1) if pairs % c == 0)
    hot = (key[:, None] == jnp.arange(buckets)).astype(BF16).reshape(
        pairs // blk, blk, buckets)
    inside = jnp.einsum("ij,bjc->bic", jnp.tril(jnp.ones((blk, blk), BF16)), hot,
                        preferred_element_type=F32)            # inclusive
    per_block = inside[:, -1]
    ran = (inside + (jnp.cumsum(per_block, 0) - per_block)[:, None]).reshape(
        pairs, buckets).astype(jnp.int32)
    counts = ran[-1]
    pos = ((jnp.cumsum(counts) - counts)[key]
           + jnp.take_along_axis(ran, key[:, None], 1)[:, 0] - 1)
    order = jnp.zeros(pairs, jnp.int32).at[pos].set(
        jnp.arange(pairs, dtype=jnp.int32), unique_indices=True)
    return pos, order, counts


def _held_counted(arch: LMArch, w, x, key, pair_w, at):
    """`held_experts` with no sort in it (a step of `hybrid_layers`' scan;
    the chip's compiler takes 15 s over a sort of 65,536 keys wherever one
    stands, and `_held_front` has four): the pairs'
    order by counting (`_counted_order`), the first `pair_front` sorted
    pairs in one grouped product of that many rows, every row computed and
    the un-sort a gather a (token, slot) (`_held_rows`), what is behind them
    in `_held_blocks`. key int32[T * k]: a pair's held expert, or `held` for
    an absent one; pair_w [T, k]: 0 for a pair of an absent expert. This
    layer's experts are w's groups from `at * held` on: the groups of the
    other layers are given no row, and no matrix is sliced out of w."""
    t, k = pair_w.shape
    held, pairs = arch.held_experts, t * k
    pos, order, counts = _counted_order(key, held + 1)
    load = counts[:held]
    groups = jax.lax.dynamic_update_slice(
        jnp.zeros(w["down"].shape[0], jnp.int32), load, (at * held,))
    xf, pair_w = x.astype(F32), pair_w.reshape(pairs)
    front = min(arch.pair_front or pairs, pairs)
    rows = min(arch.pair_block, pairs)
    front = front if front == pairs else front // rows * rows
    y = _held_rows(w, xf, order, pos, groups, pair_w, k, front)
    if front < pairs:
        blocks = -(-pairs // rows)
        y = y + _held_blocks(w, xf, jnp.pad(order, (0, blocks * rows - pairs)),
                             groups, pair_w, k, rows, front // rows)
    return y, load


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _held_rows(w, x, order, pos, load, pair_w, k: int, front: int):
    """`_held_front` without its sorts: the first `front` sorted pairs
    (`order`; `pos` its inverse) in one grouped product a matrix, every row
    computed (the rows behind the last held pair, pairs of absent experts
    with weight 0, are given to the last group: a fixed capacity that costs
    the same whatever the routers do), and the un-sort as one gather a
    (token, slot) of the row the pair sorted to (`_sum_held`). Its gradient,
    with respect to x and the pairs' weights, makes the rows again.
    -> y f32[T, D]."""
    return _held_rows_fwd(w, x, order, pos, load, pair_w, k, front)[0]


def _held_rows_fwd(w, x, order, pos, load, pair_w, k, front):
    mine = order[:front]
    ends = jnp.cumsum(load)
    sizes = (jnp.clip(ends, 0, front)
             - jnp.clip(ends - load, 0, front)).astype(jnp.int32)
    n = jnp.sum(sizes)
    tokens, ws, live = mine // k, pair_w[mine], jnp.arange(front) < n
    sizes = sizes.at[-1].add(front - n)
    at = jnp.where(pos < n, pos, front).reshape(-1, k)   # held sort first
    with jax.named_scope(obs_scopes.MOE_GMM):
        gu = _gmm_call(x.astype(BF16)[tokens], w["gate_up"], sizes, False,
                       FRONT_ROWS)
    f = gu.shape[-1] // 2
    hid = (jax.nn.silu(gu[:, :f]) * gu[:, f:]).astype(BF16)
    with jax.named_scope(obs_scopes.MOE_GMM):
        ys = _gmm_call(hid, w["down"], sizes, False, FRONT_ROWS)
    ys = jnp.where(live[:, None], ys * ws[:, None], 0.0)
    return _sum_held(ys, at), (w, x, tokens, ws, sizes, live, at)


def _held_rows_bwd(k, front, res, dy):
    w, x, tokens, ws, sizes, live, at = res
    with jax.named_scope(obs_scopes.MOE_GMM):
        gu = _gmm_call(x.astype(BF16)[tokens], w["gate_up"], sizes, False,
                       FRONT_ROWS)
        # dy @ down^T a row; the pair's weight comes in behind it
        dh = _gmm_call(dy.astype(BF16)[tokens], w["down"], sizes, True,
                       FRONT_ROWS)
    f = gu.shape[-1] // 2
    g, u = gu[:, :f], gu[:, f:]
    s = jax.nn.sigmoid(g)
    act = g * s
    dws = jnp.where(live, jnp.sum((act * u).astype(BF16).astype(F32) * dh, -1),
                    0.0)
    dh = dh * ws[:, None]
    dgu = jnp.concatenate([dh * u * (s + act * (1.0 - s)), dh * act],
                          -1).astype(BF16)
    with jax.named_scope(obs_scopes.MOE_GMM):
        dxs = _gmm_call(dgu, w["gate_up"], sizes, True, FRONT_ROWS)
    dx = _sum_held(jnp.where(live[:, None], dxs, 0.0), at)
    among = at < front
    return (None, dx, None, None, None,
            jnp.where(among, dws[jnp.where(among, at, 0)], 0.0).reshape(-1))


_held_rows.defvjp(_held_rows_fwd, _held_rows_bwd)


def expert_layer(arch: LMArch, w, router, x, at=None):
    """x: [B, S, D] (already normed) -> (y, load, the selections [T, k]).
    `at`: `held_experts`'s (the other leaves of w are this layer's own)."""
    b, s, d = x.shape
    flat = x.reshape(b * s, d)
    idx, weights = route(arch, router, w["bias"], flat)
    y, load = held_experts(arch, w["experts"], flat, idx, weights, at)
    if arch.shared_experts:
        y = y + glu(w["shared"], flat)
    return y.reshape(b, s, d), load, idx


def block(arch: LMArch, w, g, h, kind: int | None = None):
    """One transformer block on the float32 residual stream h. A block with
    `experts` among its frozen matrices is an expert block (its trained
    leaves then hold the router); `kind` is its place in `layer_pattern`
    where attention is grouped. -> (h, (load, selections) or None, the
    pairs its indexer picked or None)."""
    x = rms_norm(h, g["ln_attn"], arch.eps)
    if arch.kv_heads:
        a, count = grouped_attention(arch, kind, w["attn"], g, x), None
    else:
        a, count = _attend(arch, w["attn"], g, x)
    h = h + a
    x = rms_norm(h, g["ln_mlp"], arch.eps)
    if "experts" in w:
        y, load, idx = expert_layer(arch, w, g["router"], x)
        return h + y, (load, idx), count
    return h + glu_by_parts(w["mlp"], x), None, count


# --------------------------------------------------------------------------
# the module
# --------------------------------------------------------------------------


def _leaf_shapes(arch: LMArch, vocab: int):
    """(base shapes, trained shapes) as pytrees of tuples."""
    d, h = arch.hidden, arch.heads
    dn, dr, dv = arch.qk_nope_head_dim, arch.qk_rope_head_dim, arch.v_head_dim
    gains = {"ln_attn": (d,), "ln_mlp": (d,)}
    if arch.kv_heads:
        return _grouped_leaf_shapes(arch, vocab, gains)
    if arch.kda_head_dim:
        return _hybrid_leaf_shapes(arch, vocab, gains)
    attn = {"q_a": (d, arch.q_lora_rank),
            "q_b": (arch.q_lora_rank, h * (dn + dr)),
            "kv_a": (d, arch.kv_lora_rank + dr),
            "kv_b": (arch.kv_lora_rank, h * (dn + dv)),
            "o": (h * dv, d)}
    if arch.index_topk:
        hi, di = arch.index_heads, arch.index_head_dim
        attn["index"] = {"q": (arch.q_lora_rank, hi * di), "k": (d, di),
                         "k_gain": (di,), "k_bias": (di,), "w": (d, hi)}
    gains = dict(gains, q_norm=(arch.q_lora_rank,), kv_norm=(arch.kv_lora_rank,))
    f, e = arch.moe_intermediate, arch.held_experts
    dense = {"attn": attn, "mlp": {"gate_up": (d, 2 * arch.intermediate),
                                   "down": (arch.intermediate, d)}}
    moe = {"attn": attn,
           "experts": {"gate_up": (e, d, 2 * f), "down": (e, f, d)},
           "shared": {"gate_up": (d, 2 * f), "down": (f, d)},
           "bias": (arch.n_experts,)}
    moe_g = dict(gains, router=(arch.n_experts, d))
    blocks = [dense] * arch.dense_layers + [moe] * arch.expert_layers
    blocks_g = [gains] * arch.dense_layers + [moe_g] * arch.expert_layers
    base = {"embed": (vocab, d), "head": (d, vocab), "blocks": blocks,
            "mtp": {"eh": (2 * d, d), "block": moe}}
    trained = {"blocks": blocks_g, "final_norm": (d,),
               "mtp": {"hnorm": (d,), "enorm": (d,), "norm": (d,),
                       "block": moe_g}}
    return base, trained


def _grouped_leaf_shapes(arch: LMArch, vocab: int, gains):
    """`_leaf_shapes` of a model whose attention is grouped: a layer's `k`
    and `v` are as wide as its kind's KV heads, a kind with sinks trains one
    a head, and there is neither a shared expert nor a prediction module."""
    d, h, dv = arch.hidden, arch.heads, arch.v_head_dim
    dq = arch.qk_nope_head_dim + arch.qk_rope_head_dim
    f, e = arch.moe_intermediate, arch.held_experts
    blocks, blocks_g = [], []
    for layer, kind in enumerate(arch.layer_pattern):
        kv = arch.kv_heads[kind]
        w = {"attn": {"q": (d, h * dq), "k": (d, kv * dq), "v": (d, kv * dv),
                      "o": (h * dv, d)}}
        g = dict(gains, sink=(h,)) if arch.sinks[kind] else dict(gains)
        if layer < arch.dense_layers:
            w["mlp"] = {"gate_up": (d, 2 * arch.intermediate),
                        "down": (arch.intermediate, d)}
        else:
            w["experts"] = {"gate_up": (e, d, 2 * f), "down": (e, f, d)}
            w["bias"] = (arch.n_experts,)
            g["router"] = (arch.n_experts, d)
        blocks.append(w)
        blocks_g.append(g)
    return ({"embed": (vocab, d), "head": (d, vocab), "blocks": blocks},
            {"blocks": blocks_g, "final_norm": (d,)})


def _hybrid_leaf_shapes(arch: LMArch, vocab: int, gains):
    """`_leaf_shapes` of a model with linear layers. The trained subset is a
    list a layer as the others' (a linear layer trains the decay's `A_log`
    and `dt_bias` and the output norm's gain, a latent one `kv_norm`; there
    is no prediction module). The base is **stacked by kind**, because the
    expert layers run as steps of one `lax.scan` (`hybrid_layers`): `linear`
    (`kda_layer`'s leaves) and `latent` (`_attend`'s with no query low-rank
    and a gate a head) with a leading axis over the layers of that kind,
    `mlp` over the dense layers, `shared` and `bias` over the expert layers,
    and `experts` with every expert layer's held experts along one axis,
    layer-major ([expert layers * held, ...]: the grouped product finds a
    layer's through its group sizes and nothing is sliced)."""
    d, h, dk = arch.hidden, arch.heads, arch.kda_head_dim
    dn, dr, dv = arch.qk_nope_head_dim, arch.qk_rope_head_dim, arch.v_head_dim
    f, e = arch.moe_intermediate, arch.held_experts
    n_lin = sum(arch.layer_pattern)
    n_lat, n_exp = len(arch.layer_pattern) - n_lin, arch.expert_layers
    linear = {"in": (d, 5 * h * dk), "beta": (d, h),
              "conv": (3 * h * dk, arch.kda_conv), "o": (h * dk, d)}
    latent = {"q": (d, h * (dn + dr)), "kv_a": (d, arch.kv_lora_rank + dr),
              "kv_b": (arch.kv_lora_rank, h * (dn + dv)), "o": (h * dv, d),
              "gate": (d, h)}
    over = lambda n, tree: {k: (n, *v) for k, v in tree.items()}  # noqa: E731
    base = {"embed": (vocab, d), "head": (d, vocab),
            "linear": over(n_lin, linear), "latent": over(n_lat, latent),
            "mlp": over(arch.dense_layers, {
                "gate_up": (d, 2 * arch.intermediate),
                "down": (arch.intermediate, d)}),
            "experts": {"gate_up": (n_exp * e, d, 2 * f),
                        "down": (n_exp * e, f, d)},
            "shared": over(n_exp, {"gate_up": (d, 2 * f), "down": (f, d)}),
            "bias": (n_exp, arch.n_experts)}
    blocks_g = []
    for layer, kind in enumerate(arch.layer_pattern):
        g = dict(gains, A_log=(h,), dt_bias=(h * dk,), o_norm=(dk,)) if (
            kind == LINEAR) else dict(gains, kv_norm=(arch.kv_lora_rank,))
        if layer >= arch.dense_layers:
            g["router"] = (arch.n_experts, d)
        blocks_g.append(g)
    return base, {"blocks": blocks_g, "final_norm": (d,)}


def hybrid_layers(arch: LMArch, base, blocks_g, h):
    """The residual stream h [B, S, D] through every layer of a model with
    linear layers -> (h, load int32[expert layers, held], selections
    int32[expert layers, T, k]). The leading dense layers one after another;
    the expert layers as one `lax.scan`, so that an expert layer and each
    kind of attention are compiled once in each direction whatever the depth
    (the cold run's budget): a step picks its attention (`kda_layer` or the
    gated `_attend`) by `lax.cond` and its frozen matrices out of the base's
    stacks by its place among its kind. The expert layer stands in the
    step itself, under no `lax.cond`: a `custom_vjp` gives each of its
    arguments a tangent, zeros of its own size where it has none, and a
    branch would have to write out 7 GB of them for the held experts. A
    layer is made again for the gradient; nothing of it is kept (`_kept`)."""
    kinds, first = arch.layer_pattern, arch.dense_layers
    linear = [kind == LINEAR for kind in kinds]
    place = [sum(f == ok for f in linear[:i]) for i, ok in enumerate(linear)]
    stack = lambda name, layers: jnp.stack(  # noqa: E731
        [blocks_g[i][name] for i in layers])
    g_linear = {n: stack(n, [i for i, ok in enumerate(linear) if ok])
                for n in ("A_log", "dt_bias", "o_norm")}
    kv_norm = stack("kv_norm", [i for i, ok in enumerate(linear) if not ok])
    at = lambda tree, i: jax.tree_util.tree_map(lambda t: t[i], tree)  # noqa: E731

    def attention(is_linear, ia, x):  # Python values, or a scan step's
        run = (lambda: kda_layer(arch, at(base["linear"], ia), at(g_linear, ia), x),
               lambda: _attend(arch, at(base["latent"], ia),
                               {"kv_norm": kv_norm[ia]}, x)[0])
        if isinstance(is_linear, bool):
            return run[0]() if is_linear else run[1]()
        return jax.lax.cond(is_linear, *run)

    @functools.partial(jax.checkpoint, policy=_kept(arch), static_argnums=(0,))
    def dense(i: int, ln_attn, ln_mlp, h):
        h = h + attention(linear[i], place[i], rms_norm(h, ln_attn, arch.eps))
        return h + glu_by_parts(at(base["mlp"], i),
                                rms_norm(h, ln_mlp, arch.eps))

    @functools.partial(jax.checkpoint, policy=_kept(arch))
    def routed(h, step):
        is_linear, ia, im, ln_attn, ln_mlp, router = step
        h = h + attention(is_linear, ia, rms_norm(h, ln_attn, arch.eps))
        w = {"experts": base["experts"], "bias": base["bias"][im],
             "shared": at(base["shared"], im)}
        y, load, idx = expert_layer(arch, w, router,
                                    rms_norm(h, ln_mlp, arch.eps), at=im)
        return h + y, (load, idx)

    for i in range(first):
        h = dense(i, blocks_g[i]["ln_attn"], blocks_g[i]["ln_mlp"], h)
    rest = range(first, len(kinds))
    h, seen = jax.lax.scan(routed, h, (
        jnp.asarray(linear[first:]), jnp.asarray(place[first:], jnp.int32),
        jnp.arange(len(rest)), stack("ln_attn", rest), stack("ln_mlp", rest),
        stack("router", rest)))
    return (h, *seen)


_is_shape = lambda t: isinstance(t, tuple)  # noqa: E731
_names = lambda path, name: jax.tree_util.keystr(path).endswith(  # noqa: E731
    f"['{name}']")
STARTS_AT_0 = ("sink", "A_log", "dt_bias")   # trained leaves that start at 0


@dataclasses.dataclass(frozen=True)
class FrozenBaseLM:
    """A model of this file as `fl/` sees it, whichever `arch` it has:
    hashable, `apply({"params": trained, "base": base}, tokens)`.
    `num_classes` is the vocabulary held here; `seed` is the base's (two
    modules of different seeds have different bases and are different keys
    of every cache)."""

    num_classes: int
    arch: LMArch = LMArch()
    seed: int = 0
    token_model = True      # a class attribute, not a field

    # ---- parameters --------------------------------------------------------

    def init_trained(self, key=None):
        """The trained subset at its start: gains 1, sinks and the decay's
        `A_log` and `dt_bias` 0, routers normal(std)."""
        key = jax.random.key(self.seed) if key is None else key
        shapes = _leaf_shapes(self.arch, self.num_classes)[1]
        leaves, tree = jax.tree_util.tree_flatten_with_path(
            shapes, is_leaf=_is_shape)
        out = [
            jnp.zeros(s, F32) if any(
                name in jax.tree_util.keystr(path) for name in STARTS_AT_0)
            else jnp.ones(s, F32) if len(s) == 1 else self.arch.init_std
            * jax.random.normal(jax.random.fold_in(key, i), s, F32)
            for i, (path, s) in enumerate(leaves)]
        return jax.tree_util.tree_unflatten(tree, out)

    def _base_leaves(self):
        """-> ([(shape, dtype: None for a gain that starts at 1)], the tree)
        of the base's leaves."""
        leaves, tree = jax.tree_util.tree_flatten_with_path(
            _leaf_shapes(self.arch, self.num_classes)[0], is_leaf=_is_shape)
        return [(s, None if "k_gain" in jax.tree_util.keystr(path)  # LayerNorm's
                 else "float32" if len(s) == 1 or _names(path, "bias")
                 else "bfloat16") for path, s in leaves], tree

    def base_generators(self) -> dict:
        """{(shape, dtype): `_normal_leaf` compiled for it}: the distinct
        generators of `init_base`, lowered and compiled side by side on the
        host's cores (one after another the hybrid base's 18 take the chip's
        compiler 24 s of a cold run, an older base's stacks 9 to 17 s each).
        For `init_base`'s `made`."""
        import concurrent.futures

        distinct = list(dict.fromkeys(
            leaf for leaf in self._base_leaves()[0] if leaf[1]))
        with concurrent.futures.ThreadPoolExecutor(8) as pool:
            return dict(zip(distinct, pool.map(
                lambda leaf: _leaf_generator(
                    *leaf, stacked=bool(self.arch.kda_head_dim)), distinct)))

    def init_base(self, key=None, made=None):
        """The frozen base, made on the default device leaf by leaf in
        bfloat16 (the router's bias buffer in float32, stacked or not):
        normal(std). `made`: `base_generators`'s programs, called in place
        of `_normal_leaf` (the same programs, compiled ahead)."""
        key = jax.random.key(self.seed) if key is None else key
        leaves, tree = self._base_leaves()

        def normal(i, s, dtype):
            args = (jax.random.fold_in(key, 1000 + i), self.arch.init_std)
            if made is not None:
                return made[s, dtype](*args)
            return _normal_leaf(*args, shape=s, dtype=dtype,
                                stacked=bool(self.arch.kda_head_dim))

        return jax.tree_util.tree_unflatten(tree, [
            normal(i, s, dtype) if dtype else jnp.ones(s, F32)
            for i, (s, dtype) in enumerate(leaves)])

    def bind(self, base):
        return BoundLM(self, base)

    # ---- forward -------------------------------------------------------------

    def hidden(self, variables, tokens, normed: bool = True):
        """tokens int[B, S + 2] -> (h_main, h_mtp, routed, picked): the two
        heads' normed inputs, float32 [B, S, D] (h_mtp None for a model
        without a prediction module); of every expert layer (the prediction
        module's last) the load int32[layers, held] and the selections
        int32[layers, T, k]; and of every attention layer the (query, key)
        pairs its indexer picked, int32[layers], or None for a model
        without one. Without `normed` the two heads' inputs come
        before their last norms and the prediction module's input is made
        again for the gradient (`loss`, where a [tokens, hidden] array is
        large)."""
        arch, p, base = self.arch, variables["params"], variables["base"]
        s = tokens.shape[1] - 2
        emb = lambda t: base["embed"][t].astype(F32)  # noqa: E731
        if arch.kda_head_dim:
            h, loads, idx = hybrid_layers(arch, base, p["blocks"],
                                          emb(tokens[:, :s]))
            linear = sum(arch.layer_pattern)
            for name, value in (("fused", len(p["blocks"]) - linear),
                                ("sparse", 0), ("window", 0), ("linear", linear),
                                ("gated", len(p["blocks"]) - linear)):
                obs_metrics.gauge(f"model.{name}_attention_layers").set(value)
            # the linear layers whose front is the kernel pair (`_kda_front`)
            obs_metrics.gauge("model.kda_front_kernel_layers").set(
                linear if kda_front_kernel(arch) else 0)
            for name in ("selection", "attention"):   # no indexer
                obs_metrics.gauge(f"dsa.kept_{name}_layers").set(0)
            return (rms_norm(h, p["final_norm"], arch.eps) if normed else h,
                    None, (loads, idx), None)
        kinds = arch.layer_pattern or (None,) * len(base["blocks"])
        blk = {kind: jax.checkpoint(
            lambda w, g, h, kind=kind: block(arch, w, g, h, kind),
            policy=_kept(arch)) for kind in set(kinds)}   # one a layer kind
        h, routed, picked = emb(tokens[:, :s]), [], []
        for kind, w, g in zip(kinds, base["blocks"], p["blocks"]):
            h, seen, count = blk[kind](w, g, h)
            picked.append(count)
            if seen is not None:
                routed.append(seen)
        h_main = rms_norm(h, p["final_norm"], arch.eps) if normed else h
        h_mtp = None
        if arch.mtp_modules:
            with jax.named_scope(obs_scopes.MTP):
                m, mb = p["mtp"], base["mtp"]
                joined = lambda h, m: _mm(jnp.concatenate(  # noqa: E731
                    [rms_norm(h, m["hnorm"], arch.eps),
                     rms_norm(emb(tokens[:, 1:s + 1]), m["enorm"], arch.eps)],
                    -1), mb["eh"])
                h2, seen, count = blk[None](
                    mb["block"], m["block"],
                    joined(h, m) if normed else jax.checkpoint(joined)(h, m))
                h_mtp = rms_norm(h2, m["norm"], arch.eps) if normed else h2
            routed.append(seen)
            picked.append(count)
        # every block's attention is a fused kernel: causal, over the
        # indexer's selection, or grouped (causal or over a window)
        obs_metrics.gauge("model.fused_attention_layers").set(
            len(base["blocks"]) + arch.mtp_modules)
        obs_metrics.gauge("model.sparse_attention_layers").set(
            len(picked) if arch.index_topk else 0)
        for name, kept in (("selection", DSA_PICKED), ("attention", ATTN_SAVED)):
            obs_metrics.gauge(f"dsa.kept_{name}_layers").set(
                len(picked) if arch.index_topk and kept in _kept_names(arch)
                else 0)
        obs_metrics.gauge("model.window_attention_layers").set(
            sum(arch.layer_pattern))
        obs_metrics.gauge("model.linear_attention_layers").set(0)
        obs_metrics.gauge("model.kda_front_kernel_layers").set(0)
        obs_metrics.gauge("model.gated_attention_layers").set(0)
        return (h_main, h_mtp, (jnp.stack([r[0] for r in routed]),
                                jnp.stack([r[1] for r in routed])),
                jnp.stack(picked) if arch.index_topk else None)

    def apply(self, variables, tokens, routed: bool = False):
        """-> (logits of the main head, of the prediction module or None),
        float32 [B, S, vocab]: position i predicts token i + 1 and token
        i + 2. With `routed` also `hidden`'s (loads, selections)."""
        h_main, h_mtp, seen, _ = self.hidden(variables, tokens)
        with jax.named_scope(obs_scopes.LM_HEAD):
            head = variables["base"]["head"]
            out = (_mm(h_main, head),
                   None if h_mtp is None else _mm(h_mtp, head))
            return out + (seen,) if routed else out

    def loss(self, variables, tokens):
        """-> (CE_main + mtp_weight * CE_mtp, (CE_main, next-token accuracy,
        loads)), means over every position of every sequence; CE_main alone
        for a model without a prediction module. The logits are
        made a slice of `loss_chunk` tokens at a time and made again for the
        gradient: no [tokens, vocab] array outlives its slice. A model with
        an indexer, grouped attention or linear layers appends four columns
        to `loads` (`COUNTED`)."""
        arch, p = self.arch, variables["params"]
        s = tokens.shape[1] - 2
        # a float32 [tokens, hidden] array over `STREAM_BYTES` is not kept
        # for the gradient where the layer before can make it again: the
        # heads' last norms then run inside the head's own checkpoint
        lean = tokens.shape[0] * s * arch.hidden * 4 > STREAM_BYTES
        h_main, h_mtp, (loads, _), picked = self.hidden(variables, tokens,
                                                        normed=not lean)
        if picked is not None or arch.kv_heads or arch.kda_head_dim:
            loads = _with_counts(arch, loads, picked, *tokens.shape)
        head = variables["base"]["head"]
        if lean:
            normed_ce = jax.checkpoint(lambda h, gain, t: _head_ce(
                rms_norm(h, gain, arch.eps), head, t, arch.loss_chunk))
            ce, acc = normed_ce(h_main, p["final_norm"], tokens[:, 1:s + 1])
        else:
            ce, acc = _head_ce(h_main, head, tokens[:, 1:s + 1], arch.loss_chunk)
        if h_mtp is None:
            return ce, (ce, acc, loads)
        if lean:
            ce2, _ = normed_ce(h_mtp, p["mtp"]["norm"], tokens[:, 2:s + 2])
        else:
            ce2, _ = _head_ce(h_mtp, head, tokens[:, 2:s + 2], arch.loss_chunk)
        return ce + arch.mtp_weight * ce2, (ce, acc, loads)


JoyAIFlash = FrozenBaseLM   # the name the first model of this file came under


COUNTED = 4   # columns `_with_counts` appends to `loss`'s loads


def _with_counts(arch: LMArch, loads, picked, sequences: int, length: int):
    """loads int32[layers, held] -> [layers, held + COUNTED]: a marker (-1,
    so that a sum over sequences stays negative), the rows the layer's
    grouped product was given, the (query, key) pairs its block's indexer
    picked and the causal pairs they were picked from (an expert layer its
    own block's; the dense layers' go to row 0; both 0 where `picked` is
    None: a model without an indexer). What `record_expert_load` turns into
    gauges."""
    s, n = length - 2, loads.shape[0]
    pairs = sequences * s * arch.experts_per_tok
    blocks, rows = pair_blocks(arch, pairs)
    front = front_pairs(arch, pairs) // rows      # blocks `_held_front` takes
    held = jnp.sum(loads, -1)
    given = rows * jnp.clip(-(-held // rows), 0 if blocks > 1 else 1, blocks)
    if front:   # (`_held_front` gives one tile more, `_held_rows` none)
        given = (jnp.maximum(given, front * rows) + (
            0 if arch.kda_head_dim else FRONT_ROWS)).astype(given.dtype)
    if picked is None:
        mine = causal = jnp.zeros((n,), jnp.int32)
    else:
        front = picked.shape[0] - n                      # the dense layers
        mine = picked[front:].at[0].add(jnp.sum(picked[:front]))
        causal = jnp.full((n,), sequences * s * (s + 1) // 2, jnp.int32).at[
            0].mul(front + 1)
    return jnp.concatenate(
        [loads, jnp.stack([jnp.full((n,), -1, jnp.int32), given, mine, causal],
                          -1)], -1)


class BoundLM:
    """A `FrozenBaseLM` with its base: what the client code holds inside a
    round program, where the base is the program's argument. Same `apply`
    and `loss`, over `{"params": trained}`."""

    token_model = True

    def __init__(self, module: FrozenBaseLM, base):
        self.module, self.base = module, base

    def apply(self, variables, tokens, routed: bool = False):
        return self.module.apply({**variables, "base": self.base}, tokens,
                                 routed)

    def loss(self, variables, tokens):
        return self.module.loss({**variables, "base": self.base}, tokens)


def _head_ce(h, head, targets, chunk: int):
    """Mean cross-entropy and accuracy of softmax(h @ head) against integer
    targets, a slice of tokens at a time."""
    with jax.named_scope(obs_scopes.LM_HEAD):
        d = h.shape[-1]
        h, targets = h.reshape(-1, d), targets.reshape(-1)
        n = h.shape[0]
        chunk = min(chunk, n)
        pad = (-n) % chunk
        if pad:
            h = jnp.concatenate([h, jnp.zeros((pad, d), h.dtype)])
            targets = jnp.concatenate([targets, jnp.zeros((pad,), targets.dtype)])
        live = (jnp.arange(n + pad) < n).reshape(-1, chunk)

        @jax.checkpoint
        def one(hc, tc, lc):
            z = _mm(hc, head)
            lse = jax.nn.logsumexp(z, axis=-1)
            hit = jnp.take_along_axis(z, tc[:, None], axis=-1)[:, 0]
            right = (jnp.argmax(z, -1) == tc)
            return (jnp.sum(jnp.where(lc, lse - hit, 0.0)),
                    jnp.sum(jnp.where(lc, right, False).astype(F32)))

        ces, hits = jax.lax.map(
            lambda a: one(*a),
            (h.reshape(-1, chunk, d), targets.reshape(-1, chunk), live))
        return jnp.sum(ces) / n, jnp.sum(hits) / n


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "stacked"))
def _normal_leaf(key, std, shape, dtype, stacked=False):
    # compiled once a shape; made in float32 and narrowed on the device. A
    # `stacked` leaf (`_hybrid_leaf_shapes`: matrices along leading axes) is
    # made a matrix at a time: no float32 form of the whole (10 GB of the
    # held experts), and the chip's compiler takes 1 s over the generator of
    # a matrix where it takes 9 to 17 over that of a stack
    if not stacked or len(shape) < 3:
        return (std * jax.random.normal(key, shape, F32)).astype(dtype)
    return jax.lax.map(
        lambda k: (std * jax.random.normal(k, shape[-2:], F32)).astype(dtype),
        jax.random.split(key, math.prod(shape[:-2]))).reshape(shape)


@functools.lru_cache(maxsize=None)
def _leaf_generator(shape, dtype, stacked):
    """`_normal_leaf` compiled for a shape, once a process (its key and the
    deviation are arguments)."""
    return _normal_leaf.lower(jax.eval_shape(jax.random.key, 0), 0.02,
                              shape=shape, dtype=dtype, stacked=stacked).compile()


# --------------------------------------------------------------------------
# the base of a module, held once a process
# --------------------------------------------------------------------------

_BASES: dict = {}


def frozen_base(module):
    """The base the round programs of `module` take as their argument, or
    None for a model that has none. One base is held at a time: a module
    of another seed or size drops the one before (6.65 GB at the
    benchmark's size). `run_experiment` makes it inside its span
    `hefl.setup.base`."""
    if not is_token_model(module):
        return None
    if module not in _BASES:
        _BASES.clear()
        _BASES[module] = jax.block_until_ready(
            module.init_base(made=module.base_generators()))
    return _BASES[module]


def set_frozen_base(module, base) -> None:
    """Give `module` this base (a check's seeded weights) in place of its
    own; None drops it."""
    _BASES.clear()
    if base is not None:
        _BASES[module] = base


def record_expert_load(loads) -> None:
    """Gauge `moe.load_max_over_mean`: the busiest held expert's pairs over
    the mean, worst layer, of an evaluation forward. From the columns a
    model appends that has an indexer or grouped attention (`_with_counts`;
    the marker is negative) also `moe.rows_over_held_pairs` (rows given to
    the grouped product over pairs held, worst layer) and, with an indexer,
    `dsa.selected_share` (picked over causal (query, key) pairs, percent)."""
    import numpy as np

    loads = np.asarray(loads, np.float64)
    if loads.shape[-1] > COUNTED and loads[0, -COUNTED] < 0:
        given, picked, causal = loads[:, -COUNTED + 1:].T
        loads = loads[:, :-COUNTED]
        obs_metrics.gauge("moe.rows_over_held_pairs").set(
            float(np.max(given / np.maximum(loads.sum(-1), 1.0))))
        if causal.sum():
            obs_metrics.gauge("dsa.selected_share").set(
                100.0 * float(picked.sum() / causal.sum()))
    mean = np.maximum(loads.mean(-1), 1e-9)
    obs_metrics.gauge("moe.load_max_over_mean").set(
        float(np.max(loads.max(-1) / mean)))
