"""A DeepSeek-V3-shaped language model as a frozen base and a trained subset.

`JoyAIFlash` is JoyAI-LLM-Flash (huggingface.co/jdopensource/JoyAI-LLM-Flash,
config.json; every key a DeepSeek-V3 key, so the equations are those of
arXiv:2412.19437 section 2): multi-head latent attention, one leading dense
layer, expert layers with a sigmoid router (256 wide, 8 a token, one shared
expert, `noaux_tc` selection over `s + bias`, weights normalised over the
selected and scaled by 2.5) and one multi-token-prediction module.

What a federation can afford of such a model (PERF.md, PR 26-27: a trained
parameter costs a client 16 bytes for its step and 24 for its ciphertext, a
frozen one 2) decides the layout. The parameters are two pytrees:

  * the **base** (`init_base`): every matrix, bfloat16, made on the device
    leaf by leaf from the seed. It stays on the client: it is an argument of
    the round program (never a constant of it), is in no `ClientState`, no
    optimizer, no `PackSpec` and no ciphertext, and no gradient with respect
    to it is ever formed.
  * the **trained subset** (`init_trained`): every router matrix and every
    RMSNorm gain, float32. It is what `create_model` returns as `params`:
    what is stepped, encrypted, summed and decrypted.

The expert layer is told which experts it holds (`held_start`,
`held_experts`): selection and normalisation run over all `n_experts`, the
held experts' terms are computed by a grouped matrix product
(`grouped_matmul`, every (token, held expert) pair whatever the
imbalance: no capacity, nothing dropped) and summed with the shared expert;
what absent experts would add is left out. Compute is bfloat16 with float32
accumulation; the residual stream, the norms, the router and the softmax are
float32. Attention is causal and one fused Pallas kernel a layer
(`causal_attention`: splash attention, forward and gradient): scores and
probabilities live a block at a time in the chip's fast memory and never in
HBM, the blocks above the diagonal are skipped, and memory is linear in the
sequence. Every block is made again for the gradient (a `jax.checkpoint` a
layer) but for attention's output and log-sum-exp, which are kept.

A module here is a frozen dataclass, hashable like a flax module, with the
same `apply({"params": ...}, x)`; `bind(base)` gives the module that the
client code sees inside a round program, its base the program's argument.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

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
}


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


def rope(x, theta: float):
    """Rotary embedding over interleaved pairs (`rope_interleave`), no
    scaling. x: [B, S, H, d]; the position is the index along S."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x = x.astype(F32).reshape(*x.shape[:-1], d // 2, 2)
    a, b = x[..., 0], x[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(
        *x.shape[:-2], d)


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


def causal_attention(q, k, v, q_block: int):
    """softmax(q k^T / sqrt(d)) v, causal. q, k: [B, S, H, dq]; v: [B, S, H,
    dv] -> f32[B, S, H, dv]. One fused Pallas kernel (`_attention_kernel`)
    and one more for its gradient: bfloat16 operands, float32 scores, a
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
    q = q.astype(F32) * (1.0 / math.sqrt(dq))
    kernel = _attention_kernel(s + pad, h, blk, _interpret())
    o = jax.vmap(kernel)(heads_first(q), heads_first(k), heads_first(v))
    return o.transpose(0, 2, 1, 3)[:, :s].astype(F32)


def latent_attention(arch: LMArch, w, g, x):
    """Multi-head latent attention. w: the block's frozen matrices, g: its
    trained gains (`q_norm`, `kv_norm`), x: [B, S, D] (already normed)."""
    with jax.named_scope(obs_scopes.MLA):
        b, s, _ = x.shape
        h, dn, dr, dv = (arch.heads, arch.qk_nope_head_dim,
                         arch.qk_rope_head_dim, arch.v_head_dim)
        c_q = rms_norm(_mm(x, w["q_a"]), g["q_norm"], arch.eps)
        q = _mm(c_q, w["q_b"]).reshape(b, s, h, dn + dr)
        kv_a = _mm(x, w["kv_a"])
        c_kv, k_r = kv_a[..., :arch.kv_lora_rank], kv_a[..., arch.kv_lora_rank:]
        kv = _mm(rms_norm(c_kv, g["kv_norm"], arch.eps), w["kv_b"]).reshape(
            b, s, h, dn + dv)
        q_r = rope(q[..., dn:], arch.rope_theta)
        k_r = rope(k_r[:, :, None, :], arch.rope_theta)      # one shared head
        q = jnp.concatenate([q[..., :dn], q_r], -1)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_r, (b, s, h, dr))], -1)
        o = causal_attention(q, k, kv[..., dn:], arch.q_block)
        return _mm(o.reshape(b, s, h * dv), w["o"])


def glu(w, x):
    """W_down (silu(W_gate x) * W_up x); gate and up side by side in one
    matrix (`gate_up`: the first half of its columns is the gate)."""
    gu = _mm(x, w["gate_up"])
    f = gu.shape[-1] // 2
    return _mm(jax.nn.silu(gu[..., :f]) * gu[..., f:], w["down"])


def route(arch: LMArch, router, bias, x):
    """The published router, float32: s = sigmoid(W_r x) over all experts,
    the `experts_per_tok` largest of s + bias, weights routed_scaling * s_k
    / sum of the selected s. x: [T, D] -> (experts int32[T, k], weights
    f32[T, k])."""
    with jax.named_scope(obs_scopes.MOE_ROUTE):
        s = jax.nn.sigmoid(jnp.dot(x.astype(F32), router.T, precision=HIGHEST,
                                   preferred_element_type=F32))
        _, idx = jax.lax.top_k(s + bias, arch.experts_per_tok)
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


def _gmm_call(x, w, sizes, transpose: bool):
    """out[rows of group g] = x[rows of group g] @ w[g] (w[g].T if
    `transpose`), rows sorted by group, `sizes` rows a group. The Pallas
    grouped product of `jax.experimental.pallas.ops.tpu.megablox` (the
    expert's matrix is found through scalar prefetch and, transposed, read as
    it lies: no copy of a frozen matrix in any layout). Interpreted off the
    TPU. Rows behind the last group are not written: the caller masks them."""
    import importlib

    gmm = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm").gmm
    m = x.shape[0]
    n = w.shape[1] if transpose else w.shape[2]
    tm = min(GMM_ROWS, -(-m // 8) * 8)
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


def held_experts(arch: LMArch, w, x, idx, weights):
    """The held experts' part of the layer: sum over the selected experts
    that live here of weight * E(x). Every (token, held expert) pair is
    computed, sorted by expert into one grouped matrix product; pairs of
    absent experts sort behind the last group and add nothing.
    -> (y f32[T, D], load int32[held]: pairs a held expert computed)."""
    with jax.named_scope(obs_scopes.MOE_EXPERTS):
        t, k = idx.shape
        held = arch.held_experts
        local = idx - arch.held_start
        here = (local >= 0) & (local < held)
        key = jnp.where(here, local, held).reshape(t * k)
        order = jnp.argsort(key, stable=True)
        load = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
        xs = x.astype(BF16)[order // k]                       # [T*k, D]
        with jax.named_scope(obs_scopes.MOE_GMM):
            gu = grouped_matmul(xs, w["gate_up"], load)
        f = gu.shape[-1] // 2
        hid = (jax.nn.silu(gu[:, :f]) * gu[:, f:]).astype(BF16)
        with jax.named_scope(obs_scopes.MOE_GMM):
            ys = grouped_matmul(hid, w["down"], load)
        # (rows behind the last group, the absent experts', read 0)
        pair_w = jnp.where(here, weights, 0.0).reshape(t * k)
        back = jnp.argsort(order)                              # the inverse
        y = (ys[back] * pair_w[:, None]).reshape(t, k, -1).sum(1)
        return y, load


def expert_layer(arch: LMArch, w, router, x):
    """x: [B, S, D] (already normed) -> (y, load, the selections [T, k])."""
    b, s, d = x.shape
    flat = x.reshape(b * s, d)
    idx, weights = route(arch, router, w["bias"], flat)
    y, load = held_experts(arch, w["experts"], flat, idx, weights)
    y = y + glu(w["shared"], flat)
    return y.reshape(b, s, d), load, idx


def block(arch: LMArch, w, g, h):
    """One transformer block on the float32 residual stream h. A block with
    `experts` among its frozen matrices is an expert block (its trained
    leaves then hold the router). -> (h, (load, selections) or None)."""
    h = h + latent_attention(arch, w["attn"], g, rms_norm(h, g["ln_attn"], arch.eps))
    x = rms_norm(h, g["ln_mlp"], arch.eps)
    if "experts" in w:
        y, load, idx = expert_layer(arch, w, g["router"], x)
        return h + y, (load, idx)
    return h + glu(w["mlp"], x), None


# --------------------------------------------------------------------------
# the module
# --------------------------------------------------------------------------


def _leaf_shapes(arch: LMArch, vocab: int):
    """(base shapes, trained shapes) as pytrees of tuples."""
    d, h = arch.hidden, arch.heads
    dn, dr, dv = arch.qk_nope_head_dim, arch.qk_rope_head_dim, arch.v_head_dim
    attn = {"q_a": (d, arch.q_lora_rank),
            "q_b": (arch.q_lora_rank, h * (dn + dr)),
            "kv_a": (d, arch.kv_lora_rank + dr),
            "kv_b": (arch.kv_lora_rank, h * (dn + dv)),
            "o": (h * dv, d)}
    gains = {"ln_attn": (d,), "ln_mlp": (d,), "q_norm": (arch.q_lora_rank,),
             "kv_norm": (arch.kv_lora_rank,)}
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


_is_shape = lambda t: isinstance(t, tuple)  # noqa: E731


@dataclasses.dataclass(frozen=True)
class JoyAIFlash:
    """The model as `fl/` sees it: hashable, `apply({"params": trained,
    "base": base}, tokens)`. `num_classes` is the vocabulary held here;
    `seed` is the base's (two modules of different seeds have different
    bases and are different keys of every cache)."""

    num_classes: int
    arch: LMArch = LMArch()
    seed: int = 0
    token_model = True      # a class attribute, not a field

    # ---- parameters --------------------------------------------------------

    def init_trained(self, key=None):
        """The trained subset at its start: gains 1, routers normal(std)."""
        key = jax.random.key(self.seed) if key is None else key
        shapes = _leaf_shapes(self.arch, self.num_classes)[1]
        leaves, tree = jax.tree_util.tree_flatten(shapes, is_leaf=_is_shape)
        out = [
            jnp.ones(s, F32) if len(s) == 1 else self.arch.init_std
            * jax.random.normal(jax.random.fold_in(key, i), s, F32)
            for i, s in enumerate(leaves)]
        return jax.tree_util.tree_unflatten(tree, out)

    def init_base(self, key=None):
        """The frozen base, made on the default device leaf by leaf in
        bfloat16 (the router's bias buffer in float32): normal(std)."""
        key = jax.random.key(self.seed) if key is None else key
        shapes = _leaf_shapes(self.arch, self.num_classes)[0]
        leaves, tree = jax.tree_util.tree_flatten(shapes, is_leaf=_is_shape)
        out = [_normal_leaf(jax.random.fold_in(key, 1000 + i),
                            self.arch.init_std, shape=s,
                            dtype="float32" if len(s) == 1 else "bfloat16")
               for i, s in enumerate(leaves)]
        return jax.tree_util.tree_unflatten(tree, out)

    def bind(self, base):
        return BoundLM(self, base)

    # ---- forward -------------------------------------------------------------

    def hidden(self, variables, tokens):
        """tokens int[B, S + 2] -> (h_main, h_mtp, routed): the two heads'
        normed inputs, float32 [B, S, D], and of every expert layer (the
        prediction module's last) the load int32[layers, held] and the
        selections int32[layers, T, k]."""
        arch, p, base = self.arch, variables["params"], variables["base"]
        s = tokens.shape[1] - 2
        emb = lambda t: base["embed"][t].astype(F32)  # noqa: E731
        blk = jax.checkpoint(
            lambda w, g, h: block(arch, w, g, h),
            policy=jax.checkpoint_policies.save_only_these_names(ATTN_SAVED))
        h, routed = emb(tokens[:, :s]), []
        for w, g in zip(base["blocks"], p["blocks"]):
            h, seen = blk(w, g, h)
            if seen is not None:
                routed.append(seen)
        h_main = rms_norm(h, p["final_norm"], arch.eps)
        with jax.named_scope(obs_scopes.MTP):
            m, mb = p["mtp"], base["mtp"]
            both = jnp.concatenate(
                [rms_norm(h, m["hnorm"], arch.eps),
                 rms_norm(emb(tokens[:, 1:s + 1]), m["enorm"], arch.eps)], -1)
            h2, seen = blk(mb["block"], m["block"], _mm(both, mb["eh"]))
            h_mtp = rms_norm(h2, m["norm"], arch.eps)
        routed.append(seen)
        # every block's attention is the fused kernel: `causal_attention`
        # has one path
        obs_metrics.gauge("model.fused_attention_layers").set(
            len(base["blocks"]) + 1)
        return h_main, h_mtp, (jnp.stack([r[0] for r in routed]),
                               jnp.stack([r[1] for r in routed]))

    def apply(self, variables, tokens, routed: bool = False):
        """-> (logits of the main head, of the prediction module), float32
        [B, S, vocab]: position i predicts token i + 1 and token i + 2.
        With `routed` also `hidden`'s (loads, selections)."""
        h_main, h_mtp, seen = self.hidden(variables, tokens)
        with jax.named_scope(obs_scopes.LM_HEAD):
            head = variables["base"]["head"]
            out = _mm(h_main, head), _mm(h_mtp, head)
            return out + (seen,) if routed else out

    def loss(self, variables, tokens):
        """-> (CE_main + mtp_weight * CE_mtp, (CE_main, next-token accuracy,
        loads)), means over every position of every sequence. The logits are
        made a slice of `loss_chunk` tokens at a time and made again for the
        gradient: no [tokens, vocab] array outlives its slice."""
        h_main, h_mtp, (loads, _) = self.hidden(variables, tokens)
        s = tokens.shape[1] - 2
        head = variables["base"]["head"]
        ce, acc = _head_ce(h_main, head, tokens[:, 1:s + 1], self.arch.loss_chunk)
        ce2, _ = _head_ce(h_mtp, head, tokens[:, 2:s + 2], self.arch.loss_chunk)
        return ce + self.arch.mtp_weight * ce2, (ce, acc, loads)


class BoundLM:
    """A `JoyAIFlash` with its base: what the client code holds inside a
    round program, where the base is the program's argument. Same `apply`
    and `loss`, over `{"params": trained}`."""

    token_model = True

    def __init__(self, module: JoyAIFlash, base):
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


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _normal_leaf(key, std, shape, dtype):
    # compiled once a shape; made in float32 and narrowed on the device
    return (std * jax.random.normal(key, shape, F32)).astype(dtype)


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
        _BASES[module] = jax.block_until_ready(module.init_base())
    return _BASES[module]


def set_frozen_base(module, base) -> None:
    """Give `module` this base (a check's seeded weights) in place of its
    own; None drops it."""
    _BASES.clear()
    if base is not None:
        _BASES[module] = base


def record_expert_load(loads) -> None:
    """Gauge `moe.load_max_over_mean`: the busiest held expert's pairs over
    the mean, worst layer, of an evaluation forward."""
    import numpy as np

    loads = np.asarray(loads, np.float64)
    mean = np.maximum(loads.mean(-1), 1e-9)
    obs_metrics.gauge("moe.load_max_over_mean").set(
        float(np.max(loads.max(-1) / mean)))
