"""Softmax attention of the token models, one fused Pallas kernel a layer
(splash attention, forward and gradient): causal over every key
(`causal_attention`: JoyAI-LLM-Flash's multi-head latent attention, the
equations of arXiv:2412.19437 section 2; Ling-3.0-flash's one layer in six,
without a query low-rank and with a sigmoid gate a head on the output), over
an indexer's selection (`select_keys`, `selected_attention`: DeepSeek-V3.2-Exp's
learned sparse attention; the indexer is frozen with the base and its products
are bfloat16 where the published one is FP8 after a Hadamard turn and trained
by an alignment loss: `benchmarks/configs/deepseek-v32-exp-l5e8.json` lists
each departure), or grouped with no latents (`grouped_attention`:
MiMo-V2-Flash's 64 query heads of 192 over 4 or 8 KV heads, causal in a global
layer, over the 128 last keys with a trained sink a head in a window layer).
Scores and probabilities live a block at a time in the chip's fast memory and
never in HBM, blocks no query of which attends any key are skipped, and memory
is linear in the sequence. Every block is made again for the gradient (a
`jax.checkpoint` a layer); what is kept besides its input is `model._kept`'s.
"""

from __future__ import annotations

import contextlib
import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from hefl_tpu.models.lm import common
from hefl_tpu.models.lm.common import (
    BF16, F32, LMArch, _mm, rms_norm, rope, softmax_scale)
from hefl_tpu.obs import metrics as obs_metrics
from hefl_tpu.obs import scopes as obs_scopes

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
    kernel = _attention_kernel(s + pad, h, blk, common._interpret())
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
    kernel, ratio = _grouped_kernel(s + pad, h // g, window, blk, common._interpret())
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
        residual_checkpoint_name=ATTN_SAVED, interpret=common._interpret())
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
