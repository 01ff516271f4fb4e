"""Expert language models as a frozen base and a trained subset: one module
class, `FrozenBaseLM`, over an `LMArch`, and the table of the kinds of
attention a layer can run (`LayerKind`, `kinds_of`). A published model is a
preset (`common.PRESETS`) and, where it brought a layer of its own, a row of
that table and a module beside this one: **JoyAI-LLM-Flash**
(huggingface.co/jdopensource/JoyAI-LLM-Flash; every key a DeepSeek-V3 key:
latent attention, one leading dense layer, a sigmoid router 256 wide and 8 a
token, one shared expert, one prediction module), **DeepSeek-V3.2-Exp**
(huggingface.co/deepseek-ai/DeepSeek-V3.2-Exp: the same at hidden 7168 over
an indexer's selection, group-limited routing, YaRN, 8 of 256 experts held),
**MiMo-V2-Flash** (huggingface.co/XiaomiMiMo/MiMo-V2-Flash: grouped-query
attention, five window layers to one global, no shared expert and no
prediction module: the loss is the next-token cross-entropy alone) and
**Ling-3.0-flash** (huggingface.co/inclusionAI/Ling-3.0-flash: five linear
layers to one gated latent layer, 512 experts in 8 groups, 128 held, no
prediction module). `benchmarks/configs/*.json` list what each cell assumes.

What a federation can afford of such a model (PERF.md, PR 26-27: a trained
parameter costs a client 16 bytes for its step and 24 for its ciphertext, a
frozen one 2) decides the layout, two pytrees: the **base** (`init_base`),
every matrix, bfloat16, made on the device leaf by leaf from the seed: an
argument of the round program (never a constant of it), in no `ClientState`,
optimizer, `PackSpec` or ciphertext, and no gradient with respect to it is
ever formed; and the **trained subset** (`init_trained`), every router
matrix, RMSNorm gain, sink and linear layer's `A_log` and `dt_bias`, float32:
what `create_model` returns as `params` and what is stepped, encrypted,
summed and decrypted. A module is a frozen dataclass, hashable like a flax
module, with the same `apply({"params": ...}, x)`; `bind(base)` gives the one
the client code sees inside a round program, its base the program's argument.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from hefl_tpu.models.lm.attention import (
    ATTN_SAVED, DSA_PICKED, _attend, grouped_attention)
from hefl_tpu.models.lm.common import (
    F32, LINEAR, LMArch, _mm, is_token_model, rms_norm)
from hefl_tpu.models.lm.experts import expert_layer, expert_plan, glu_by_parts
from hefl_tpu.models.lm.kda import kda_front_kernel, kda_layer
from hefl_tpu.obs import metrics as obs_metrics
from hefl_tpu.obs import scopes as obs_scopes

STREAM_BYTES = 2 ** 27   # a float32 [tokens, hidden] array above this is large


@dataclasses.dataclass(frozen=True)
class LayerKind:
    """One kind of attention a layer can run, as what the model asks of it:
    its forward `(arch, *args, w, g, x) -> a, or (a, the pairs its indexer
    picked)` over the layer's frozen matrices w and trained leaves g, the
    shapes of both (beside the two norms' gains every layer has), the names
    its checkpoint keeps and the `model.<name>_attention_layers` it counts in."""

    name: str
    forward: object
    frozen: object
    trained: object
    kept: tuple = ()
    gauges: tuple = ("fused",)
    args: tuple = ()       # what `forward` takes between `arch` and w


def _run(out):
    """A kind's output and the pairs its indexer picked or None (split behind
    the call: no frame is added under the trace)."""
    return out if isinstance(out, tuple) else (out, None)


def _latent_shapes(arch: LMArch) -> dict:
    d, h, r = arch.hidden, arch.heads, arch.kv_lora_rank
    dn, dr, dv = arch.qk_nope_head_dim, arch.qk_rope_head_dim, arch.v_head_dim
    return {"q_a": (d, arch.q_lora_rank),
            "q_b": (arch.q_lora_rank, h * (dn + dr)), "kv_a": (d, r + dr),
            "kv_b": (r, h * (dn + dv)), "o": (h * dv, d)}


def _selected_shapes(arch: LMArch) -> dict:
    hi, di = arch.index_heads, arch.index_head_dim
    return dict(_latent_shapes(arch), index={
        "q": (arch.q_lora_rank, hi * di), "k": (arch.hidden, di),
        "k_gain": (di,), "k_bias": (di,), "w": (arch.hidden, hi)})


def _gated_shapes(arch: LMArch) -> dict:
    shapes = _latent_shapes(arch)
    return {"q": (arch.hidden, shapes["q_b"][1]), "gate": (arch.hidden, arch.heads),
            **{name: shapes[name] for name in ("kv_a", "kv_b", "o")}}


def _grouped_kind(name: str, i: int, gauges: tuple) -> LayerKind:
    """Grouped-query attention of `layer_pattern`'s kind i (MiMo-V2-Flash; 0
    global, 1 window): `k`, `v` as wide as the kind's KV heads, a trained sink
    a head where it has one, nothing kept (kept, the round was over 14 GB)."""
    def frozen(arch):
        d, h, kv = arch.hidden, arch.heads, arch.kv_heads[i]
        dq = arch.qk_nope_head_dim + arch.qk_rope_head_dim
        return {"q": (d, h * dq), "k": (d, kv * dq),
                "v": (d, kv * arch.v_head_dim), "o": (h * arch.v_head_dim, d)}

    return LayerKind(
        name, grouped_attention, frozen,
        lambda arch: {"sink": (arch.heads,)} if arch.sinks[i] else {},
        gauges=gauges, args=(i,))


def _linear_shapes(arch: LMArch) -> dict:
    d, n = arch.hidden, arch.heads * arch.kda_head_dim
    return {"in": (d, 5 * n), "beta": (d, arch.heads),
            "conv": (3 * n, arch.kda_conv), "o": (n, d)}


# The kinds of attention a layer runs, a row each; `kinds_of` says which.
# Latent (JoyAI-LLM-Flash) keeps the kernel's output and log-sum-exp; selected
# (DeepSeek-V3.2-Exp) its indexer's selection too, packed. Gated
# (Ling-3.0-flash's one latent layer in six) keeps nothing: its layers are
# steps of one scan, and a name kept in a step is kept for every step. Of a
# linear layer's recurrence the state at each chunk's edge is kept while that
# layer's gradient is made, by the scan over its chunks itself, and nothing
# from layer to layer.
_latent_gains = lambda arch: {  # noqa: E731
    "q_norm": (arch.q_lora_rank,), "kv_norm": (arch.kv_lora_rank,)}
LATENT_KIND = LayerKind("latent", _attend, _latent_shapes, _latent_gains,
                        kept=(ATTN_SAVED,))
SELECTED_KIND = LayerKind("selected", _attend, _selected_shapes, _latent_gains,
                          kept=(DSA_PICKED, ATTN_SAVED),
                          gauges=("fused", "sparse"))
GATED_KIND = LayerKind("gated", _attend, _gated_shapes,
                       lambda arch: {"kv_norm": (arch.kv_lora_rank,)},
                       gauges=("fused", "gated"))
GLOBAL_KIND = _grouped_kind("global", 0, ("fused",))
WINDOW_KIND = _grouped_kind("window", 1, ("fused", "window"))
LINEAR_KIND = LayerKind(
    "linear", kda_layer, _linear_shapes,
    lambda arch: {"A_log": (arch.heads,),
                  "dt_bias": (arch.heads * arch.kda_head_dim,),
                  "o_norm": (arch.kda_head_dim,)},
    gauges=("linear",))


def kinds_of(arch: LMArch) -> tuple:
    """The kind of attention each layer runs: the one reader of the keys that
    decide it. `kv_heads`: grouped, global or window by `layer_pattern`;
    `kda_head_dim`: linear where `layer_pattern` says so; a latent layer is
    gated with `attn_gate` and no query low-rank, selected with an indexer."""
    layers = arch.dense_layers + arch.expert_layers
    if arch.kv_heads:
        return tuple((GLOBAL_KIND, WINDOW_KIND)[i] for i in arch.layer_pattern)
    latent = (SELECTED_KIND if arch.index_topk
              else GATED_KIND if arch.attn_gate and not arch.q_lora_rank
              else LATENT_KIND)
    if arch.kda_head_dim:
        return tuple(LINEAR_KIND if i == LINEAR else latent
                     for i in arch.layer_pattern)
    return (latent,) * layers


def stacked(arch: LMArch) -> bool:
    """Whether the layers run as `hybrid_layers`' scan over a stacked base."""
    return LINEAR_KIND in kinds_of(arch)


def _set_layer_gauges(arch: LMArch | None = None, kinds=()) -> None:
    """The gauges a traced forward sets, from the kinds of the attention
    layers it ran; without any (an image model's record) every one reads 0."""
    count = lambda name: sum(name in kind.gauges for kind in kinds)  # noqa: E731
    for name in ("fused", "sparse", "window", "linear", "gated"):
        obs_metrics.gauge(f"model.{name}_attention_layers").set(count(name))
    obs_metrics.gauge("model.kda_front_kernel_layers").set(
        count("linear") if kinds and kda_front_kernel(arch) else 0)
    kept = _kept_names(arch) if kinds else ()
    for name, saved in (("selection", DSA_PICKED), ("attention", ATTN_SAVED)):
        obs_metrics.gauge(f"dsa.kept_{name}_layers").set(
            count("sparse") if saved in kept else 0)


def _kept_names(arch: LMArch) -> tuple:
    """The checkpoint names `_kept` keeps: what the kinds of the layers keep."""
    return tuple(dict.fromkeys(
        name for kind in kinds_of(arch) for name in kind.kept))


def _kept(arch: LMArch):
    """What a layer's checkpoint keeps for the gradient besides the layer's
    input, as a policy: the names its kinds keep (the table above says which
    and why). `ATTN_SAVED`: attention's output and log-sum-exp, so the forward
    kernel does not run again; `DSA_PICKED`: an indexer's selection, packed,
    so the gradient's copy of the layer runs neither the indexer's scores nor
    the 32 counting passes (`select_keys` has no gradient). At 8,192 positions
    and 128 heads 8.4 MB and 0.272 GB a layer, 1.68 GB over deepseek's six,
    sized by the described compile of the round program against the 16.91e9
    bytes a v5e lets a program use (14.9e9 in all; PERF.md, PR 42)."""
    names = _kept_names(arch)
    if not names:
        return jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint_policies.save_only_these_names(*names)


def block(arch: LMArch, w, g, h, kind: LayerKind | None = None):
    """One transformer block on the float32 residual stream h. A block with
    `experts` among its frozen matrices is an expert block (its trained
    leaves then hold the router); `kind` is its attention's (the first
    layer's without one). -> (h, (load, selections) or None, the pairs its
    indexer picked or None)."""
    x = rms_norm(h, g["ln_attn"], arch.eps)
    kind = kind or kinds_of(arch)[0]
    a, count = _run(kind.forward(arch, *kind.args, w["attn"], g, x))
    h = h + a
    x = rms_norm(h, g["ln_mlp"], arch.eps)
    if "experts" in w:
        y, load, idx = expert_layer(arch, w, g["router"], x)
        return h + y, (load, idx), count
    return h + glu_by_parts(w["mlp"], x), None, count


def _leaf_shapes(arch: LMArch, vocab: int):
    """(base shapes, trained shapes) as pytrees of tuples. The trained
    subset is a list a layer: the two norms' gains, what the layer's kind
    trains and, in an expert layer, the router. The base is a list a layer
    too (`attn`, and `mlp` or `experts`, `shared`, `bias`) or, where the
    layers run as a scan (`stacked`), **stacked by kind**: `linear`,
    `latent`, `mlp`, `shared` and `bias` with a leading axis over their
    layers and `experts` with every expert layer's held experts along one
    axis, layer-major (the grouped product finds a layer's through its group
    sizes and nothing is sliced). A prediction module is one more expert
    block behind `eh`."""
    d, f, e = arch.hidden, arch.moe_intermediate, arch.held_experts
    kinds, n_exp = kinds_of(arch), arch.expert_layers
    mlp = {"gate_up": (d, 2 * arch.intermediate), "down": (arch.intermediate, d)}
    experts = lambda n: {"gate_up": (n * e, d, 2 * f),  # noqa: E731
                         "down": (n * e, f, d)}
    shared = {"gate_up": (d, 2 * f), "down": (f, d)}
    gains = lambda kind, routed: dict(  # noqa: E731
        {"ln_attn": (d,), "ln_mlp": (d,)}, **kind.trained(arch),
        **({"router": (arch.n_experts, d)} if routed else {}))
    blocks_g = [gains(kind, layer >= arch.dense_layers)
                for layer, kind in enumerate(kinds)]
    base = {"embed": (vocab, d), "head": (d, vocab)}
    trained = {"blocks": blocks_g, "final_norm": (d,)}
    if stacked(arch):
        over = lambda n, tree: {k: (n, *v) for k, v in tree.items()}  # noqa: E731
        rest = [kind for kind in kinds if kind is not LINEAR_KIND]
        base.update(
            linear=over(len(kinds) - len(rest), LINEAR_KIND.frozen(arch)),
            latent=over(len(rest), rest[0].frozen(arch)),
            mlp=over(arch.dense_layers, mlp), experts=experts(n_exp),
            shared=over(n_exp, shared), bias=(n_exp, arch.n_experts))
        return base, trained

    def routed(kind):
        w = {"attn": kind.frozen(arch), "experts": experts(1),
             "bias": (arch.n_experts,)}
        return dict(w, shared=shared) if arch.shared_experts else w

    base["blocks"] = [
        {"attn": kind.frozen(arch), "mlp": mlp} if layer < arch.dense_layers
        else routed(kind) for layer, kind in enumerate(kinds)]
    if arch.mtp_modules:
        base["mtp"] = {"eh": (2 * d, d), "block": routed(kinds[-1])}
        trained["mtp"] = {"hnorm": (d,), "enorm": (d,), "norm": (d,),
                          "block": gains(kinds[-1], True)}
    return base, trained


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
    kinds, first = kinds_of(arch), arch.dense_layers
    linear = [kind is LINEAR_KIND for kind in kinds]
    place = [sum(f == ok for f in linear[:i]) for i, ok in enumerate(linear)]
    other = next(kind for kind in kinds if kind is not LINEAR_KIND)
    stack = lambda name, layers: jnp.stack(  # noqa: E731
        [blocks_g[i][name] for i in layers])
    g_linear, g_other = (
        {n: stack(n, [i for i, ok in enumerate(linear) if ok == which])
         for n in kind.trained(arch)}
        for kind, which in ((LINEAR_KIND, True), (other, False)))
    at = lambda tree, i: jax.tree_util.tree_map(lambda t: t[i], tree)  # noqa: E731

    def attention(is_linear, ia, x):  # Python values, or a scan step's
        run = (lambda: _run(LINEAR_KIND.forward(
                   arch, at(base["linear"], ia), at(g_linear, ia), x))[0],
               lambda: _run(other.forward(
                   arch, *other.args, at(base["latent"], ia), at(g_other, ia),
                   x))[0])
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
                    *leaf, stacked=stacked(self.arch)), distinct)))

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
                                stacked=stacked(self.arch))

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
        kinds = kinds_of(arch)
        if stacked(arch):
            h, loads, idx = hybrid_layers(arch, base, p["blocks"],
                                          emb(tokens[:, :s]))
            _set_layer_gauges(arch, kinds)
            return (rms_norm(h, p["final_norm"], arch.eps) if normed else h,
                    None, (loads, idx), None)
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
                h2, seen, count = blk[kinds[-1]](
                    mb["block"], m["block"],
                    joined(h, m) if normed else jax.checkpoint(joined)(h, m))
                h_mtp = rms_norm(h2, m["norm"], arch.eps) if normed else h2
            routed.append(seen)
            picked.append(count)
        _set_layer_gauges(arch, kinds + kinds[-1:] * arch.mtp_modules)
        return (h_main, h_mtp, (jnp.stack([r[0] for r in routed]),
                                jnp.stack([r[1] for r in routed])),
                None if None in picked else jnp.stack(picked))

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
        if any(kind is not LATENT_KIND for kind in kinds_of(arch)):
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
    plan = expert_plan(arch, sequences * s * arch.experts_per_tok,
                       stacked(arch))
    given = plan.rows * jnp.clip(-(-jnp.sum(loads, -1) // plan.rows),
                                 0 if plan.blocks > 1 else 1, plan.blocks)
    if plan.filled:   # the front's rows whatever it holds, and its spare tile
        given = jnp.maximum(given, plan.front) + plan.spare
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
