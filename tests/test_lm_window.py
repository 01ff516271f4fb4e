"""The token model with grouped-query attention of two kinds
(hefl_tpu/models/lm.py at `mimo_v2_flash_tiny`: window 8 over 64 positions,
4 query heads over 1 and 2 KV heads, 8 experts of which 4 held, 2 window
layers to 1 global) against its plain reference
(benchmarks/reference/mimo_v2_flash.py) on seeded weights: logits, loss,
every trained leaf's gradient, the window's edge exactly, the sinks, the
rotated dims, K and V never repeated, the expert shares adding up, the
encrypted round with a ragged last row, and the two older models' traced
programs. No device or topology call at import time."""

from __future__ import annotations

import dataclasses
import hashlib
import importlib.util
import json
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hefl_tpu.ckks.keys import keygen
from hefl_tpu.ckks.packing import PackSpec
from hefl_tpu.data import iid_contiguous, make_dataset, stack_federated
from hefl_tpu.experiment import HEConfig
from hefl_tpu.fl import TrainConfig, decrypt_average, secure_fedavg_round
from hefl_tpu.models import create_model, frozen_base, lm, set_frozen_base
from hefl_tpu.obs import metrics as obs_metrics
from hefl_tpu.parallel import make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, POSITIONS = 50, 64
TINY = lm.PRESETS["mimo_v2_flash_tiny"]
LEAVES = (            # the trained subset, in the order JAX flattens it
    "['blocks'][0]['ln_attn']", "['blocks'][0]['ln_mlp']",
    "['blocks'][1]['ln_attn']", "['blocks'][1]['ln_mlp']",
    "['blocks'][1]['router']", "['blocks'][1]['sink']",
    "['blocks'][2]['ln_attn']", "['blocks'][2]['ln_mlp']",
    "['blocks'][2]['router']", "['blocks'][2]['sink']",
    "['blocks'][3]['ln_attn']", "['blocks'][3]['ln_mlp']",
    "['blocks'][3]['router']", "['final_norm']")


def _load(name):
    path = os.path.join(ROOT, "benchmarks", "reference", name + ".py")
    spec = importlib.util.spec_from_file_location("_ref_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _conf(arch: lm.LMArch, vocab: int = VOCAB) -> dict:
    """The reference's configuration keys for a system preset; the two
    lists longer than the layers held, as the published ones are."""
    layers = len(arch.layer_pattern)
    return dict(
        hidden_size=arch.hidden, num_attention_heads=arch.heads,
        head_dim=arch.qk_nope_head_dim + arch.qk_rope_head_dim,
        v_head_dim=arch.v_head_dim, num_key_value_heads=arch.kv_heads[0],
        swa_num_key_value_heads=arch.kv_heads[1],
        rope_theta=arch.rope_thetas[0], swa_rope_theta=arch.rope_thetas[1],
        add_full_attention_sink_bias=arch.sinks[0],
        add_swa_attention_sink_bias=arch.sinks[1],
        sliding_window=arch.window, attention_value_scale=arch.value_scale,
        partial_rotary_factor=0.334,
        hybrid_layer_pattern=list(arch.layer_pattern) + [1, 1],
        moe_layer_freq=[0] * arch.dense_layers + [1] * (arch.expert_layers + 2),
        intermediate_size=arch.intermediate,
        moe_intermediate_size=arch.moe_intermediate,
        n_routed_experts=arch.held_experts,
        num_experts_per_tok=arch.experts_per_tok, num_hidden_layers=layers,
        vocab_size=vocab, layernorm_epsilon=arch.eps,
        routed_scaling_factor=None, n_shared_experts=None,
        held=dict(router_width=arch.n_experts, first_expert=arch.held_start,
                  init_std=arch.init_std))


def _highest(fn, *a, **kw):
    with jax.default_matmul_precision("highest"):
        return fn(*a, **kw)


@pytest.fixture(scope="module")
def ref():
    return _load("mimo_v2_flash")


@pytest.fixture(scope="module")
def case(ref):
    """The tiny model, the reference's seeded weights with the gains moved
    off 1 and the sinks off 0 so they count, and both sides' logits, loss
    and gradients of one sequence."""
    conf = _conf(TINY)
    v = ref.init(3, conf)
    p = jax.tree_util.tree_map(
        lambda a: a * (1 + 0.1 * jax.random.normal(jax.random.key(a.size),
                                                   a.shape))
        if a.ndim == 1 else a, v["params"])
    for i, g in enumerate(p["blocks"]):
        if "sink" in g:   # about the log of the window: a third of the mass
            g["sink"] = 2.0 + jax.random.normal(jax.random.key(70 + i),
                                                g["sink"].shape)
    v = {"base": v["base"], "params": p}
    tokens = jax.random.randint(jax.random.key(0), (1, POSITIONS + 2), 0, VOCAB)
    module = lm.FrozenBaseLM(num_classes=VOCAB, arch=TINY, seed=3)
    (l_ref, (z_ref, none, aux)), g_ref = _highest(jax.value_and_grad(
        lambda q: ref.loss({"base": v["base"], "params": q}, tokens, conf),
        has_aux=True), p)
    assert none is None
    obs_metrics.gauge("model.window_attention_layers").set(0)
    z_sys, z_mtp, (loads, sel) = module.apply(v, tokens, routed=True)
    gauges = {k: obs_metrics.gauge(k).value for k in (
        "model.window_attention_layers", "model.fused_attention_layers",
        "model.sparse_attention_layers", "swa.block_pairs_over_window_pairs")}
    (l_sys, (ce, acc, counted)), g_sys = jax.value_and_grad(
        lambda q: module.loss({"base": v["base"], "params": q}, tokens),
        has_aux=True)(p)
    return types.SimpleNamespace(
        conf=conf, z=ref._sizes(conf), variables=v, tokens=tokens,
        module=module, l_ref=l_ref, z_ref=z_ref, aux=aux, g_ref=g_ref,
        z_sys=z_sys, z_mtp=z_mtp, loads=loads, sel=sel, l_sys=l_sys, ce=ce,
        acc=acc, counted=counted, g_sys=g_sys, gauges=gauges)


def test_logits_and_selections_match_reference(case):
    assert case.z_sys.shape == case.z_ref.shape == (1, POSITIONS, VOCAB)
    assert case.z_mtp is None          # one head: no prediction module
    # bfloat16 operands against float32: a few parts in a hundred of a logit
    assert float(jnp.max(jnp.abs(case.z_sys - case.z_ref))) < (
        0.05 * float(jnp.std(case.z_ref)))
    assert case.sel.shape == case.aux["experts"].shape == (3, POSITIONS, 2)
    assert float(jnp.mean((case.sel == case.aux["experts"]).astype(
        jnp.float32))) > 0.98
    assert np.asarray(case.loads).tolist() == np.asarray(
        case.aux["loads"]).tolist()


def test_loss_is_the_next_token_cross_entropy_alone(case, ref):
    assert abs(float(case.l_sys) - float(case.l_ref)) < 1e-4 * float(case.l_ref)
    assert float(case.ce) == float(case.l_sys)    # nothing added to it
    assert 0.0 <= float(case.acc) <= 1.0
    s = POSITIONS
    own = _highest(ref._ce, case.z_sys, case.tokens[:, 1:s + 1])
    assert float(case.l_sys) == pytest.approx(float(own), rel=1e-5)


@pytest.mark.parametrize("leaf", range(len(LEAVES)), ids=LEAVES)
def test_gradient_of_every_trained_leaf_matches_reference(case, leaf):
    flat_s = jax.tree_util.tree_leaves_with_path(case.g_sys)
    flat_r = jax.tree_util.tree_leaves(case.g_ref)
    assert [jax.tree_util.keystr(path) for path, _ in flat_s] == list(LEAVES)
    (_, a), b = flat_s[leaf], flat_r[leaf]
    assert a.shape == b.shape and float(jnp.linalg.norm(b)) > 0
    gap = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
    # the sinks' gradient is a sum of bfloat16 products (splash's `dsinks`)
    assert gap < (0.05 if "sink" in LEAVES[leaf] else 0.02), gap


def test_gauges_count_the_layers_and_the_windows_blocks(case, ref):
    g = case.gauges
    assert g["model.window_attention_layers"] == sum(TINY.layer_pattern) == 2
    assert g["model.fused_attention_layers"] == len(TINY.layer_pattern) == 4
    assert g["model.sparse_attention_layers"] == 0
    # 64 positions padded to one block of 128 x 128; the window allows
    # sum_t min(t + 1, 8) pairs of the padded 128 queries
    assert g["swa.block_pairs_over_window_pairs"] == pytest.approx(
        128 * 128 / ref.window_pairs(128, 8))
    # at the benchmark's size: two blocks of 128 a query block but the first
    _, ratio = lm.attention._grouped_kernel(8192, 8, 128, 128, True)
    assert ratio == pytest.approx(127 * 128 * 128 / ref.window_pairs(8192, 128))
    assert 1.99 < ratio < 2.0
    create_model("smallcnn", num_classes=2, input_shape=(16, 16, 3))
    assert obs_metrics.gauge("model.window_attention_layers").value == 0


def test_loads_carry_the_rows_given_without_an_indexer(case):
    """`loss`'s loads: the held experts' pairs, the marker, the rows the
    grouped product was given (every pair: this chip holds half the
    experts), and no picked pairs; `record_expert_load` sets the rows'
    gauge from them and leaves the indexer's alone."""
    counted = np.asarray(case.counted)
    held = TINY.held_experts
    assert counted.shape == (3, held + lm.model.COUNTED)
    assert np.array_equal(counted[:, :held], np.asarray(case.loads))
    pairs = POSITIONS * TINY.experts_per_tok
    assert counted[:, held:].tolist() == [[-1, pairs, 0, 0]] * 3
    obs_metrics.gauge("dsa.selected_share").set(12.5)
    lm.record_expert_load(counted)
    assert obs_metrics.gauge("moe.rows_over_held_pairs").value == pytest.approx(
        float(np.max(pairs / counted[:, :held].sum(-1))))
    assert obs_metrics.gauge("dsa.selected_share").value == 12.5
    assert obs_metrics.gauge("moe.load_max_over_mean").value >= 1.0
    # a chip that holds few of the experts is given blocks of its held pairs
    few = dataclasses.replace(TINY, n_experts=16, pair_block=32)
    loads = jnp.asarray([[3, 0, 40, 2], [0, 0, 0, 0]], jnp.int32)
    rows = np.asarray(lm.model._with_counts(few, loads, None, 1, POSITIONS + 2))
    assert rows[:, held:].tolist() == [[-1, 64, 0, 0], [-1, 0, 0, 0]]
    # ... the first `pair_front` of them in one product, a tile behind them
    ahead = dataclasses.replace(few, pair_front=64)
    loads = jnp.asarray([[3, 0, 40, 2], [0, 0, 0, 0], [60, 0, 40, 0]], jnp.int32)
    rows = np.asarray(lm.model._with_counts(ahead, loads, None, 1, POSITIONS + 2))
    assert rows[:, held + 1].tolist() == [64 + 512, 64 + 512, 128 + 512]


@pytest.mark.parametrize("front,lead", [(32, 4), (96, 4), (128, 4), (128, 1), (96, 1)])
@pytest.mark.parametrize("routing", ["spread", "two_held", "none_held"])
def test_the_front_of_the_held_pairs_is_the_blocks_layer(front, lead, routing,
                                                         monkeypatch):
    """`_held_front` takes the first `pair_front` sorted held pairs in one
    grouped product and leaves the rest to the blocks: the layer and its
    gradient with respect to the tokens and the pairs' weights are those of
    the blocks alone, whether the front holds every held pair, a part of
    them (the blocks take over behind it) or none, and whether the un-sort
    gathers every pair of a token or adds those behind the first in blocks
    (`SUM_LEAD` 1 of the 2 a token has here: two blocks of 48, the second
    padded, where every token has two held pairs)."""
    monkeypatch.setattr(lm.experts, "SUM_LEAD", lead)
    monkeypatch.setattr(lm.experts, "SUM_BLOCK", 48)
    few = dataclasses.replace(TINY, n_experts=16, pair_block=32)
    ahead = dataclasses.replace(few, pair_front=front)
    assert lm.experts.front_pairs(few, 128) == 0 and lm.experts.front_pairs(ahead, 128) == front
    w = jax.eval_shape(lm.FrozenBaseLM(num_classes=VOCAB, arch=few).init_base)
    w = jax.tree_util.tree_map(
        lambda a: 0.1 * jax.random.normal(jax.random.key(a.size), a.shape,
                                          a.dtype), w["blocks"][1]["experts"])
    x = jax.random.normal(jax.random.key(1), (POSITIONS, few.hidden), jnp.float32)
    if routing == "spread":      # 16 experts, 4 held: a pair in four is here
        idx = jnp.argsort(jax.random.uniform(jax.random.key(2),
                                             (POSITIONS, 16)))[:, :2]
    else:
        idx = jnp.tile(jnp.array([[1, 2] if routing == "two_held" else [9, 12]]),
                       (POSITIONS, 1))
    idx = idx.astype(jnp.int32)
    weights = jax.random.uniform(jax.random.key(3), (POSITIONS, 2), minval=0.2)
    ct = jax.random.normal(jax.random.key(4), x.shape)

    def layer(arch):
        (_, (y, load)), grads = jax.value_and_grad(
            lambda a, b: (lambda y, load: (jnp.sum(y * ct), (y, load)))(
                *lm.held_experts(arch, w, a, idx, b)),
            (0, 1), has_aux=True)(x, weights)
        return y, load, grads

    y, load, got = layer(ahead)
    want_y, want_load, want = layer(few)
    held_pairs = {"two_held": 128, "none_held": 0}.get(routing)
    assert held_pairs is None or int(jnp.sum(load)) == held_pairs
    assert jnp.array_equal(load, want_load)
    assert jnp.allclose(y, want_y, rtol=1e-5, atol=1e-6)
    for a, b in zip(got, want):
        assert float(jnp.max(jnp.abs(a - b))) <= 0.02 * float(jnp.max(jnp.abs(b)))


# --------------------------------------------------------------------------
# the grouped heads: window, sink, KV heads
# --------------------------------------------------------------------------


def _plain_heads(q, k, v, sinks, window, scale):
    """q [B, S, H, dq], k, v [B, S, G, .]: K and V repeated to H heads, a
    dense [S, S] mask from the two inequalities, the sink one more column
    of the softmax, dropped."""
    h, g, s = q.shape[2], k.shape[2], q.shape[1]
    k, v = jnp.repeat(k, h // g, 2), jnp.repeat(v, h // g, 2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    t, u = np.arange(s)[:, None], np.arange(s)[None, :]
    ok = (u <= t) & ((u > t - window) if window else True)
    sc = jnp.where(ok[None, None], sc, -jnp.inf)
    if sinks is not None:
        sc = jnp.concatenate([sc, jnp.broadcast_to(
            sinks[None, :, None, None], sc.shape[:3] + (1,))], -1)
    p = jax.nn.softmax(sc, -1)[..., :s]
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _qkv(s=POSITIONS, b=1, h=4, g=2, dq=24, dv=16, seed=21):
    ks = jax.random.split(jax.random.key(seed), 5)
    return (jax.random.normal(ks[0], (b, s, h, dq)),
            jax.random.normal(ks[1], (b, s, g, dq)),
            jax.random.normal(ks[2], (b, s, g, dv)),
            jax.random.normal(ks[3], (b, s, h, dv)),
            jax.random.normal(ks[4], (h,)))


@pytest.mark.parametrize("window,sink", [(0, False), (8, True), (8, False)],
                         ids=["global", "window_sink", "window"])
def test_grouped_heads_and_their_gradients_match_plain_attention(window, sink):
    q, k, v, ct, sinks = _qkv()
    sinks = sinks if sink else None
    scale = 24 ** -0.5
    want = _highest(_plain_heads, q, k, v, sinks, window, scale)
    got = lm.grouped_heads(q, k, v, sinks, window, 128, scale)
    assert got.shape == want.shape == (1, POSITIONS, 4, 16)
    assert got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got - want))) < 0.02 * float(
        jnp.max(jnp.abs(want)))
    wrt = (0, 1, 2, 3) if sink else (0, 1, 2)
    g_want = _highest(jax.grad(lambda *a: jnp.sum(_plain_heads(
        *a[:3], a[3] if sink else None, window, scale) * ct), wrt), q, k, v, sinks)
    g_got = jax.grad(lambda *a: jnp.sum(lm.grouped_heads(
        *a[:3], a[3] if sink else None, window, 128, scale) * ct), wrt)(
            q, k, v, sinks)
    for a, b in zip(g_got, g_want):
        assert a.shape == b.shape      # dk, dv as many heads wide as K, V
        assert float(jnp.max(jnp.abs(a - b))) < 0.03 * float(jnp.max(jnp.abs(b)))


def test_nothing_behind_the_window_moves_an_output():
    """`window_leak`: keys and values at positions <= t - window replaced,
    every output at positions >= t is bit for bit what it was; a key inside
    the window moves it."""
    q, k, v, _, sinks = _qkv(seed=22)
    t, window = 37, 8
    out = lm.grouped_heads(q, k, v, sinks, window, 128, 0.2)
    behind = (jnp.arange(POSITIONS) <= t - window)[None, :, None, None]
    noise = jax.random.normal(jax.random.key(5), k.shape)
    k2, v2 = jnp.where(behind, noise, k), jnp.where(behind, noise[..., :16], v)
    moved = lm.grouped_heads(q, k2, v2, sinks, window, 128, 0.2)
    assert jnp.array_equal(moved[:, t:], out[:, t:])
    assert not jnp.array_equal(moved[:, :t], out[:, :t])
    inside = (jnp.arange(POSITIONS) == t - window + 1)[None, :, None, None]
    nearer = lm.grouped_heads(q, jnp.where(inside, noise, k), v, sinks, window,
                              128, 0.2)
    assert not jnp.array_equal(nearer[:, t], out[:, t])
    assert jnp.array_equal(nearer[:, t + 1:], out[:, t + 1:])


@pytest.mark.parametrize("window", [7, 9])
def test_a_window_one_key_off_differs(window):
    q, k, v, _, sinks = _qkv(seed=23)
    right = lm.grouped_heads(q, k, v, sinks, 8, 128, 0.2)
    wrong = lm.grouped_heads(q, k, v, sinks, window, 128, 0.2)
    want = _highest(_plain_heads, q, k, v, sinks, window, 0.2)
    # the kernel computes the window it is given ...
    assert float(jnp.max(jnp.abs(wrong - want))) < 0.02 * float(
        jnp.max(jnp.abs(want)))
    # ... and one key more or less is far outside bfloat16's error, from the
    # first query that has a full window on
    far = jnp.linalg.norm(wrong[:, 8:] - right[:, 8:]) / jnp.linalg.norm(
        right[:, 8:])
    assert float(far) > 0.05
    assert jnp.array_equal(wrong[:, :7], right[:, :7])


def test_a_sink_of_minus_infinity_is_no_sink():
    q, k, v, ct, _ = _qkv(seed=24)
    none = lm.grouped_heads(q, k, v, None, 8, 128, 0.2)
    never = lm.grouped_heads(q, k, v, jnp.full((4,), -jnp.inf), 8, 128, 0.2)
    assert jnp.array_equal(never, none)
    some = lm.grouped_heads(q, k, v, jnp.zeros((4,)), 8, 128, 0.2)
    # a sink takes mass and gives no value: every output shrinks towards 0
    assert float(jnp.linalg.norm(some)) < float(jnp.linalg.norm(none))
    g = jax.grad(lambda b: jnp.sum(lm.grouped_heads(
        q, k, v, b, 8, 128, 0.2) * ct))(jnp.full((4,), -jnp.inf))
    assert not np.any(np.asarray(g))


def test_only_the_first_dims_of_a_head_turn(monkeypatch):
    """RoPE over the first `qk_rope_head_dim` dims: the others of q and k
    are the projections themselves at every position, the rotated ones are
    at position 0 alone; v is scaled."""
    seen = {}

    def heads(q, k, v, sinks, window, q_block, scale):
        seen.update(q=q, k=k, v=v, window=window, scale=scale, sinks=sinks)
        return jnp.zeros(q.shape[:3] + (v.shape[-1],), jnp.float32)

    monkeypatch.setattr(lm.attention, "grouped_heads", heads)
    base = jax.tree_util.tree_map(
        lambda s: 0.1 * jax.random.normal(jax.random.key(len(s)), s.shape, s.dtype),
        jax.eval_shape(lm.FrozenBaseLM(VOCAB, TINY).init_base))
    x = jax.random.normal(jax.random.key(1), (1, 16, TINY.hidden))
    dr, dq = TINY.qk_rope_head_dim, TINY.qk_nope_head_dim + TINY.qk_rope_head_dim
    for kind, layer in ((0, 0), (1, 1)):
        w = base["blocks"][layer]["attn"]
        g = {"sink": jnp.ones(4)} if kind else {}
        lm.grouped_attention(TINY, kind, w, g, x)
        kv = TINY.kv_heads[kind]
        assert seen["q"].shape == (1, 16, 4, dq)
        assert seen["k"].shape == (1, 16, kv, dq)      # never 4 heads wide
        assert seen["v"].shape == (1, 16, kv, TINY.v_head_dim)
        assert seen["window"] == (TINY.window if kind else 0)
        assert (seen["sinks"] is None) == (kind == 0)
        assert seen["scale"] == pytest.approx(dq ** -0.5)
        for name, heads_ in (("q", 4), ("k", kv)):
            plain = lm.common._mm(x, w[name]).reshape(1, 16, heads_, dq)
            assert jnp.array_equal(seen[name][..., dr:], plain[..., dr:])
            assert jnp.allclose(seen[name][:, 0], plain[:, 0], atol=1e-6)
            assert not jnp.allclose(seen[name][:, 5, :, :dr], plain[:, 5, :, :dr],
                                    atol=1e-3)
            # a rotation: the pair (i, i + dr / 2) keeps its length
            pair = lambda t: t[..., :dr // 2] ** 2 + t[..., dr // 2:dr] ** 2  # noqa: E731
            assert jnp.allclose(pair(seen[name]), pair(plain), rtol=1e-4,
                                atol=1e-6)
        assert jnp.allclose(seen["v"], TINY.value_scale * lm.common._mm(
            x, w["v"]).reshape(1, 16, kv, -1))
    # the two kinds turn at different bases
    a = lm.common.rope(x[..., :dr].reshape(1, 16, 1, dr), TINY.rope_thetas[0], None, False)
    b = lm.common.rope(x[..., :dr].reshape(1, 16, 1, dr), TINY.rope_thetas[1], None, False)
    assert not jnp.allclose(a, b, atol=1e-3)


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (tuple, list)) else (val,)):
                if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                    yield from _eqns(getattr(sub, "jaxpr", sub))


@pytest.mark.parametrize("kind", [0, 1], ids=["global", "window"])
def test_keys_and_values_are_never_repeated_to_the_query_heads(kind):
    """Every kernel of the layer's traced program, forward and gradient,
    takes ONE KV head's keys [S, dq] and values [S, dv] beside its
    H / G query heads' [H / G, S, dq], and the gradient's kernel gives
    dk and dv of that one head: no array of keys or values as many heads
    wide as the queries is there to give them."""
    arch = dataclasses.replace(TINY, v_head_dim=20)   # tell v from k and q
    base = jax.eval_shape(lm.FrozenBaseLM(VOCAB, arch).init_base)
    w = base["blocks"][kind]["attn"]
    g = {"sink": jnp.zeros(4)} if kind else {}
    x = jax.ShapeDtypeStruct((1, POSITIONS, arch.hidden), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda x, w, g: jnp.sum(lm.grouped_attention(arch, kind, w, g, x))))(
            x, w, g)
    kv, h, dq, dv = arch.kv_heads[kind], arch.heads, 24, 20
    calls = [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "pallas_call"]
    # forward, and the gradient in one kernel (global) or two (window)
    assert len(calls) == (3 if kind else 2)
    for eqn in calls:
        ins = [tuple(v.aval.shape) for v in eqn.invars]
        outs = [tuple(v.aval.shape) for v in eqn.outvars]
        keys = [s for s in ins if s[-2:] == (128, dq)]
        values = [s for s in ins if s[-2:] == (128, dv)]
        k_op, q_op = sorted(keys, key=len)                  # k, and q
        assert len(q_op) == len(k_op) + 1 and q_op[-3] == h // kv
        assert h not in k_op[:-2] or kv == h
        assert min(len(s) for s in values) == len(k_op)     # v (o, do: as q)
        assert all(len(s) == len(k_op) for s in outs
                   if s[-2:] == (128, dv) and eqn is not calls[0])  # dv: one head
    # the loop over KV heads carries K and V as they are made: [G, B, S, d]
    loops = [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "scan"
             and e.params["length"] == kv
             and any(tuple(v.aval.shape) == (kv, 1, 128, dq) for v in e.invars)]
    assert len(loops) >= 2                                  # forward, gradient


# --------------------------------------------------------------------------
# the expert layer's shares, the counts, the files
# --------------------------------------------------------------------------


def test_the_two_shares_add_up_to_the_uncut_layer(ref):
    """Experts 0-3 and 4-7, nothing counted twice since no expert is shared:
    the two shares' parts sum to the uncut reference's layer."""
    n, held = TINY.n_experts, TINY.held_experts
    whole = dataclasses.replace(TINY, held_start=0, held_experts=n)
    z = ref._sizes(_conf(whole))
    w = ref.init(5, _conf(whole))["base"]["blocks"][1]
    assert "shared" not in w
    router = 0.5 * jax.random.normal(jax.random.key(9), (n, TINY.hidden))
    x = jax.random.normal(jax.random.key(4), (40, TINY.hidden), jnp.float32)
    mm = ref._Products(None)
    idx_r, w_r = _highest(ref._route, z, router, w["bias"], x)
    uncut, _ = _highest(ref._experts, z, w["experts"], x, idx_r, w_r, mm)
    idx, weights = lm.route(whole, router, w["bias"], x)
    assert jnp.array_equal(idx, idx_r)
    assert jnp.allclose(jnp.sum(weights, -1), 1.0, atol=1e-6)   # no factor
    parts, pairs = [], 0
    for start in range(0, n, held):
        share = dataclasses.replace(TINY, held_start=start)
        mine = {k: v[start:start + held] for k, v in w["experts"].items()}
        y, load = lm.held_experts(share, mine, x, idx, weights)
        parts.append(y)
        pairs += int(jnp.sum(load))
    assert len(parts) == 2 and pairs == 40 * TINY.experts_per_tok
    assert float(jnp.max(jnp.abs(sum(parts) - uncut))) < 0.02 * float(
        jnp.std(uncut))
    # and the layer is its routed part alone
    flat = x.reshape(1, 40, -1)
    y, _, _ = lm.experts.expert_layer(TINY, {"experts": {
        k: v[:held] for k, v in w["experts"].items()}, "bias": w["bias"]},
        router, flat)
    assert jnp.allclose(y[0], parts[0], rtol=1e-5, atol=1e-6)


def test_forward_flops_are_the_models_own_count(ref):
    """ISSUE 34's arithmetic at the published widths and 8,192 positions:
    2.384 GFLOP a token."""
    conf = json.load(open(os.path.join(
        ROOT, "benchmarks", "configs", "mimo-v2-flash-l7e16.json")))
    parts = ref.forward_flops(conf, 8192)
    assert ref.window_pairs(8192, 128) == 1_040_448
    assert parts["global_projections"] == 2 * 89_128_960
    assert parts["window_projections"] == 2 * 94_371_840
    assert parts["global_attend"] == pytest.approx(2 * 64 * 320 * 8193 / 2)
    assert parts["window_attend"] == pytest.approx(
        2 * 64 * 320 * 1_040_448 / 8192)
    assert parts["dense_mlp"] == 2 * 201_326_592
    assert parts["held_experts"] == pytest.approx(8 * 16 / 256 * 6 * 4096 * 2048)
    assert parts["router"] == 2 * 256 * 4096
    assert parts["head"] == 2 * 4096 * 19072
    layer0 = (parts["global_projections"] + parts["global_attend"]
              + parts["dense_mlp"])
    window = (parts["window_projections"] + parts["window_attend"]
              + parts["held_experts"] + parts["router"])
    assert layer0 == pytest.approx(748.8e6, rel=1e-3)
    assert window == pytest.approx(221.2e6, rel=1e-3)
    assert parts["total"] == pytest.approx(2.384e9, rel=1e-3)
    # attention is 70% of it; unskipped, the five windows would add 35%
    attention = (2 * parts["global_projections"] + 5 * parts["window_projections"]
                 + 2 * parts["global_attend"] + 5 * parts["window_attend"])
    assert attention / parts["total"] == pytest.approx(0.70, abs=0.01)
    assert 5 * (parts["global_attend"] - parts["window_attend"]) / parts[
        "total"] == pytest.approx(0.34, abs=0.01)
    # a round of the cell: 2 clients x (2 x 1 trained + 1 validation) sequences
    assert 6 * parts["total"] * 8192 == pytest.approx(117.2e12, rel=1e-3)


def test_published_preset_is_the_configuration_file(ref):
    conf = json.load(open(os.path.join(
        ROOT, "benchmarks", "configs", "mimo-v2-flash-l7e16.json")))
    arch = lm.PRESETS["mimo_v2_flash"]
    ours = _conf(arch, conf["vocab_size"])
    layers = ours["num_hidden_layers"]
    for key in ("hybrid_layer_pattern", "moe_layer_freq"):   # kept whole
        assert conf[key][:layers] == ours.pop(key)[:layers] and len(conf[key]) == 48
    assert int(conf["partial_rotary_factor"] * conf["head_dim"]) == (
        arch.qk_rope_head_dim) == 64
    for key, value in ours.items():
        assert conf[key] == value, key
    assert conf["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    assert conf["published"]["num_hidden_layers"] == 48 and "env" not in conf
    module, params = create_model("mimo_v2_flash", seed=1)
    assert module.num_classes == conf["vocab_size"] == 19072
    count = lambda t: sum(int(np.prod(a.shape))  # noqa: E731
                          for a in jax.tree_util.tree_leaves(t))
    trained = count(jax.eval_shape(module.init_trained))
    assert trained == conf["deployment"]["trained_parameters"] == 6_353_216
    assert trained == 6 * 256 * 4096 + 15 * 4096 + 5 * 64
    assert -(-trained // 4096) == conf["deployment"]["ciphertexts_a_client"] == 1552
    assert trained % 4096 == 320              # a ragged last row
    base = jax.eval_shape(module.init_base)
    assert count(base) == 3_423_600_640 + 6 * 256
    assert count(base["blocks"][0]["attn"]) == 89_128_960       # global
    assert count(base["blocks"][1]["attn"]) == 94_371_840       # window
    assert base["blocks"][5]["attn"]["k"].shape == (4096, 4 * 192)
    assert base["blocks"][6]["attn"]["v"].shape == (4096, 8 * 128)
    assert "mtp" not in base and "shared" not in base["blocks"][1]
    # sinks start at 0, in the window layers alone
    sinks = [g["sink"] for g in params["blocks"] if "sink" in g]
    assert len(sinks) == 5 and not any(np.any(np.asarray(s)) for s in sinks)
    assert ["sink" in g for g in params["blocks"]] == [
        bool(k) for k in arch.layer_pattern]
    # the reference makes the same leaves
    twin = jax.eval_shape(lambda: ref.init(0, conf))
    for ours_, theirs in ((base, twin["base"]),
                          (jax.eval_shape(module.init_trained), twin["params"])):
        assert (jax.tree_util.tree_structure(theirs)
                == jax.tree_util.tree_structure(ours_))
        assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in zip(
            jax.tree_util.tree_leaves(theirs), jax.tree_util.tree_leaves(ours_)))


def test_reference_imports_nothing_of_the_program():
    text = open(os.path.join(ROOT, "benchmarks", "reference",
                             "mimo_v2_flash.py")).read()
    assert not re.search(r"^\s*(from|import) hefl_tpu", text, re.M)


# --------------------------------------------------------------------------
# the encrypted round
# --------------------------------------------------------------------------


def test_encrypted_round_with_a_ragged_last_row_is_the_plain_mean():
    module, params = create_model("mimo_v2_flash_tiny", num_classes=64, seed=5)
    (x, y), _, _ = make_dataset("tokens-v64-s64", seed=5, n_train=4, n_test=1)
    xs, ys = stack_federated(x, y, iid_contiguous(len(y), 2))
    cfg = TrainConfig(epochs=1, batch_size=1, num_classes=64, val_fraction=0.5,
                      lr_decay=0.0, augment=False)
    base = frozen_base(module)
    before = jax.tree_util.tree_map(np.asarray, base)
    ctx = HEConfig(n=256).build()
    sk, pk = keygen(ctx, jax.random.key(1))
    ct, mets, overflow, plain = secure_fedavg_round(
        module, cfg, make_mesh(2), ctx, pk, params, jnp.asarray(xs),
        jnp.asarray(ys), jax.random.key(2), with_plain_reference=True)
    spec = PackSpec.for_params(params, ctx.n)
    total = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert spec.total == total == 3 * 8 * 64 + 9 * 64 + 2 * 4
    assert total % ctx.n == 72          # the last row is ragged
    assert ct.c0.shape[0] == spec.n_ct == -(-total // ctx.n) == 9
    avg = decrypt_average(ctx, sk, ct, 2, spec)
    assert jax.tree_util.tree_structure(avg) == jax.tree_util.tree_structure(params)
    host = lambda t: [np.asarray(a) for a in jax.tree_util.tree_leaves(t)]  # noqa: E731
    for a, b, p0 in zip(host(avg), host(plain), host(params)):
        assert float(np.max(np.abs(a - b))) < 5e-5
        assert float(np.max(np.abs(b - p0))) > 0         # every leaf trained,
    moved = [np.asarray(g["sink"]) for g in plain["blocks"] if "sink" in g]
    assert len(moved) == 2 and all(np.all(m != 0) for m in moved)  # the sinks too
    assert int(np.sum(np.asarray(overflow))) == 0
    assert np.all(np.isfinite(np.asarray(mets)))
    for a, b in zip(jax.tree_util.tree_leaves(before),
                    jax.tree_util.tree_leaves(frozen_base(module))):
        assert np.array_equal(a, np.asarray(b))            # bit for bit
    set_frozen_base(module, None)


# --------------------------------------------------------------------------
# the two older models
# --------------------------------------------------------------------------


def _program_digest(closed) -> str:
    """A digest of a traced program that does not depend on which of its
    sub-programs JAX's caches happened to share: every equation in order
    (primitive, operand and result types, parameters), sub-programs walked
    in place."""
    h = hashlib.sha256()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            h.update(eqn.primitive.name.encode())
            h.update(str([str(v.aval) for v in eqn.invars]).encode())
            h.update(str([str(v.aval) for v in eqn.outvars]).encode())
            for key in sorted(eqn.params):
                val = eqn.params[key]
                subs = [s for s in (val if isinstance(val, (tuple, list))
                                    else (val,))
                        if hasattr(s, "eqns") or hasattr(s, "jaxpr")]
                if subs:
                    for s in subs:
                        walk(getattr(s, "jaxpr", s))
                else:
                    h.update(key.encode())
                    h.update(re.sub(r" at 0x[0-9a-f]+", "", str(val)).encode())

    walk(closed.jaxpr)
    return h.hexdigest()[:16]


@pytest.mark.parametrize("name,positions,digest", [
    ("joyai_llm_flash_tiny", 24, "cf307f770ca7df44"),
    ("deepseek_v32_tiny", 40, "5d6083e2be9bfcf7"),
    ("mimo_v2_flash_tiny", 64, "8b45fb77abb684e0"),
    ("ling_3_flash_tiny", 40, "d7e896098760a5ce"),
])
def test_the_older_models_traced_programs_are_unchanged(name, positions, digest):
    """The loss and its gradient of the two DeepSeek-V3-shaped presets,
    traced: deepseek's digest is PR 42's own, read from its tree (its
    attention layers pack and name their selection and their checkpoint
    keeps it and the kernel's output and log-sum-exp, `lm.model._kept`: the one
    change to its program since commit c298e53, PR 32: 9181a7b43929e507
    before); the three other digests stood through that change, which is
    the proof that `_kept` answers those models as before. joyai's is PR
    40's, whose
    one-block expert layer (`_held_whole`) is the one change to its program
    since (3a6633ee86b7eed0 before). mimo's is that of commit 0cbd328 (PR
    40), read there before a fourth model came out of the class (PR 41), and
    ling's is PR 41's own. A change of the installed JAX moves them too:
    then read them again from those commits."""
    module = lm.FrozenBaseLM(num_classes=50, arch=lm.PRESETS[name], seed=3)
    p, base = jax.eval_shape(module.init_trained), jax.eval_shape(module.init_base)
    tokens = jax.ShapeDtypeStruct((2, positions + 2), jnp.int32)
    vg = jax.value_and_grad(
        lambda p, base, t: module.loss({"params": p, "base": base}, t),
        has_aux=True)
    assert _program_digest(jax.make_jaxpr(vg)(p, base, tokens)) == digest
    if lm.PRESETS[name].mtp_modules:
        assert "mtp" in base and "shared" in base["blocks"][1]
