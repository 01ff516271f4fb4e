"""The token models with a frozen base (hefl_tpu/models/lm/) against their
plain references (benchmarks/reference/joyai_llm_flash.py, deepseek_v32.py)
on seeded weights at a small size, the share of a stated deployment tied to
the uncut layer, routing under a planted imbalance, the indexer's selection
and attention over it, token data, and the encrypted round of the trained
subset alone. No device or topology call at import time."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hefl_tpu.ckks.keys import keygen
from hefl_tpu.ckks.packing import PackSpec
from hefl_tpu.data import iid_contiguous, make_dataset, stack_federated
from hefl_tpu.experiment import ExperimentConfig, HEConfig, run_experiment
from hefl_tpu.fl import TrainConfig, decrypt_average, secure_fedavg_round
from hefl_tpu.models import create_model, frozen_base, lm, set_frozen_base
from hefl_tpu.parallel import make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 50


def _load(path):
    name = "_ref_" + os.path.basename(path)[:-3]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REFERENCES = {"joyai_llm_flash_tiny": "joyai_llm_flash.py",
              "deepseek_v32_tiny": "deepseek_v32.py"}


def _conf(arch: lm.LMArch, vocab: int = VOCAB) -> dict:
    """The reference's configuration keys for a system preset."""
    conf = dict(
        hidden_size=arch.hidden, num_attention_heads=arch.heads,
        q_lora_rank=arch.q_lora_rank, kv_lora_rank=arch.kv_lora_rank,
        qk_nope_head_dim=arch.qk_nope_head_dim,
        qk_rope_head_dim=arch.qk_rope_head_dim, v_head_dim=arch.v_head_dim,
        intermediate_size=arch.intermediate,
        moe_intermediate_size=arch.moe_intermediate,
        n_routed_experts=arch.held_experts,
        num_experts_per_tok=arch.experts_per_tok,
        first_k_dense_replace=arch.dense_layers,
        num_hidden_layers=arch.dense_layers + arch.expert_layers,
        vocab_size=vocab, rms_norm_eps=arch.eps, rope_theta=arch.rope_theta,
        routed_scaling_factor=arch.routed_scaling,
        held=dict(router_width=arch.n_experts, first_expert=arch.held_start,
                  mtp_loss_weight=arch.mtp_weight, init_std=arch.init_std))
    if arch.index_topk:
        factor, positions, fast, slow, all_dim = arch.rope_scaling
        conf.update(
            n_group=arch.n_group, topk_group=arch.topk_group,
            index_n_heads=arch.index_heads, index_head_dim=arch.index_head_dim,
            index_topk=arch.index_topk,
            rope_scaling=dict(
                beta_fast=fast, beta_slow=slow, factor=factor, mscale=1,
                mscale_all_dim=all_dim, type="yarn",
                original_max_position_embeddings=positions))
    return conf


TINY = lm.PRESETS["joyai_llm_flash_tiny"]
SPARSE = lm.PRESETS["deepseek_v32_tiny"]
POSITIONS = {"joyai_llm_flash_tiny": 24,
             "deepseek_v32_tiny": 40}      # five times the tiny indexer's top 8


@pytest.fixture(scope="module", params=sorted(REFERENCES))
def case(request):
    """A tiny model, its reference, and the reference's seeded weights with
    the gains moved off 1 so they count."""
    arch = lm.PRESETS[request.param]
    ref = _load(os.path.join(ROOT, "benchmarks", "reference",
                             REFERENCES[request.param]))
    conf = _conf(arch)
    v = ref.init(3, conf)
    p = jax.tree_util.tree_map(
        lambda a: a * (1 + 0.1 * jax.random.normal(jax.random.key(a.size),
                                                   a.shape))
        if a.ndim == 1 else a, v["params"])
    positions = POSITIONS[request.param]
    tokens = jax.random.randint(jax.random.key(0), (2, positions + 2), 0, VOCAB)
    # both references' layers under one signature: (z, w, g, x) -> array
    z = ref._sizes(conf)
    if arch.index_topk:
        mm = ref._Products(None)
        attention = lambda w, g, x: ref._attention(  # noqa: E731
            z, w, g, x, mm, yarn=z["yarn"])[0]
        blk = lambda w, g, h: ref._block(  # noqa: E731
            z, w, g, h, mm, None, None, None, {"yarn": z["yarn"]}, True)
    else:
        mm = lambda a, b: a @ b.astype(jnp.float32)  # noqa: E731
        attention = lambda w, g, x: ref._attention(z, w, g, x, mm)  # noqa: E731
        blk = lambda w, g, h: ref._block(z, w, g, h, mm, None, None, None)  # noqa: E731
    return types.SimpleNamespace(
        name=request.param, arch=arch, ref=ref, conf=conf, z=z, mm=mm,
        positions=positions,
        variables={"base": v["base"], "params": p}, tokens=tokens,
        attention=attention, block=blk)


def _highest(fn, *a, **kw):
    with jax.default_matmul_precision("highest"):
        return fn(*a, **kw)


def test_latent_attention_block_matches_reference(case):
    v, arch = case.variables, case.arch
    w, g = v["base"]["blocks"][0]["attn"], v["params"]["blocks"][0]
    x = jax.random.normal(jax.random.key(1), (2, case.positions, arch.hidden),
                          jnp.float32)
    want = _highest(case.attention, w, g, x)
    got = lm.latent_attention(arch, w, g, x)
    assert got.shape == want.shape == (2, case.positions, arch.hidden)
    same = jnp.ones((2, case.positions), bool)
    if arch.index_topk:
        # compared where both indexers select the same 8 keys (bfloat16
        # products against float32 rank a pair at the edge differently)
        c_q = lm.common.rms_norm(lm.common._mm(x, w["q_a"]), g["q_norm"], arch.eps)
        ours = lm.select_keys(arch, w["index"], x, c_q)
        theirs = _highest(case.ref._attention, case.z, w, g, x, case.mm,
                          yarn=case.z["yarn"])[1]
        same = jnp.all(ours == theirs, -1)
        assert float(jnp.mean(same)) > 0.9
    # bfloat16 operands against float32: a few parts in a thousand
    assert float(jnp.max(jnp.where(same[..., None], jnp.abs(got - want), 0))
                 ) < 0.02 * float(jnp.std(want)) + 1e-4
    # causal: a later token does not move an earlier position
    x2 = x.at[:, -1].add(1.0)
    assert jnp.array_equal(lm.latent_attention(arch, w, g, x2)[:, :-1],
                           got[:, :-1])


def _plain_attention(q, k, v):
    """The reference's attention (`ref._attention`'s `attend`) on q, k, v:
    float32 scores, mask, softmax and products, one block."""
    s = q.shape[1]
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    ok = np.arange(s)[:, None] >= np.arange(s)[None, :]
    p = jax.nn.softmax(jnp.where(ok[None, None], sc, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _qkv(s, seed=11, b=2, h=2, dq=24, dv=16):
    ks = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(ks[0], (b, s, h, dq)),
            jax.random.normal(ks[1], (b, s, h, dq)),
            jax.random.normal(ks[2], (b, s, h, dv)),
            jax.random.normal(ks[3], (b, s, h, dv)))


def test_fused_attention_and_its_gradients_match_plain_attention():
    # three key blocks of 128, dq != dv, 300 positions padded to 384
    q, k, v, ct = _qkv(300)
    want = _highest(_plain_attention, q, k, v)
    got = lm.causal_attention(q, k, v, 128)
    assert got.shape == want.shape == (2, 300, 2, 16) and got.dtype == jnp.float32
    # bfloat16 operands and a bfloat16 output against float32: a few parts
    # in a thousand of the largest entry (the first rows average few keys)
    assert float(jnp.max(jnp.abs(got - want))) < 0.01 * float(jnp.max(jnp.abs(want)))
    g_want = _highest(jax.grad(
        lambda *a: jnp.sum(_plain_attention(*a) * ct), (0, 1, 2)), q, k, v)
    g_got = jax.grad(
        lambda *a: jnp.sum(lm.causal_attention(*a, 128) * ct), (0, 1, 2))(q, k, v)
    for a, b in zip(g_got, g_want):
        assert a.shape == b.shape
        assert float(jnp.max(jnp.abs(a - b))) < 0.02 * float(jnp.max(jnp.abs(b)))


def test_padded_positions_move_no_output_and_receive_no_gradient():
    # 300 positions are padded to 384 inside; 384 positions are not padded.
    # Whatever lies behind position 299 must read as the padding does.
    q, k, v, ct = _qkv(384, seed=12)
    cut = lambda *a: tuple(t[:, :300] for t in a)  # noqa: E731
    got = lm.causal_attention(*cut(q, k, v), 128)
    assert jnp.array_equal(got, lm.causal_attention(q, k, v, 128)[:, :300])
    loss = lambda *a: jnp.sum(lm.causal_attention(*a, 128)[:, :300]  # noqa: E731
                              * ct[:, :300])
    g_pad = jax.grad(loss, (0, 1, 2))(*cut(q, k, v))
    g_all = jax.grad(loss, (0, 1, 2))(q, k, v)
    for a, b in zip(g_pad, g_all):
        assert jnp.array_equal(a, b[:, :300])
        assert not np.any(np.asarray(b[:, 300:]))
    # one kernel object a (padded length, heads, block): built once
    before = lm.attention._attention_kernel.cache_info().misses
    lm.causal_attention(*cut(q, k, v), 128)
    assert lm.attention._attention_kernel.cache_info().misses == before


def test_gauge_counts_the_attention_layers_that_took_the_kernel(case):
    from hefl_tpu.obs import metrics as obs_metrics

    v, tokens, arch = case.variables, case.tokens, case.arch
    gauge = obs_metrics.gauge("model.fused_attention_layers")
    gauge.set(0)
    module = lm.JoyAIFlash(num_classes=VOCAB, arch=arch, seed=3)
    jax.eval_shape(lambda p: module.apply({"base": v["base"], "params": p},
                                          tokens), v["params"])
    # 1 dense + 2 expert layers + the prediction module
    assert gauge.value == arch.dense_layers + arch.expert_layers + 1 == 4
    # ... all of them over an indexer's selection, where the model has one
    assert obs_metrics.gauge("model.sparse_attention_layers").value == (
        4 if arch.index_topk else 0)
    # ... whose selection and whose kernel's output a gradient would keep
    for kept in ("selection", "attention"):
        assert obs_metrics.gauge(f"dsa.kept_{kept}_layers").value == (
            4 if arch.index_topk else 0)
    create_model("smallcnn", num_classes=2, input_shape=(16, 16, 3))
    assert obs_metrics.snapshot()["model.fused_attention_layers"] == 0
    assert obs_metrics.snapshot()["dsa.kept_selection_layers"] == 0


def test_expert_block_matches_reference(case):
    v, arch = case.variables, case.arch
    w, g = v["base"]["blocks"][1], v["params"]["blocks"][1]
    h = jax.random.normal(jax.random.key(2), (2, case.positions, arch.hidden),
                          jnp.float32)
    want, aux = _highest(case.block, w, g, h)
    got, (load, idx), picked = lm.model.block(arch, w, g, h)
    # the float32 router agrees (but for a token whose attention read a key
    # the two indexers rank differently)
    assert float(jnp.mean((idx == aux["experts"]).astype(jnp.float32))) >= (
        0.97 if arch.index_topk else 1.0)
    if not arch.index_topk:
        assert np.asarray(load).tolist() == np.asarray(aux["loads"]).tolist()
        assert picked is None
    else:
        assert int(picked) == 2 * case.ref.selected_pairs(case.positions,
                                                          arch.index_topk)
    assert float(jnp.max(jnp.abs(got - want))) < 0.03 * float(jnp.std(want))


def test_whole_model_logits_loss_and_gradients_match_reference(case):
    ref, conf, v, tokens = case.ref, case.conf, case.variables, case.tokens
    module = lm.FrozenBaseLM(num_classes=VOCAB, arch=case.arch, seed=3)
    (l_ref, (z1, z2, aux)), g_ref = _highest(jax.value_and_grad(
        lambda q: ref.loss({"base": v["base"], "params": q}, tokens, conf),
        has_aux=True), v["params"])
    s1, s2, (loads, sel) = module.apply(v, tokens, routed=True)
    (l_sys, (ce, acc, _)), g_sys = jax.value_and_grad(
        lambda q: module.loss({"base": v["base"], "params": q}, tokens),
        has_aux=True)(v["params"])
    assert s1.shape == s2.shape == (2, case.positions, VOCAB)
    scale = float(jnp.std(z1))
    assert float(jnp.max(jnp.abs(s1 - z1))) < (
        0.3 if case.arch.index_topk else 0.05) * scale
    assert float(jnp.max(jnp.abs(s2 - z2))) < 0.3 * float(jnp.std(z2))
    assert float(jnp.mean((sel == aux["experts"]).astype(jnp.float32))) > 0.98
    assert abs(float(l_sys) - float(l_ref)) < 1e-4 * float(l_ref)
    assert 0.0 <= float(acc) <= 1.0 and float(ce) < float(l_sys)
    # every trained leaf: the main path's within a percent, the prediction
    # module's (a tenth of the loss, one routing flip away) within a third
    flat_s = jax.tree_util.tree_leaves_with_path(g_sys)
    flat_r = jax.tree_util.tree_leaves(g_ref)
    assert len(flat_s) == len(flat_r) == 3 * 4 + 2 + 1 + 3 + 5
    for (path, a), b in zip(flat_s, flat_r):
        gap = float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-12))
        limit = 0.4 if "mtp" in jax.tree_util.keystr(path) else (
            0.1 if case.arch.index_topk else 0.02)
        assert gap < limit, (jax.tree_util.keystr(path), gap)
    if case.arch.index_topk:
        # the selections: each layer's, by the system's indexer on what the
        # reference's indexer saw, are the reference's but for pairs at the
        # edge (bfloat16 products against float32)
        xs, c_qs = _highest(
            lambda: ref.forward(v, tokens, conf, keep_inputs=True))[2]["index_in"]
        layers = v["base"]["blocks"] + [v["base"]["mtp"]["block"]]
        for k, layer in enumerate(layers):
            got = lm.select_keys(case.arch, layer["attn"]["index"], xs[k], c_qs[k])
            want = aux["picked"][k]
            assert int(jnp.sum(got)) == int(jnp.sum(want))
            assert float(jnp.sum(got & want) / jnp.sum(want)) > 0.98


def test_the_shares_and_the_shared_expert_add_up_to_the_uncut_layer(case):
    """The share test: the routed parts that every share gives (each
    `held_start` in turn: 8 experts in two shares of 4, or 16 experts under
    group-limited routing in four), with the shared expert counted once, are
    the uncut reference's layer."""
    arch, ref = case.arch, case.ref
    n, held = arch.n_experts, arch.held_experts
    whole = dataclasses.replace(arch, held_start=0, held_experts=n)
    z = ref._sizes(_conf(whole))
    w = ref.init(5, _conf(whole))["base"]["blocks"][1]
    router = 0.5 * jax.random.normal(jax.random.key(9), (n, arch.hidden))
    x = jax.random.normal(jax.random.key(4), (40, arch.hidden), jnp.float32)
    idx_r, w_r = _highest(ref._route, z, router, w["bias"], x, None)
    routed_ref, _ = _highest(ref._experts, z, w["experts"], x, idx_r, w_r,
                             case.mm, None, None)
    uncut = routed_ref + _highest(ref._glu, w["shared"], x, case.mm)
    idx, weights = lm.route(whole, router, w["bias"], x)
    assert jnp.array_equal(idx, idx_r)
    parts, pairs = [], 0
    for start in range(0, n, held):
        share = dataclasses.replace(arch, held_start=start)
        mine = {k: v[start:start + held] for k, v in w["experts"].items()}
        y, load = lm.held_experts(share, mine, x, idx, weights)
        parts.append(y)
        pairs += int(jnp.sum(load))
    assert len(parts) == n // held
    assert pairs == 40 * arch.experts_per_tok       # every pair, once
    total = sum(parts) + lm.experts.glu(w["shared"], x)
    assert float(jnp.max(jnp.abs(total - uncut))) < 0.02 * float(jnp.std(uncut))


def test_planted_imbalance_drops_no_pair(case):
    """A router that sends every token to the same two held experts: every
    pair is computed (no capacity; in blocks of the held pairs where the
    chip holds few of the experts), the layer is still the reference's, and
    so is its gradient with respect to the tokens and the weights."""
    arch, ref, v = case.arch, case.ref, case.variables
    w = v["base"]["blocks"][1]
    bias = jnp.zeros(arch.n_experts).at[jnp.array([1, 2])].set(10.0)
    router = 0.02 * jax.random.normal(jax.random.key(6),
                                      (arch.n_experts, arch.hidden))
    x = jax.random.normal(jax.random.key(7), (64, arch.hidden), jnp.float32)
    idx, weights = lm.route(arch, router, bias, x)
    assert set(np.asarray(idx).ravel().tolist()) == {1, 2}
    blocks, rows = lm.experts.pair_blocks(arch, idx.size)
    assert (blocks, rows) == ((4, 32) if arch.index_topk else (1, 128))
    y, load = lm.held_experts(arch, w["experts"], x, idx, weights)
    assert np.asarray(load).tolist() == [0, 64, 64, 0]
    want, _ = _highest(ref._experts, case.z, w["experts"], x, idx, weights,
                       case.mm, None, None)
    assert float(jnp.max(jnp.abs(y - want))) < 0.02 * float(jnp.std(want))
    ct = jax.random.normal(jax.random.key(8), y.shape)
    got = jax.grad(lambda a, b: jnp.sum(lm.held_experts(
        arch, w["experts"], a, idx, b)[0] * ct), (0, 1))(x, weights)
    ref_g = _highest(jax.grad(lambda a, b: jnp.sum(ref._experts(
        case.z, w["experts"], a, idx, b, case.mm, None, None)[0] * ct), (0, 1)),
        x, weights)
    for a, b in zip(got, ref_g):
        assert float(jnp.max(jnp.abs(a - b))) < 0.05 * float(jnp.max(jnp.abs(b)))


# joyai's shares at the tests' widths: 8 selections a token, half of the
# experts held, 4 held pairs a token on average
WIDE = dataclasses.replace(TINY, n_experts=16, held_experts=8, experts_per_tok=8)


def _planted_selections(kind: str, tokens: int = 40):
    """idx int32[tokens, 8], distinct in a token: how many of a token's
    eight experts are held (0-7 of 16) is planted, the slots shuffled."""
    rng = np.random.default_rng(12)
    held = {"uniform": None,
            "all_or_none": np.where(np.arange(tokens) % 2, 8, 0),
            "none_held": np.zeros(tokens, int),
            "all_held": np.full(tokens, 8),
            "most_held": rng.integers(3, 9, tokens)}[kind]
    rows = []
    for t in range(tokens):
        if held is None:
            rows.append(rng.permutation(16)[:8])
        else:
            rows.append(rng.permutation(np.concatenate(
                [rng.permutation(8)[:held[t]], 8 + rng.permutation(8)[:8 - held[t]]])))
    return jnp.asarray(np.stack(rows), jnp.int32)


@pytest.fixture(scope="module")
def wide():
    """The joyai reference's expert layer at `WIDE` and seeded weights."""
    ref = _load(os.path.join(ROOT, "benchmarks", "reference",
                             REFERENCES["joyai_llm_flash_tiny"]))
    conf = _conf(WIDE)
    return types.SimpleNamespace(
        ref=ref, z=ref._sizes(conf),
        w=ref.init(5, conf)["base"]["blocks"][1]["experts"],
        mm=lambda a, b: a @ b.astype(jnp.float32),
        router=0.5 * jax.random.normal(jax.random.key(9), (16, WIDE.hidden)),
        x=jax.random.normal(jax.random.key(4), (40, WIDE.hidden), jnp.float32))


def _routed_weights(arch, router, x, idx):
    """`route`'s weights for planted selections: differentiable in x and the
    router."""
    s = jnp.take_along_axis(jax.nn.sigmoid(x @ router.T), idx, axis=-1)
    return arch.routed_scaling * s / jnp.sum(s, -1, keepdims=True)


def _one_block_against_reference(wide, idx):
    """(outputs, gradients by x and the router) of the one-block path and
    of the reference's expert layer."""
    ct = jax.random.normal(jax.random.key(8), wide.x.shape)
    sys_fn = lambda x, r: lm.held_experts(  # noqa: E731
        WIDE, wide.w, x, idx, _routed_weights(WIDE, r, x, idx))[0]
    ref_fn = lambda x, r: wide.ref._experts(  # noqa: E731
        wide.z, wide.w, x, idx, _routed_weights(WIDE, r, x, idx), wide.mm,
        None, None)[0]
    got = (sys_fn(wide.x, wide.router),) + jax.grad(
        lambda x, r: jnp.sum(sys_fn(x, r) * ct), (0, 1))(wide.x, wide.router)
    want = _highest(lambda: (ref_fn(wide.x, wide.router),) + jax.grad(
        lambda x, r: jnp.sum(ref_fn(x, r) * ct), (0, 1))(wide.x, wide.router))
    return got, want


@pytest.mark.parametrize("kind", ["uniform", "all_or_none", "none_held",
                                  "all_held", "most_held"])
def test_one_block_path_matches_reference_under_planted_routing(kind, wide):
    """`_held_whole` (a chip that holds half of the experts: one grouped
    product, the un-sort and both gradients as gathers of the held rows)
    against the reference's expert layer: value, gradient by x and by the
    router, from no held pair at all to every pair held."""
    assert lm.experts.pair_blocks(WIDE, 320) == (1, 320)
    idx = _planted_selections(kind)
    held = int(np.sum(np.asarray(idx) < 8))
    assert {"none_held": held == 0, "all_held": held == 320,
            "most_held": held > 200}.get(kind, 120 < held < 200)
    got, want = _one_block_against_reference(wide, idx)
    for a, b, tol in zip(got, want, (0.02, 0.05, 0.05)):
        assert bool(jnp.all(jnp.isfinite(a)))
        assert float(jnp.max(jnp.abs(a - b))) <= tol * float(jnp.max(jnp.abs(b)))
    if kind == "none_held":
        assert not any(np.any(np.asarray(a)) for a in got)


def test_rows_behind_the_last_group_are_never_read(wide, monkeypatch):
    """The grouped product leaves the rows behind its last group unwritten
    (on the chip: whatever the buffer held). Poisoned with NaN, in both
    directions, they change no output and no gradient of the one-block
    path: no select pass cleans them, so nothing may read them."""
    idx = _planted_selections("uniform")
    clean, _ = _one_block_against_reference(wide, idx)
    call, poisoned = lm.experts._gmm_call, []

    def poison(x, w, sizes, transpose, rows=lm.experts.GMM_ROWS):
        out = call(x, w, sizes, transpose, rows)
        behind = jnp.arange(out.shape[0]) >= jnp.sum(sizes)
        poisoned.append(int(jnp.sum(behind)))
        return jnp.where(behind[:, None], jnp.nan, out)

    monkeypatch.setattr(lm.experts, "_gmm_call", poison)
    got, _ = _one_block_against_reference(wide, idx)
    # the value's two products, then the gradient's two each way
    assert len(poisoned) == 6 and min(poisoned) > 100
    for a, b in zip(got, clean):
        assert bool(jnp.all(jnp.isfinite(a)))
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_no_held_pair_and_every_pair_held_cost_their_blocks():
    """Blocks of the held pairs alone: none when no token is routed here,
    all of them when every token is (nothing dropped either way)."""
    arch = SPARSE
    w = jax.eval_shape(lm.FrozenBaseLM(num_classes=VOCAB, arch=arch).init_base)
    w = jax.tree_util.tree_map(
        lambda a: 0.05 * jax.random.normal(jax.random.key(a.size), a.shape,
                                           a.dtype), w["blocks"][1]["experts"])
    x = jax.random.normal(jax.random.key(1), (48, arch.hidden), jnp.float32)
    away = jnp.full((48, 2), 9, jnp.int32)                 # experts held elsewhere
    y, load = lm.held_experts(arch, w, x, away, jnp.ones((48, 2)))
    assert not np.any(np.asarray(load)) and not np.any(np.asarray(y))
    here = jnp.tile(jnp.array([[0, 3]], jnp.int32), (48, 1))
    y, load = lm.held_experts(arch, w, x, here, jnp.ones((48, 2)))
    assert np.asarray(load).tolist() == [48, 0, 0, 48]
    one = dataclasses.replace(arch, held_experts=arch.n_experts)   # one block
    full = {k: jnp.concatenate([v, jnp.zeros((12, *v.shape[1:]), v.dtype)])
            for k, v in w.items()}
    want, _ = lm.held_experts(one, full, x, here, jnp.ones((48, 2)))
    assert jnp.allclose(y, want, rtol=1e-5, atol=1e-6)


def test_group_limited_routing_matches_reference():
    """The selection leaves the `topk_group` best groups only (a group's
    score: the sum of its two largest s + bias), and so differs from plain
    top-k routing."""
    arch = SPARSE
    ref = _load(os.path.join(ROOT, "benchmarks", "reference", "deepseek_v32.py"))
    z = ref._sizes(_conf(arch))
    router = 0.5 * jax.random.normal(jax.random.key(3), (16, arch.hidden))
    bias = 0.1 * jax.random.normal(jax.random.key(4), (16,))
    x = jax.random.normal(jax.random.key(5), (200, arch.hidden), jnp.float32)
    idx, w = lm.route(arch, router, bias, x)
    idx_r, w_r = _highest(ref._route, z, router, bias, x, None)
    assert jnp.array_equal(idx, idx_r) and jnp.allclose(w, w_r, rtol=1e-5)
    plain, _ = _highest(ref._route, z, router, bias, x, None, groups=False)
    assert not jnp.array_equal(idx, plain)          # the groups bind
    groups = np.asarray(idx) // 4
    s = np.asarray(jax.nn.sigmoid(jnp.dot(x, router.T, precision="highest"))
                   + bias).reshape(200, 4, 4)
    best = np.argsort(-np.sort(s, -1)[..., -2:].sum(-1), -1)[:, :2]
    assert all(set(g) <= set(b) for g, b in zip(groups, best))
    assert jnp.allclose(jnp.sum(w, -1), arch.routed_scaling, rtol=1e-5)


def test_yarn_frequencies_against_the_closed_form():
    """Published DeepSeek-V3.2 scaling over 64 rope dims: pairs below 10
    keep theta^(-2i/64), pairs past 23 are divided by 40, a linear ramp
    between; the softmax scale carries m^2, m = 0.1 ln 40 + 1."""
    arch = lm.PRESETS["deepseek_v32"]
    ramp = lm.common.yarn_ramp(64, arch.rope_theta, arch.rope_scaling)
    i = np.arange(32)
    cd = lambda r: 64 * np.log(4096 / (2 * np.pi * r)) / (2 * np.log(1e4))  # noqa: E731
    low, high = int(np.floor(cd(32))), int(np.ceil(cd(1)))
    assert (low, high) == (10, 23)
    assert np.allclose(ramp, np.clip((i - 10) / 13, 0, 1))
    # rope() turns position 1 by the scaled frequencies
    x = jnp.zeros((1, 2, 1, 64)).at[..., 0::2].set(1.0)
    got = lm.common.rope(x, arch.rope_theta, arch.rope_scaling)[0, 1, 0]
    f = 1e4 ** (-2 * i / 64)
    f = f / 40 * ramp + f * (1 - ramp)
    assert np.allclose(got[0::2], np.cos(f), atol=1e-6)
    assert np.allclose(got[1::2], np.sin(f), atol=1e-6)
    half = lm.common.rope(x[..., :2].repeat(32, -1), arch.rope_theta,
                   arch.rope_scaling, interleaved=False)[0, 1, 0]
    assert np.allclose(half[:32], np.cos(f), atol=1e-6)     # pair i: (x_i, x_i+32)
    assert np.allclose(half[32:], np.sin(f), atol=1e-6)
    m = 0.1 * np.log(40) + 1
    assert lm.common.softmax_scale(arch) == pytest.approx(m * m / np.sqrt(192))
    assert lm.common.softmax_scale(lm.PRESETS["joyai_llm_flash"]) == 1 / np.sqrt(192)


def test_kth_largest_mask_is_top_k_with_its_ties():
    scores = jax.random.normal(jax.random.key(0), (16, 200))
    scores = jnp.round(scores * 4) / 4                    # many equal values
    scores = scores.at[3, :50].set(-jnp.inf).at[4].set(1.0)
    for k in (1, 7, 64, 200, 300):
        _, idx = jax.lax.top_k(scores, min(k, 200))
        want = np.zeros((16, 200), bool)
        np.put_along_axis(want, np.asarray(idx), True, axis=1)
        assert np.array_equal(np.asarray(lm.attention.kth_largest_mask(scores, k)), want), k


def test_selection_does_not_depend_on_the_slice_of_queries_it_is_made_in():
    """`select_keys` scores and selects `index_block` queries at a time (a
    last slice padded): whole, 16 at a time and 7 at a time, the same keys."""
    arch = SPARSE
    w = lm.FrozenBaseLM(num_classes=VOCAB, arch=arch, seed=4).init_base()[
        "blocks"][0]["attn"]["index"]
    w = jax.tree_util.tree_map(lambda a: a * 8, w)        # scores that differ
    x = jax.random.normal(jax.random.key(2), (2, 50, arch.hidden), jnp.float32)
    c_q = jax.random.normal(jax.random.key(3), (2, 50, arch.q_lora_rank))
    picks = [np.asarray(lm.select_keys(
        dataclasses.replace(arch, index_block=rows), w, x, c_q))
        for rows in (64, 16, 7)]
    assert picks[0].sum() == 2 * (36 + 42 * 8)
    assert np.array_equal(picks[0], picks[1])
    assert np.array_equal(picks[0], picks[2])


def test_selection_is_causal_has_min_t_plus_1_k_members_and_dense_when_short():
    arch = SPARSE
    base = lm.FrozenBaseLM(num_classes=VOCAB, arch=arch, seed=2).init_base()
    w, g = base["blocks"][0]["attn"], {
        "q_norm": jnp.ones(arch.q_lora_rank), "kv_norm": jnp.ones(arch.kv_lora_rank)}
    w = jax.tree_util.tree_map(lambda a: a * 8, w)        # scores that differ
    x = jax.random.normal(jax.random.key(1), (2, 50, arch.hidden), jnp.float32)
    c_q = lm.common.rms_norm(lm.common._mm(x, w["q_a"]), g["q_norm"], arch.eps)
    picked = np.asarray(lm.select_keys(arch, w["index"], x, c_q))
    assert picked.shape == (2, 50, 50) and not np.triu(picked, 1).any()
    assert np.array_equal(picked.sum(-1),
                          np.tile(np.minimum(np.arange(50) + 1, 8), (2, 1)))
    assert picked[:, np.arange(8), :][:, :, :8].sum() == 2 * 36   # all causal keys
    # the picked keys are top_k's over the scores the indexer's parts give
    q, k, wt = lm.attention.index_scores(arch, w["index"], x, c_q)
    score = jnp.einsum("bqj,bqjs->bqs", wt, jax.nn.relu(jnp.einsum(
        "bqjd,bsd->bqjs", q, k, preferred_element_type=jnp.float32)))
    score = jnp.where(np.tril(np.ones((50, 50), bool)), score, -jnp.inf)
    _, idx = jax.lax.top_k(score, 8)
    want = np.zeros((2, 50, 50), bool)
    np.put_along_axis(want, np.asarray(idx), True, axis=2)
    assert np.array_equal(picked, want & np.tril(np.ones((50, 50), bool)))
    # at S <= k every causal key is picked: the output is the dense path's
    short = x[:, :8]
    dense = dataclasses.replace(arch, index_topk=0, index_heads=0)
    got = lm.latent_attention(arch, w, g, short)
    assert jnp.allclose(got, lm.latent_attention(dense, w, g, short),
                        rtol=2e-2, atol=2e-3)


def test_attention_over_a_selection_and_its_gradients_match_plain_attention():
    # three query blocks of 128 against key blocks of 128 (384 is no multiple
    # of 256), heads in two groups; 300 positions padded to 384
    q, k, v, ct = _qkv(300, h=32, b=1)
    rnd = np.asarray(jax.random.uniform(jax.random.key(5), (1, 300, 300)) < 0.3)
    picked = jnp.asarray((rnd | np.eye(300, dtype=bool)) & np.tril(
        np.ones((300, 300), bool)))
    scale = 1 / np.sqrt(q.shape[-1])

    def plain(q, k, v):
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        p = jax.nn.softmax(jnp.where(picked[:, None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    def ours(q, k, v):
        groups = lambda x: x.reshape(1, 300, 2, 16, -1).transpose(2, 0, 1, 3, 4)  # noqa: E731
        return lm.selected_attention(
            lambda xs: (xs[0] * scale, xs[1], xs[2]),
            (groups(q), groups(k), groups(v)), picked, 128).astype(jnp.float32)

    want, got = _highest(plain, q, k, v), ours(q, k, v)
    assert got.shape == want.shape == (1, 300, 32, 16)
    assert float(jnp.max(jnp.abs(got - want))) < 0.01 * float(jnp.max(jnp.abs(want)))
    g_want = _highest(jax.grad(lambda *a: jnp.sum(plain(*a) * ct), (0, 1, 2)),
                      q, k, v)
    g_got = jax.grad(lambda *a: jnp.sum(ours(*a) * ct), (0, 1, 2))(q, k, v)
    for a, b in zip(g_got, g_want):
        assert float(jnp.max(jnp.abs(a - b))) < 0.02 * float(jnp.max(jnp.abs(b)))


@pytest.mark.parametrize("shape", [(2, 40, 40), (3, 77, 5), (1, 256, 4)])
def test_a_packed_selection_unpacks_to_itself(shape):
    """32 queries a word, a number of queries that is no multiple of 32
    padded with queries that pick nothing."""
    picked = jax.random.uniform(jax.random.key(sum(shape)), shape) < 0.4
    bits = lm.attention.pack_selection(picked)
    words = -(-shape[-2] // 32)
    assert bits.dtype == jnp.uint32 and bits.shape == (
        shape[0], words, shape[-1])
    assert np.array_equal(np.asarray(lm.attention.unpack_selection(bits, shape[-2])),
                          np.asarray(picked))
    # a query behind the last reads 0, whatever the words hold
    assert not np.asarray(lm.attention.unpack_selection(
        lm.attention.pack_selection(jnp.ones(shape, bool)), 32 * words)
    )[:, shape[-2]:].any()


def _sparse_loss_and_gradient(seed: int):
    """(module, variables, tokens) of the tiny model with an indexer, its
    weights and tokens from `seed`, the gains moved off 1."""
    module = lm.FrozenBaseLM(num_classes=VOCAB, arch=SPARSE, seed=seed)
    p = jax.tree_util.tree_map(
        lambda a: a * (1 + 0.1 * jax.random.normal(jax.random.key(a.size + seed),
                                                   a.shape))
        if a.ndim == 1 else a, module.init_trained())
    tokens = jax.random.randint(jax.random.key(seed), (2, 42), 0, VOCAB)
    return module, {"params": p, "base": module.init_base()}, tokens


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_what_the_checkpoint_keeps_moves_no_bit_of_loss_or_gradient(
        seed, monkeypatch):
    """The gradient that keeps the selection and the kernel's output and
    log-sum-exp (`_kept`) against the one that keeps nothing and makes both
    again from the layer's input (the form before PR 42): the loss and the
    gradient by every trained leaf, each form compiled whole, equal to the
    last bit (the kept arrays are the ones the second forward would make)."""
    module, v, tokens = _sparse_loss_and_gradient(seed)
    vg = lambda: jax.jit(jax.value_and_grad(lambda q: module.loss(  # noqa: E731
        {"base": v["base"], "params": q}, tokens)[0]))(v["params"])
    assert lm.model._kept_names(SPARSE) == (lm.attention.DSA_PICKED, lm.attention.ATTN_SAVED)
    l_kept, g_kept = vg()
    monkeypatch.setattr(lm.model, "_kept_names", lambda arch: ())
    l_none, g_none = vg()
    assert float(l_kept) == float(l_none)
    flat = jax.tree_util.tree_leaves_with_path
    for (path, a), (_, b) in zip(flat(g_kept), flat(g_none)):
        assert float(jnp.max(jnp.abs(a))) > 0, path
        assert np.array_equal(np.asarray(a), np.asarray(b)), path


def _executions(jaxpr, wanted, times: int = 1) -> int:
    """How often the equations `wanted(eqn)` picks run in `jaxpr`: a scan's
    body counts once a step."""
    n = 0
    for eqn in jaxpr.eqns:
        if wanted(eqn):
            n += times
            continue
        inner = times * eqn.params["length"] if eqn.primitive.name == "scan" else times
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (tuple, list)) else (val,)):
                if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                    n += _executions(getattr(sub, "jaxpr", sub), wanted, inner)
    return n


@pytest.mark.parametrize("kept,twice", [(None, 1), ((), 2)],
                         ids=["kept", "nothing_kept"])
def test_the_gradient_runs_the_indexer_and_the_forward_kernel_once(
        kept, twice, monkeypatch):
    """In the traced loss and gradient, heads two a call (two group calls a
    layer, four attention layers): `kth_largest_mask`'s loop of 32 counting
    passes once a layer and slice of queries, and the forward kernel once a
    group call; with nothing kept (the form before PR 42) each twice. The
    gradient kernel once a group call either way."""
    if kept is not None:
        monkeypatch.setattr(lm.model, "_kept_names", lambda arch: kept)
    monkeypatch.setattr(lm.attention, "HEADS_A_CALL", 2)
    module, v, tokens = _sparse_loss_and_gradient(1)
    closed = jax.make_jaxpr(jax.value_and_grad(lambda q: module.loss(
        {"base": v["base"], "params": q}, tokens)[0]))(v["params"])
    kernel = lambda name: lambda eqn: (  # noqa: E731
        eqn.primitive.name == "pallas_call"
        and name in str(eqn.params["name"]))
    counting = lambda eqn: (  # noqa: E731
        eqn.primitive.name == "scan" and eqn.params["length"] == 32
        and any(e.primitive.name == "ge"             # key >= candidate
                for e in eqn.params["jaxpr"].jaxpr.eqns))
    layers, groups = 4, SPARSE.heads // 2
    slices = 2 * -(-40 // SPARSE.index_block)        # two sequences' queries
    assert _executions(closed.jaxpr, counting) == twice * layers * slices
    assert _executions(closed.jaxpr, kernel("splash_mha_fwd")) == (
        twice * layers * groups * 2)
    assert _executions(closed.jaxpr, kernel("splash_mha_dkv")) == (
        layers * groups * 2)


def test_loss_with_a_lean_tail_is_the_loss(case, monkeypatch):
    """Where a [tokens, hidden] float32 array is large the heads' last norms
    and the prediction module's input are made again for the gradient:
    the same loss, the same gradient. Each form compiled whole, as a round
    program holds it: op by op the lean heads' backward (fused inside its
    checkpoint) differs from the kept one in a float32's last bits, and a
    bfloat16 rounding in the attention below that falls the other way for it
    moves a gain's gradient by 0.3% (the expert layer's gradient by x is
    float32 since PR 40; rounded to bfloat16 it used to hide those bits)."""
    v, tokens = case.variables, case.tokens
    module = lm.FrozenBaseLM(num_classes=VOCAB, arch=case.arch, seed=3)
    vg = lambda: jax.jit(jax.value_and_grad(lambda q: module.loss(  # noqa: E731
        {"base": v["base"], "params": q}, tokens)[0]))(v["params"])
    l_kept, g_kept = vg()
    monkeypatch.setattr(lm.model, "STREAM_BYTES", 0)
    l_lean, g_lean = vg()
    assert float(l_lean) == pytest.approx(float(l_kept), rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g_lean),
                    jax.tree_util.tree_leaves(g_kept)):
        assert jnp.allclose(a, b, rtol=2e-3, atol=1e-6)


def test_dense_mlp_by_parts_is_the_mlp(monkeypatch):
    w = {"gate_up": jax.random.normal(jax.random.key(0), (32, 128)).astype(
        jnp.bfloat16), "down": jax.random.normal(jax.random.key(1), (64, 32)
                                                 ).astype(jnp.bfloat16)}
    x = jax.random.normal(jax.random.key(2), (2, 24, 32))
    want = lm.experts.glu(w, x)
    assert lm.experts.glu_by_parts(w, x) is not None
    monkeypatch.setattr(lm.experts, "GLU_BYTES", 2 * 24 * 128 * 4 // 4)   # four parts
    got = lm.experts.glu_by_parts(w, x)
    assert jnp.allclose(got, want, rtol=1e-6, atol=1e-6)
    g = jax.grad(lambda a: jnp.sum(lm.experts.glu_by_parts(w, a) ** 2))(x)
    assert jnp.allclose(g, jax.grad(lambda a: jnp.sum(lm.experts.glu(w, a) ** 2))(x),
                        rtol=1e-5, atol=1e-5)


def test_forward_flops_are_the_models_own_count():
    """ISSUE 31's arithmetic at the published widths and 8,192 positions:
    5.73 GFLOP a token, 1.45 of them the indexer, the selection's attention."""
    ref = _load(os.path.join(ROOT, "benchmarks", "reference", "deepseek_v32.py"))
    conf = json.load(open(os.path.join(ROOT, "benchmarks", "configs",
                                       "deepseek-v32-exp-l5e8.json")))
    parts = ref.forward_flops(conf, 8192)
    assert ref.selected_pairs(8192, 2048) == 14_681_088
    assert parts["attend_selected"] == pytest.approx(
        2 * 128 * 320 * 14_681_088 / 8192)
    assert parts["indexer"] == pytest.approx(
        2 * 13_959_424 - 512 + 2 * 64 * 128 * 8193 / 2)
    sparse = 6 * (parts["attend_selected"] + parts["indexer"])
    assert sparse == pytest.approx(1.451e9, rel=1e-3)
    assert parts["total"] == pytest.approx(5.727e9, rel=1e-3)
    assert parts["held_experts"] == pytest.approx(8 * 8 / 256 * 6 * 7168 * 2048)
    # a round of the cell: 2 clients x (2 x 1 trained + 1 validation) sequences
    assert 6 * parts["total"] * 8192 == pytest.approx(281.5e12, rel=1e-3)


def test_grouped_matmul_is_the_ragged_product_and_differentiates_its_rows():
    x = jax.random.normal(jax.random.key(0), (40, 16)).astype(jnp.bfloat16)
    w = jax.random.normal(jax.random.key(1), (3, 16, 24)).astype(jnp.bfloat16)
    sizes = jnp.array([7, 0, 20], jnp.int32)       # 13 rows behind the last
    want = jax.lax.ragged_dot(x, w, sizes, preferred_element_type=jnp.float32)
    got = lm.grouped_matmul(x, w, sizes)
    assert jnp.allclose(got[:27], want[:27], rtol=1e-2, atol=1e-2)
    assert not jnp.any(got[27:])
    gx = jax.grad(lambda a: jnp.sum(lm.grouped_matmul(a, w, sizes) ** 2))(x)
    gx_want = jax.grad(lambda a: jnp.sum(jax.lax.ragged_dot(
        a, w, sizes, preferred_element_type=jnp.float32)[:27] ** 2))(x)
    assert gx.shape == x.shape and not jnp.any(gx[27:])
    assert jnp.allclose(gx.astype(jnp.float32), gx_want.astype(jnp.float32),
                        rtol=5e-2, atol=0.5)


def test_token_data_is_the_seeds():
    (x, y), (xt, _), spec = make_dataset("tokens-v97-s30", seed=11, n_train=6,
                                         n_test=2)
    (x2, y2), _, _ = make_dataset("tokens-v97-s30", seed=11, n_train=6, n_test=2)
    (x3, _), _, _ = make_dataset("tokens-v97-s30", seed=12, n_train=6, n_test=2)
    assert x.shape == (6, 32) and xt.shape == (2, 32) and x.dtype == np.int32
    assert y.shape == (6,) and spec.num_classes == spec.vocab == 97
    assert np.array_equal(x, x2) and np.array_equal(y, y2)
    assert not np.array_equal(x, x3) and not np.array_equal(x[:2], xt)
    assert x.min() >= 0 and x.max() < 97
    # shards of sequences: the image partitioner over the leading axis
    xs, ys = stack_federated(x, y, iid_contiguous(len(y), 2))
    assert xs.shape == (2, 3, 32) and ys.shape == (2, 3)


def test_published_preset_is_the_configuration_file():
    path = os.path.join(ROOT, "benchmarks", "configs",
                        "joyai-llm-flash-l5e128.json")
    conf = json.load(open(path))
    arch = lm.PRESETS["joyai_llm_flash"]
    ours = _conf(arch, conf["vocab_size"])
    for key, value in ours.items():
        assert conf[key] == value, key
    module, params = create_model("joyai_llm_flash", seed=1)
    assert module.num_classes == conf["vocab_size"] == 16160
    count = lambda t: sum(int(np.prod(a.shape))  # noqa: E731
                          for a in jax.tree_util.tree_leaves(t))
    trained = count(jax.eval_shape(module.init_trained))
    assert trained == conf["deployment"]["trained_parameters"] == 2_666_496
    assert -(-trained // 4096) == conf["deployment"]["ciphertexts_a_client"]
    base = jax.eval_shape(module.init_base)
    assert 3.31e9 < count(base) < 3.33e9
    assert {a.dtype for a in jax.tree_util.tree_leaves(base)
            if a.ndim > 1} == {jnp.dtype(jnp.bfloat16)}


def test_published_sparse_preset_is_the_configuration_file():
    path = os.path.join(ROOT, "benchmarks", "configs",
                        "deepseek-v32-exp-l5e8.json")
    conf = json.load(open(path))
    arch = lm.PRESETS["deepseek_v32"]
    ours = _conf(arch, conf["vocab_size"])
    ours["first_k_dense_replace"] = 3        # published; 1 of them held
    ours["held"]["dense_layers"] = 1
    ours["rope_scaling"]["mscale"] = conf["rope_scaling"]["mscale"]
    for key, value in ours.items():
        assert conf[key] == value, key
    assert conf["held"]["dense_layers"] == arch.dense_layers == 1
    assert conf["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    module, _ = create_model("deepseek_v32", seed=1)
    assert module.num_classes == conf["vocab_size"] == 16160
    count = lambda t: sum(int(np.prod(a.shape))  # noqa: E731
                          for a in jax.tree_util.tree_leaves(t))
    trained = count(jax.eval_shape(module.init_trained))
    assert trained == conf["deployment"]["trained_parameters"] == 9_302_016
    assert -(-trained // 4096) == conf["deployment"]["ciphertexts_a_client"] == 2271
    base = jax.eval_shape(module.init_base)
    assert count(base) == 3_918_990_080
    assert count(base["blocks"][1]["attn"]["index"]) == 13_959_424
    # the reference makes the same leaves
    ref = _load(os.path.join(ROOT, "benchmarks", "reference", "deepseek_v32.py"))
    twin = jax.eval_shape(lambda: ref.init(0, conf))
    assert (jax.tree_util.tree_structure(twin["base"])
            == jax.tree_util.tree_structure(base))
    assert all(a.shape == b.shape for a, b in zip(
        jax.tree_util.tree_leaves(twin["base"]), jax.tree_util.tree_leaves(base)))


def _tiny_round_inputs(seed=5):
    module, params = create_model("joyai_llm_flash_tiny", num_classes=64,
                                  seed=seed)
    (x, y), _, _ = make_dataset("tokens-v64-s24", seed=seed, n_train=10,
                                n_test=2)
    xs, ys = stack_federated(x, y, iid_contiguous(len(y), 2))
    cfg = TrainConfig(epochs=1, batch_size=2, num_classes=64, val_fraction=0.2,
                      lr_decay=0.0, augment=False)
    return module, params, jnp.asarray(xs), jnp.asarray(ys), cfg


def test_encrypted_round_of_the_subset_is_the_plain_mean_and_leaves_the_base():
    module, params, xs, ys, cfg = _tiny_round_inputs()
    base = frozen_base(module)
    before = jax.tree_util.tree_map(np.asarray, base)
    ctx = HEConfig(n=256).build()
    sk, pk = keygen(ctx, jax.random.key(1))
    ct, mets, overflow, plain = secure_fedavg_round(
        module, cfg, make_mesh(2), ctx, pk, params, xs, ys, jax.random.key(2),
        with_plain_reference=True)
    spec = PackSpec.for_params(params, ctx.n)
    # exactly ceil(trained / n) rows: the base is in no ciphertext
    assert ct.c0.shape[0] == spec.n_ct == -(-spec.total // ctx.n)
    assert spec.total == sum(a.size for a in jax.tree_util.tree_leaves(params))
    avg = decrypt_average(ctx, sk, ct, 2, spec)
    assert jax.tree_util.tree_structure(avg) == jax.tree_util.tree_structure(params)
    host = lambda t: [np.asarray(a) for a in jax.tree_util.tree_leaves(t)]  # noqa: E731
    for a, b, p0 in zip(host(avg), host(plain), host(params)):
        assert float(np.max(np.abs(a - b))) < 5e-5
        assert float(np.max(np.abs(b - p0))) > 0         # and it trained
    assert int(np.sum(np.asarray(overflow))) == 0
    assert np.all(np.isfinite(np.asarray(mets)))
    after = frozen_base(module)
    for a, b in zip(jax.tree_util.tree_leaves(before),
                    jax.tree_util.tree_leaves(after)):
        assert np.array_equal(a, np.asarray(b))            # bit for bit
    set_frozen_base(module, None)


def test_run_experiment_runs_a_tiny_token_model_end_to_end(tmp_path, monkeypatch):
    from hefl_tpu.obs import events as obs_events
    from hefl_tpu.obs import metrics as obs_metrics
    from hefl_tpu.obs import spans as obs_spans

    monkeypatch.setenv("HEFL_EVENTS", "1")
    events = str(tmp_path / "events.jsonl")
    cfg = ExperimentConfig(
        model="joyai_llm_flash_tiny", dataset="tokens-v64-s24", n_train=10,
        n_test=2, num_clients=2, rounds=2, seed=4, events_path=events,
        he=HEConfig(n=256),
        train=TrainConfig(epochs=1, batch_size=2, num_classes=64,
                          val_fraction=0.2, lr_decay=0.0, augment=False))
    out = run_experiment(cfg, verbose=False)
    assert [r["round"] for r in out["history"]] == [0, 1]
    for rec in out["history"]:
        assert np.isfinite(rec["accuracy"]) and 0 <= rec["accuracy"] <= 1
        assert rec["precision"] == rec["recall"] == rec["f1"] == rec["accuracy"]
        assert np.all(np.isfinite(rec["val_loss"]))
        assert rec["encode_overflow"] == [0, 0]
    ends = [e for e in obs_events.read_events(events) if e["event"] == "round_end"]
    assert len(ends) == 2
    assert out["client_fusion"]["backend"] == "serial"
    # the trained subset alone is the round's parameters
    assert (jax.tree_util.tree_structure(out["params"])
            == jax.tree_util.tree_structure(create_model(
                "joyai_llm_flash_tiny", num_classes=64)[1]))
    names = {s.name for s in obs_spans.recorded()}
    assert "hefl.setup.base" in names
    assert obs_metrics.gauge("moe.load_max_over_mean").value >= 1.0
    assert obs_metrics.counter("he.encrypt_rows").value >= 2 * 2 * 11


def test_token_model_goes_through_the_synchronous_rounds_only():
    from hefl_tpu.fl import StreamConfig
    from hefl_tpu.fl.client import train_centralized

    module, params, xs, ys, cfg = _tiny_round_inputs()
    with pytest.raises(NotImplementedError, match="frozen base"):
        train_centralized(module, cfg, params, xs[0], ys[0], jax.random.key(0))
    with pytest.raises(NotImplementedError, match="frozen"):
        run_experiment(ExperimentConfig(
            model="joyai_llm_flash_tiny", dataset="tokens-v64-s24", n_train=10,
            n_test=2, num_clients=2, rounds=1, he=HEConfig(n=256),
            events_path="", train=cfg, stream=StreamConfig()), verbose=False)


def test_serial_backend_trains_what_vmap_trains():
    """`serial_train` (a token model's lowering) is the vmap backend's per-client program, one
    client after another: an image model's trained weights agree."""
    from hefl_tpu.fl.fedavg import serial_train, vmapped_train

    module, params = create_model("logreg", rng=jax.random.key(0))
    (x, y), _, _ = make_dataset("mnist", seed=0, n_train=32, n_test=4)
    xs, ys = stack_federated(x, y, iid_contiguous(len(y), 2))
    cfg = TrainConfig(epochs=1, batch_size=4, num_classes=10, val_fraction=0.25,
                      augment=False)
    keys = jax.random.split(jax.random.key(3), 2)
    run = lambda f: jax.jit(lambda a, b, k: f(  # noqa: E731
        module, cfg, params, a, b, k))(jnp.asarray(xs), jnp.asarray(ys), keys)
    (p_v, m_v), (p_s, m_s) = run(vmapped_train), run(serial_train)
    for a, b in zip(jax.tree_util.tree_leaves(p_v), jax.tree_util.tree_leaves(p_s)):
        assert jnp.allclose(a, b, rtol=1e-5, atol=1e-6)
    assert jnp.allclose(m_v, m_s, rtol=1e-5, atol=1e-6)


# --------------------------------------------------------------------------
# the package: one plan an expert layer, imports that point one way
# --------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(lm.PRESETS))
def test_with_counts_reports_the_rows_the_plan_gives_the_products(
        name, monkeypatch):
    """The rows `_with_counts` puts into `loss`'s loads are the rows
    `held_experts` hands its grouped products under the same plan
    (`expert_plan`): every `_gmm_call` of a traced layer is one the plan
    names (the front's rows and tile, a block's), and at any count of held
    pairs the front's rows plus a block's for each trip of `_held_blocks`'s
    loop is what the loads carry. At the cells' 8,192 tokens for the
    published presets (traced as shapes: nothing is computed)."""
    arch = lm.PRESETS[name]
    tokens = 96 if "tiny" in name else 8192
    k, held = arch.experts_per_tok, arch.held_experts
    scanned = lm.model.stacked(arch)
    plan = lm.experts.expert_plan(arch, tokens * k, scanned)
    seen = []

    def record(x, w, sizes, transpose, rows=lm.experts.GMM_ROWS):
        seen.append((x.shape[0], rows))
        return jnp.zeros((x.shape[0], w.shape[1 if transpose else 2]),
                         jnp.float32)

    monkeypatch.setattr(lm.experts, "_gmm_call", record)
    n = held * (arch.expert_layers if scanned else 1)
    shape = jax.ShapeDtypeStruct
    jax.eval_shape(
        lambda w, x, idx, ws: lm.held_experts(
            arch, w, x, idx, ws, at=0 if scanned else None),
        {"gate_up": shape((n, arch.hidden, 2 * arch.moe_intermediate),
                          jnp.bfloat16),
         "down": shape((n, arch.moe_intermediate, arch.hidden), jnp.bfloat16)},
        shape((tokens, arch.hidden), jnp.float32),
        shape((tokens, k), jnp.int32), shape((tokens, k), jnp.float32))
    front = (plan.front + plan.spare, plan.tile)
    block = (plan.rows, lm.experts.GMM_ROWS)
    behind = plan.front < tokens * k
    assert sorted(seen) == sorted([front] * 2 * bool(plan.front)
                                  + [block] * 2 * behind)
    for pairs in (0, 1, plan.front, plan.front + 1, tokens * k):
        loads = jnp.zeros((2, held), jnp.int32).at[:, 0].set(pairs)
        given = lm.model._with_counts(arch, loads, None, 1, tokens + 2)[
            0, held + 1]
        trips = max(-(-pairs // plan.rows) - plan.front // plan.rows, 0)
        assert int(given) == front[0] * bool(plan.front) + (
            block[0] * trips * behind), pairs


def test_the_packages_imports_point_one_way():
    """`hefl_tpu/models/lm/`: `common` imports no module of the package;
    `attention`, `kda` and `experts` import `common` alone; `model` imports
    those four; nothing imports `model` but `__init__` (read with `ast`:
    no import is run)."""
    import ast

    package = os.path.join(ROOT, "hefl_tpu", "models", "lm")
    rank = {"common": 0, "attention": 1, "kda": 1, "experts": 1, "model": 2}
    found = set(f[:-3] for f in os.listdir(package) if f.endswith(".py"))
    assert found == set(rank) | {"__init__"}
    for module, level in rank.items():
        with open(os.path.join(package, module + ".py")) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            names = []
            if isinstance(node, ast.ImportFrom) and node.module:
                assert node.level == 0, (module, "a relative import")
                if node.module == "hefl_tpu.models.lm":
                    names = [a.name for a in node.names]
                elif node.module.startswith("hefl_tpu.models.lm."):
                    names = [node.module.split(".")[3]]
                elif node.module == "hefl_tpu.models":
                    names = [a.name for a in node.names if a.name == "lm"]
            elif isinstance(node, ast.Import):
                names = [a.name.split(".")[3] for a in node.names
                         if a.name.startswith("hefl_tpu.models.lm.")]
            for other in names:
                assert other in rank and rank[other] < level, (
                    f"{module} imports {other}")
