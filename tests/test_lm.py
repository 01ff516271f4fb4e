"""The token model with a frozen base (hefl_tpu/models/lm.py) against its
plain reference (benchmarks/reference/joyai_llm_flash.py) on seeded weights
at a small size, the share of a stated deployment tied to the uncut layer,
routing under a planted imbalance, token data, and the encrypted round of the
trained subset alone. No device or topology call at import time."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

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
    spec = importlib.util.spec_from_file_location("_ref_joyai", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load(os.path.join(ROOT, "benchmarks", "reference",
                              "joyai_llm_flash.py"))


def _conf(arch: lm.LMArch, vocab: int = VOCAB) -> dict:
    """The reference's configuration keys for a system preset."""
    return dict(
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


TINY = lm.PRESETS["joyai_llm_flash_tiny"]


@pytest.fixture(scope="module")
def seeded(ref):
    """The reference's seeded weights, the gains moved off 1 so they count."""
    conf = _conf(TINY)
    v = ref.init(3, conf)
    p = jax.tree_util.tree_map(
        lambda a: a * (1 + 0.1 * jax.random.normal(jax.random.key(a.size),
                                                   a.shape))
        if a.ndim == 1 else a, v["params"])
    tokens = jax.random.randint(jax.random.key(0), (2, 26), 0, VOCAB)
    return conf, {"base": v["base"], "params": p}, tokens


def _highest(fn, *a, **kw):
    with jax.default_matmul_precision("highest"):
        return fn(*a, **kw)


def test_latent_attention_block_matches_reference(ref, seeded):
    conf, v, _ = seeded
    w, g = v["base"]["blocks"][0]["attn"], v["params"]["blocks"][0]
    x = jax.random.normal(jax.random.key(1), (2, 24, TINY.hidden), jnp.float32)
    mm = lambda a, b: a @ b.astype(jnp.float32)  # noqa: E731
    want = _highest(ref._attention, ref._sizes(conf), w, g, x, mm)
    got = lm.latent_attention(TINY, w, g, x)
    assert got.shape == want.shape == (2, 24, TINY.hidden)
    # bfloat16 operands against float32: a few parts in a thousand
    assert float(jnp.max(jnp.abs(got - want))) < 0.02 * float(jnp.std(want)) + 1e-4
    # causal: a later token does not move an earlier position
    x2 = x.at[:, -1].add(1.0)
    assert jnp.array_equal(lm.latent_attention(TINY, w, g, x2)[:, :-1],
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
    before = lm._attention_kernel.cache_info().misses
    lm.causal_attention(*cut(q, k, v), 128)
    assert lm._attention_kernel.cache_info().misses == before


def test_gauge_counts_the_attention_layers_that_took_the_kernel(seeded):
    from hefl_tpu.obs import metrics as obs_metrics

    _, v, tokens = seeded
    gauge = obs_metrics.gauge("model.fused_attention_layers")
    gauge.set(0)
    module = lm.JoyAIFlash(num_classes=VOCAB, arch=TINY, seed=3)
    jax.eval_shape(lambda p: module.apply({"base": v["base"], "params": p},
                                          tokens), v["params"])
    # 1 dense + 2 expert layers + the prediction module
    assert gauge.value == TINY.dense_layers + TINY.expert_layers + 1 == 4
    create_model("smallcnn", num_classes=2, input_shape=(16, 16, 3))
    assert obs_metrics.snapshot()["model.fused_attention_layers"] == 0


def test_expert_block_matches_reference(ref, seeded):
    conf, v, _ = seeded
    w, g = v["base"]["blocks"][1], v["params"]["blocks"][1]
    h = jax.random.normal(jax.random.key(2), (2, 24, TINY.hidden), jnp.float32)
    mm = lambda a, b: a @ b.astype(jnp.float32)  # noqa: E731
    want, aux = _highest(ref._block, ref._sizes(conf), w, g, h, mm, None, None,
                         None)
    got, (load, idx) = lm.block(TINY, w, g, h)
    assert jnp.array_equal(idx, aux["experts"])      # the float32 router agrees
    assert np.asarray(load).tolist() == np.asarray(aux["loads"]).tolist()
    assert float(jnp.max(jnp.abs(got - want))) < 0.02 * float(jnp.std(want))


def test_whole_model_logits_loss_and_gradients_match_reference(ref, seeded):
    conf, v, tokens = seeded
    module = lm.JoyAIFlash(num_classes=VOCAB, arch=TINY, seed=3)
    (l_ref, (z1, z2, aux)), g_ref = _highest(jax.value_and_grad(
        lambda q: ref.loss({"base": v["base"], "params": q}, tokens, conf),
        has_aux=True), v["params"])
    s1, s2, (loads, sel) = module.apply(v, tokens, routed=True)
    (l_sys, (ce, acc, _)), g_sys = jax.value_and_grad(
        lambda q: module.loss({"base": v["base"], "params": q}, tokens),
        has_aux=True)(v["params"])
    assert s1.shape == s2.shape == (2, 24, VOCAB)
    scale = float(jnp.std(z1))
    assert float(jnp.max(jnp.abs(s1 - z1))) < 0.05 * scale
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
        limit = 0.4 if "mtp" in jax.tree_util.keystr(path) else 0.02
        assert gap < limit, (jax.tree_util.keystr(path), gap)


def test_two_shares_and_the_shared_expert_add_up_to_the_uncut_layer(ref, seeded):
    """At 8 experts in shares of 4: the two shares' routed parts plus the
    shared expert counted once are the uncut reference's layer."""
    whole = dataclasses.replace(TINY, held_start=0, held_experts=8)
    conf = _conf(whole)
    z = ref._sizes(conf)
    w = ref.init(5, conf)["base"]["blocks"][1]
    router = 0.5 * jax.random.normal(jax.random.key(9), (8, TINY.hidden))
    x = jax.random.normal(jax.random.key(4), (40, TINY.hidden), jnp.float32)
    mm = lambda a, b: a @ b.astype(jnp.float32)  # noqa: E731
    idx_r, w_r = _highest(ref._route, z, router, w["bias"], x, None)
    routed_ref, _ = _highest(ref._experts, z, w["experts"], x, idx_r, w_r, mm,
                             None, None)
    uncut = routed_ref + _highest(ref._glu, w["shared"], x, mm)
    idx, weights = lm.route(whole, router, w["bias"], x)
    assert jnp.array_equal(idx, idx_r)
    parts, pairs = [], 0
    for start in (0, 4):
        share = dataclasses.replace(TINY, held_start=start, held_experts=4)
        mine = {k: v[start:start + 4] for k, v in w["experts"].items()}
        y, load = lm.held_experts(share, mine, x, idx, weights)
        parts.append(y)
        pairs += int(jnp.sum(load))
    assert pairs == 40 * TINY.experts_per_tok       # every pair, once
    total = parts[0] + parts[1] + lm.glu(w["shared"], x)
    assert float(jnp.max(jnp.abs(total - uncut))) < 0.02 * float(jnp.std(uncut))


def test_planted_imbalance_drops_no_pair(ref, seeded):
    """A router that sends every token to the same two held experts: every
    pair is computed (no capacity), and the layer is still the reference's."""
    conf, v, _ = seeded
    w = v["base"]["blocks"][1]
    bias = jnp.zeros(8).at[jnp.array([1, 2])].set(10.0)    # always selected
    router = 0.02 * jax.random.normal(jax.random.key(6), (8, TINY.hidden))
    x = jax.random.normal(jax.random.key(7), (64, TINY.hidden), jnp.float32)
    idx, weights = lm.route(TINY, router, bias, x)
    assert set(np.asarray(idx).ravel().tolist()) == {1, 2}
    y, load = lm.held_experts(TINY, w["experts"], x, idx, weights)
    assert np.asarray(load).tolist() == [0, 64, 64, 0]
    mm = lambda a, b: a @ b.astype(jnp.float32)  # noqa: E731
    want, _ = _highest(ref._experts, ref._sizes(conf), w["experts"], x, idx,
                       weights, mm, None, None)
    assert float(jnp.max(jnp.abs(y - want))) < 0.02 * float(jnp.std(want))


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
