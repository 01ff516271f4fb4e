"""The token model with delta-rule linear-attention layers and a gated latent
layer (hefl_tpu/models/lm.py at `ling_3_flash_tiny`: 3 linear layers to 1
latent, heads of 16, chunks of 16 solved in sub-blocks of 4, 16 experts in 4
groups of which 4 held) against its plain reference
(benchmarks/reference/ling_3_flash.py, position by position) on seeded
weights: logits, selections, loss, every trained leaf's gradient, the chunked
recurrence and its gradient at lengths that are and are not whole chunks with
the decays at the bound and near 0, causality exactly, the state at a
chunk's edge, the front's kernel pair (interpreted here) against XLA's
operations at heads of 128, the gate a head, the four expert shares adding up, the model's
own counts, the published preset against the configuration's file and the
catalog's row, and the encrypted round with a ragged last row. No device or
topology call at import time."""

from __future__ import annotations

import dataclasses
import functools
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
from hefl_tpu.experiment import HEConfig
from hefl_tpu.fl import TrainConfig, decrypt_average, secure_fedavg_round
from hefl_tpu.models import create_model, frozen_base, lm, set_frozen_base
from hefl_tpu.obs import metrics as obs_metrics
from hefl_tpu.parallel import make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, POSITIONS = 64, 40          # 40 positions: two and a half chunks of 16
TINY = lm.PRESETS["ling_3_flash_tiny"]
LEAVES = ("A_log", "dt_bias", "o_norm", "kv_norm", "ln_attn", "ln_mlp",
          "router", "final_norm")


def _load(kind, name):
    path = os.path.join(ROOT, "benchmarks", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _highest(fn, *args, **kw):
    with jax.default_matmul_precision("highest"):
        return fn(*args, **kw)


@pytest.fixture(scope="module")
def ref():
    return _load("reference", "ling_3_flash")


@pytest.fixture(scope="module")
def conf():
    """The tiny cell's configuration file, as the check hands it on."""
    with open(os.path.join(ROOT, "tests", "benchmark", "tiny_lm_linear",
                           "configs", "tiny-lm-linear.json")) as f:
        whole = json.load(f)
    return {k: v for k, v in whole.items()
            if not isinstance(v, (dict, list, str)) or k == "held"}


def _planted(variables, seed=9):
    """Decays away from their start, so that the state weighs."""
    key = jax.random.key(seed)
    blocks = [dict(g, A_log=0.3 * jax.random.normal(
        jax.random.fold_in(key, i), g["A_log"].shape),
        dt_bias=-4.0 + jax.random.normal(jax.random.fold_in(key, 50 + i),
                                         g["dt_bias"].shape))
        if "A_log" in g else g
        for i, g in enumerate(variables["params"]["blocks"])]
    return {"base": variables["base"],
            "params": dict(variables["params"], blocks=blocks)}


@pytest.fixture(scope="module")
def case(ref, conf):
    """The system and the reference on one batch of seeded weights."""
    module = lm.FrozenBaseLM(num_classes=VOCAB, arch=TINY, seed=0)
    v = _planted(ref.init(5, conf))
    tokens = jax.random.randint(jax.random.key(0), (2, POSITIONS + 2), 0, VOCAB)
    base = v["base"]
    (l_ref, (z_ref, none, aux)), g_ref = jax.jit(jax.value_and_grad(
        lambda p: _highest(ref.loss, {"params": p, "base": base}, tokens, conf,
                           keep_inputs=True), has_aux=True))(v["params"])
    assert none is None
    l_sys, g_sys = jax.jit(jax.value_and_grad(
        lambda p: module.loss({"params": p, "base": base}, tokens)[0]))(
            v["params"])
    z_sys, z_mtp, (loads, sel) = jax.jit(lambda p: module.apply(
        {"params": p, "base": base}, tokens, routed=True))(v["params"])
    assert z_mtp is None
    return dict(module=module, v=v, tokens=tokens, l_ref=l_ref, z_ref=z_ref,
                aux=aux, g_ref=g_ref, l_sys=l_sys, g_sys=g_sys, z_sys=z_sys,
                loads=loads, sel=sel)


def test_the_references_layout_is_the_systems(ref, conf):
    shapes = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: (tuple(a.shape), str(a.dtype)), t)
    module = lm.FrozenBaseLM(num_classes=VOCAB, arch=TINY, seed=0)
    v = jax.eval_shape(lambda: ref.init(5, conf))
    assert shapes(v["base"]) == shapes(jax.eval_shape(module.init_base))
    assert shapes(v["params"]) == shapes(jax.eval_shape(module.init_trained))
    p = module.init_trained()
    for g in p["blocks"]:   # the decay starts at 0, the gains at 1
        if "A_log" in g:
            assert not np.any(np.asarray(g["A_log"])) and not np.any(
                np.asarray(g["dt_bias"]))
            assert np.all(np.asarray(g["o_norm"]) == 1)
    assert [("A_log" in g, "router" in g) for g in p["blocks"]] == [
        (True, False), (True, True), (False, True), (True, True)]


def test_logits_selections_and_loss_match_reference(case):
    assert float(jnp.max(jnp.abs(case["z_sys"] - case["z_ref"]))) < 0.03 * float(
        jnp.max(jnp.abs(case["z_ref"])))
    same = jnp.sort(case["sel"], -1) == jnp.sort(case["aux"]["experts"], -1)
    assert float(jnp.mean(same)) > 0.97
    assert abs(float(case["l_sys"]) - float(case["l_ref"])) < 2e-4 * float(
        case["l_ref"])
    # every (token, held expert) pair the selections name was computed
    held = (case["sel"] >= 0) & (case["sel"] < TINY.held_experts)
    assert np.array_equal(np.asarray(jnp.sum(held, (1, 2))),
                          np.asarray(case["loads"]).sum(-1))


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_of_every_trained_leaf_matches_reference(case, leaf):
    flat = lambda t: {jax.tree_util.keystr(k): v for k, v in  # noqa: E731
                      jax.tree_util.tree_leaves_with_path(t)}
    got, want = flat(case["g_sys"]), flat(case["g_ref"])
    mine = [k for k in want if k.endswith(f"['{leaf}']")]
    assert mine and set(got) == set(want)
    for k in mine:
        gap = float(jnp.linalg.norm(got[k] - want[k]) / jnp.linalg.norm(want[k]))
        # a router's gradient, and that of the gain in front of it, move
        # with the few selections that differ
        assert gap < {"router": 0.25, "ln_mlp": 0.2}.get(leaf, 0.05), (k, gap)


def test_the_decays_gradient_gap_is_the_operands_precision(case, monkeypatch):
    """The check's `decay_grad_gap` (every linear layer's `A_log` and
    `dt_bias` gradient as one vector) through the whole tiny model: percents
    with the bfloat16 operands the system computes with, under 2e-3 (what
    the expert layers' bfloat16 leaves) when the recurrence's products and
    the projections around it take float32 operands: the chunked form and
    the layers' scan have no fault of their own, the operands have a cost
    (PERF.md, PR 41, has the chip's readings)."""
    flat = lambda t: np.concatenate([  # noqa: E731
        np.asarray(g[n], np.float64).ravel() for g in t["blocks"]
        if "A_log" in g for n in ("A_log", "dt_bias")])
    want = flat(case["g_ref"])
    gap = lambda g: float(  # noqa: E731
        np.linalg.norm(flat(g) - want) / np.linalg.norm(want))
    assert 5e-3 < gap(case["g_sys"]) < 0.05
    monkeypatch.setattr(lm.kda, "kda_recurrence", functools.partial(
        lm.kda_recurrence, operands=jnp.float32))
    for module in (lm.attention, lm.kda, lm.experts, lm.model):   # every `_mm`
        monkeypatch.setattr(module, "_mm", lambda x, w: jnp.dot(
            x, w.astype(jnp.float32), precision=lm.common.HIGHEST))
    base = case["v"]["base"]
    exact = jax.jit(jax.grad(lambda p: case["module"].loss(
        {"params": p, "base": base}, case["tokens"])[0]))(case["v"]["params"])
    assert gap(exact) < 2e-3


# a model whose heads fill whole lanes: two layers, a linear and a latent one
WIDE = dataclasses.replace(TINY, heads=2, kda_head_dim=128, kda_chunk=64,
                           kda_block=16, layer_pattern=(1, 0), expert_layers=1)


@pytest.mark.parametrize("arch,linear,kernels,fused", [
    ("tiny", 3, 0, 1), ("wide", 1, 1, 1)])
def test_gauges_count_the_layers_by_kind(case, arch, linear, kernels, fused):
    """By kind, and of the linear layers those whose front is the kernel
    pair: none at the tests' preset (heads of 16), every one where a head
    is 128 wide."""
    value = lambda name: obs_metrics.gauge(name).value  # noqa: E731
    if arch == "tiny":
        jax.eval_shape(case["module"].apply, case["v"], case["tokens"])
    else:
        module = lm.FrozenBaseLM(num_classes=VOCAB, arch=WIDE, seed=0)
        jax.eval_shape(
            lambda tokens: module.apply(
                {"params": module.init_trained(), "base": module.init_base()},
                tokens), case["tokens"])
    assert value("model.linear_attention_layers") == linear
    assert value("model.kda_front_kernel_layers") == kernels
    assert value("model.gated_attention_layers") == fused
    assert value("model.fused_attention_layers") == fused
    assert value("model.window_attention_layers") == 0
    create_model("smallcnn")
    assert value("model.linear_attention_layers") == 0
    assert value("model.kda_front_kernel_layers") == 0
    assert value("model.gated_attention_layers") == 0


# --------------------------------------------------------------------------
# the recurrence alone
# --------------------------------------------------------------------------

H, DK = 2, 8


def _inputs(length: int, decay: str, seed: int = 3):
    ks = jax.random.split(jax.random.key(seed), 6)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (1, length, H, DK))) * DK ** -0.5
    k = unit(jax.random.normal(ks[1], (1, length, H, DK)))
    v = jax.random.normal(ks[2], (1, length, H, DK))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (1, length, H)))
    g = {"bound": jnp.full((1, length, H, DK), -5.0),
         "near_0": -1e-3 * jax.random.uniform(ks[4], (1, length, H, DK)),
         "mixed": -5.0 * jax.nn.sigmoid(
             -3.0 + 2.0 * jax.random.normal(ks[4], (1, length, H, DK)))}[decay]
    return q, k, v, g, beta, jax.random.normal(ks[5], (1, length, H, DK))


@functools.lru_cache(maxsize=None)
def _recurrences(ref, chunk: int, block: int):
    def both(fn):
        def run(q, k, v, g, beta, weigh):
            out = fn(q, k, v, g, beta)
            return jnp.sum(out * weigh), out
        return jax.jit(jax.value_and_grad(run, argnums=(0, 1, 2, 3, 4),
                                          has_aux=True))
    chunked = both(lambda *a: lm.kda_recurrence(*a, chunk=chunk, block=block))
    plain = both(lambda *a: _highest(ref.delta_rule, *a))
    return chunked, plain


@pytest.mark.parametrize("decay", ["bound", "near_0", "mixed"])
@pytest.mark.parametrize("length,chunk,block", [
    (24, 16, 4), (64, 16, 4), (100, 16, 4), (200, 64, 16)])
def test_chunked_recurrence_and_its_gradient_match_position_by_position(
        ref, length, chunk, block, decay):
    """Lengths that are whole chunks (64) and are not (24, 100, 200), at the
    tests' chunk and at the published one (64 solved in sub-blocks of 16);
    every g at the bound -5 for the whole sequence (the exponents that a
    sub-block of 16 has to hold), near 0 (a state that never fades) and
    mixed by channel."""
    chunked, plain = _recurrences(ref, chunk, block)
    args = _inputs(length, decay)
    (_, o_sys), g_sys = chunked(*args)
    (_, o_ref), g_ref = plain(*args)
    assert np.all(np.isfinite(np.asarray(o_sys)))
    scale = float(jnp.max(jnp.abs(o_ref)))
    assert float(jnp.max(jnp.abs(o_sys - o_ref))) < 0.02 * scale
    # (the decay's gradient is a difference of terms as large as q's, each
    # made by bfloat16 products: where the state fades at once it is small
    # beside what they round by)
    floor = 2e-3 * float(jnp.linalg.norm(g_ref[0]))
    for a, b in zip(g_sys, g_ref):
        assert np.all(np.isfinite(np.asarray(a)))
        assert float(jnp.linalg.norm(a - b)) < 0.03 * float(
            jnp.linalg.norm(b)) + floor


@pytest.mark.parametrize("operands,low,high", [
    ("float32", 0.0, 1e-5), ("bfloat16", 1e-3, 0.03)])
def test_the_decays_gradient_errs_by_the_products_operands(ref, operands, low,
                                                           high):
    """The witness of what the check's `decay_grad_gap` reads (PERF.md, PR
    41): the chunked form's gradient by the decay, against the position by
    position one, is exact to float32's rounding when its products take
    float32 operands, and off by what bfloat16 operands round when they take
    those: the form has no fault, the operands have a cost."""
    q, k, v, _, beta, weigh = _inputs(200, "mixed")
    g = -5.0 * jax.nn.sigmoid(-5.0 + jax.random.normal(
        jax.random.key(11), q.shape))         # the check's planted decays
    by_g = lambda fn: jax.jit(jax.grad(  # noqa: E731
        lambda g: jnp.sum(fn(q, k, v, g, beta) * weigh)))(g)
    want = by_g(lambda *a: _highest(ref.delta_rule, *a))
    got = by_g(lambda *a: lm.kda_recurrence(
        *a, chunk=64, block=16, operands=jnp.dtype(operands).type))
    gap = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert low <= gap < high


def test_the_state_crosses_a_chunks_edge(ref):
    """With decays near 0 a chunk's outputs rest on every chunk before it: a
    form that drops the state at the edge differs from the first position
    of the second chunk on, and equals the reference told to drop it."""
    args = _inputs(64, "near_0")[:5]
    kept = lm.kda_recurrence(*args, chunk=16, block=4)
    dropped = lm.kda_recurrence(*args, chunk=16, block=4, carry=False)
    want = _highest(ref.delta_rule, *args)
    lost = _highest(ref.delta_rule, *args, drop_state=16)
    assert float(jnp.max(jnp.abs(kept - want))) < 0.02 * float(jnp.max(jnp.abs(want)))
    assert jnp.allclose(kept[:, :16], dropped[:, :16], atol=1e-6)
    assert float(jnp.max(jnp.abs(kept[:, 16:] - dropped[:, 16:]))) > 0.1
    assert float(jnp.max(jnp.abs(dropped - lost))) < 0.02 * float(
        jnp.max(jnp.abs(lost)))


def test_the_triangular_system_is_solved_exactly_in_sub_blocks():
    n = jnp.tril(jax.random.normal(jax.random.key(1), (3, 16, 16)), -1)
    for block in (4, 8, 16):
        inv = lm.kda._unit_lower_inverse(n, block)
        assert float(jnp.max(jnp.abs(
            jnp.matmul(inv, jnp.eye(16) + n, precision="highest")
            - jnp.eye(16)))) < 1e-4
    grad = jax.grad(lambda n: jnp.sum(lm.kda._unit_lower_inverse(n, 4) ** 2))(n)
    want = jax.grad(lambda n: jnp.sum(jnp.linalg.inv(jnp.eye(16) + n) ** 2))(n)
    assert float(jnp.max(jnp.abs(grad - want))) < 1e-3 * float(
        jnp.max(jnp.abs(want)))


def test_nothing_at_a_later_position_moves_an_output(case, ref, conf):
    """The linear layer whole: with every position > t of its input replaced
    by noise the outputs up to t stay bit for bit; the convolution reaches
    back K - 1 positions and no further."""
    z = ref._sizes(conf)
    v = case["v"]
    w = ref.layer_weights(z, v["base"], 0)["attn"]
    g = v["params"]["blocks"][0]
    x = jax.random.normal(jax.random.key(2), (1, 48, TINY.hidden))
    noise = jax.random.normal(jax.random.key(3), x.shape)
    layer = jax.jit(lambda x: lm.kda_layer(TINY, w, g, x))
    t = 29                                    # inside the second chunk
    later = (jnp.arange(48) > t)[None, :, None]
    out, moved = layer(x), layer(jnp.where(later, noise, x))
    assert np.array_equal(np.asarray(out[:, :t + 1]), np.asarray(moved[:, :t + 1]))
    assert float(jnp.max(jnp.abs(out[:, t + 1:] - moved[:, t + 1:]))) > 0
    zc, other = jax.random.normal(jax.random.key(4), (2, 1, 48, 192))
    behind = (jnp.arange(48) <= t - TINY.kda_conv)[None, :, None]
    c = w["conv"]
    assert c.shape == (3 * 64, TINY.kda_conv)
    assert np.array_equal(
        np.asarray(lm.short_conv(zc, c)[:, t:]),
        np.asarray(lm.short_conv(jnp.where(behind, other, zc), c)[:, t:]))
    nearer = (jnp.arange(48) == t - TINY.kda_conv + 1)[None, :, None]
    assert not np.array_equal(
        np.asarray(lm.short_conv(zc, c)[:, t]),
        np.asarray(lm.short_conv(jnp.where(nearer, other, zc), c)[:, t]))
    assert float(jnp.max(jnp.abs(lm.short_conv(zc, c) - _highest(
        ref.conv, zc, c.astype(jnp.float32))))) < 1e-6


def test_the_linear_layer_matches_the_references(case, ref, conf):
    z, v, aux = ref._sizes(conf), case["v"], case["aux"]
    for i, (kind, _) in enumerate(z["layers"]):
        w = ref.layer_weights(z, v["base"], i)["attn"]
        g = v["params"]["blocks"][i]
        layer = lm.kda_layer if kind == ref.LINEAR else lm.latent_attention
        got = layer(TINY, w, g, aux["attn_in"][i])
        gap = float(jnp.linalg.norm(got - aux["attn_out"][i])
                    / jnp.linalg.norm(aux["attn_out"][i]))
        assert gap < 0.01, (i, kind, gap)


# --------------------------------------------------------------------------
# the front's kernel pair against XLA's operations (interpreted here)
# --------------------------------------------------------------------------


def _front_inputs(length: int, seed: int = 5):
    """`made` for two sequences, taps as the base keeps them, decays away
    from their start."""
    ks = jax.random.split(jax.random.key(seed), 4)
    n = WIDE.heads * WIDE.kda_head_dim
    return (jax.random.normal(ks[0], (2, length, 5 * n)),
            (0.5 * jax.random.normal(ks[1], (3 * n, WIDE.kda_conv))).astype(
                jnp.bfloat16),
            0.3 * jax.random.normal(ks[2], (WIDE.heads,)),
            -1.0 + jax.random.normal(ks[3], (n,)))


def _by_chunk(t, chunk: int = WIDE.kda_chunk):
    """[B, S, H, d] -> [n, B, H, chunk, d], zeros behind the end."""
    b, s, h, d = t.shape
    t = jnp.pad(t, ((0, 0), (0, (-s) % chunk), (0, 0), (0, 0)))
    return jnp.moveaxis(t.reshape(b, -1, chunk, h, d), (1, 3), (0, 2))


@functools.lru_cache(maxsize=None)
def _fronts():
    def xla(made, *leaves):
        front = lm.kda._kda_front_xla(WIDE, made, *leaves)
        return (*(_by_chunk(t) for t in front),
                made[..., 4 * WIDE.heads * WIDE.kda_head_dim:])

    def weighed(front):
        def run(made, conv, a_log, bias, weights):
            return sum(jnp.sum(o * w) for o, w in zip(
                front(made, conv, a_log, bias), weights))
        return jax.jit(jax.grad(run, argnums=(0, 2, 3)))
    kernel = functools.partial(lm.kda._kda_front, WIDE)
    return jax.jit(xla), jax.jit(kernel), weighed(xla), weighed(kernel)


@pytest.mark.parametrize("length", [256, 300, 320])
@pytest.mark.parametrize("what", ["values", "gradient"])
def test_the_fronts_kernels_are_the_xla_form(length, what):
    """Two heads of 128 over two sequences of 256 positions (whole steps of
    two chunks), 320 (five chunks: a step is one) and 300 (the last chunk
    ragged: its rows behind the end are zeros, and write nothing back): q,
    k, v, g by chunk and the gate's columns to 1e-6; the gradient by `made`,
    `A_log` and `dt_bias`, every output weighed, to 1e-5 of its norm."""
    xla, kernel, d_xla, d_kernel = _fronts()
    args = _front_inputs(length)
    want = xla(*args)
    if what == "values":
        got = kernel(*args)
        for a, b in zip(got, want):
            assert a.shape == b.shape
            assert float(jnp.max(jnp.abs(a - b))) < 1e-6
        behind = np.asarray(got[0]).shape[0] * WIDE.kda_chunk - length
        if behind:
            for a in got[:4]:
                assert not np.any(np.asarray(a[-1, :, :, -behind:]))
        return
    weights = tuple(jax.random.normal(jax.random.key(20 + i), o.shape)
                    for i, o in enumerate(want))
    for a, b in zip(d_kernel(*args, weights), d_xla(*args, weights)):
        assert np.all(np.isfinite(np.asarray(a)))
        assert float(jnp.linalg.norm(a - b)) < 1e-5 * float(jnp.linalg.norm(b))


def test_the_first_positions_read_zeros_in_front_of_the_sequence():
    """The first step's halo is a block of the sequence itself (rows 0-7,
    the index clamped): positions 0-2 must read zeros there, so what rows
    3-7 hold moves nothing at positions 0-2, and the XLA form, which pads
    with zeros, agrees."""
    xla, kernel = _fronts()[:2]
    made, *rest = _front_inputs(256)
    rows = jnp.arange(256)[None, :, None]
    other = jnp.where((rows >= 3) & (rows < 8), 7.0 - made, made)
    got, moved, want = kernel(made, *rest), kernel(other, *rest), xla(made, *rest)
    for a, b, c in zip(got[:3], moved[:3], want[:3]):       # [n, B, H, chunk, d]
        assert np.array_equal(np.asarray(a[0, ..., :3, :]),
                              np.asarray(b[0, ..., :3, :]))
        assert not np.array_equal(np.asarray(a[0, ..., 3:8, :]),
                                  np.asarray(b[0, ..., 3:8, :]))
        assert float(jnp.max(jnp.abs(a[0, ..., :3, :] - c[0, ..., :3, :]))) < 1e-6


@pytest.mark.parametrize("t", [62, 127, 200])
def test_a_position_moves_the_next_three_and_nowhere_else(t):
    """A change of `made` at position t moves q, k, v at t to t + 3 (the
    convolution's reach) and g at t, nowhere else, bit for bit: across a
    chunk's edge (62 -> 65), across a step's of two chunks (127 -> 130) and
    inside a chunk; and a cotangent at position t reaches back to t - 3 in
    d_made's q, k, v blocks, to t alone in the decay's."""
    _, kernel, _, d_kernel = _fronts()
    made, *rest = _front_inputs(256)
    here = (jnp.arange(256) == t)[None, :, None]
    got, moved = kernel(made, *rest), kernel(jnp.where(here, made + 1.0, made), *rest)
    flat = lambda a: np.asarray(  # noqa: E731
        a.transpose(1, 0, 3, 2, 4).reshape(2, 256, -1))     # [B, S, H d]
    for i, (a, b) in enumerate(zip(got[:4], moved[:4])):
        a, b = flat(a), flat(b)
        reach = range(t, t + (4 if i < 3 else 1))
        for pos in reach:
            assert not np.array_equal(a[:, pos], b[:, pos])
        still = np.ones(256, bool)
        still[list(reach)] = False
        assert np.array_equal(a[:, still], b[:, still])
    weights = tuple(
        jnp.zeros_like(o).at[t // 64, :, :, t % 64].set(1.0) if i < 4
        else jnp.zeros_like(o) for i, o in enumerate(got))
    d_made = np.asarray(d_kernel(made, *rest, weights)[0])
    n = WIDE.heads * WIDE.kda_head_dim
    for i in range(5):
        touched = np.flatnonzero(np.any(
            d_made[..., i * n:(i + 1) * n] != 0, axis=(0, 2)))
        assert list(touched) == {0: list(range(t - 3, t + 1)), 3: [t],
                                 4: []}.get(i, list(range(t - 3, t + 1)))


def test_the_layer_with_the_kernels_is_the_layer_without(monkeypatch):
    """`kda_layer` whole at heads of 128, its front the kernel pair and the
    recurrence given operands by chunk, against the same layer through the
    XLA front and the recurrence's own move into chunks: output, and the
    gradient by the input and by every trained leaf."""
    h, d, width = WIDE.heads, WIDE.kda_head_dim, WIDE.hidden
    ks = jax.random.split(jax.random.key(8), 6)
    w = {"in": 0.1 * jax.random.normal(ks[0], (width, 5 * h * d)),
         "beta": 0.1 * jax.random.normal(ks[1], (width, h)),
         "conv": 0.5 * jax.random.normal(ks[2], (3 * h * d, WIDE.kda_conv)),
         "o": 0.1 * jax.random.normal(ks[3], (h * d, width))}
    w = {name: t.astype(jnp.bfloat16) for name, t in w.items()}
    g = {"A_log": 0.3 * jax.random.normal(ks[4], (h,)),
         "dt_bias": -2.0 + jax.random.normal(ks[5], (h * d,)),
         "o_norm": jnp.ones((d,))}
    x = jax.random.normal(jax.random.key(9), (2, 150, width))
    both = lambda: jax.jit(jax.value_and_grad(  # noqa: E731
        lambda g, x: jnp.sum(lm.kda_layer(WIDE, w, g, x) ** 2), (0, 1)))(g, x)
    assert lm.kda.kda_front_kernel(WIDE) and not lm.kda.kda_front_kernel(TINY)
    got = both()
    monkeypatch.setattr(lm.kda, "kda_front_kernel", lambda arch: False)
    want = both()
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert float(jnp.linalg.norm(a - b)) < 1e-4 * float(jnp.linalg.norm(b))


@pytest.mark.parametrize("gate,differs", [("head", False), ("channel", True),
                                          (None, True)])
def test_the_latent_layers_gate_is_one_scalar_a_head(case, ref, conf, gate,
                                                     differs):
    """The system's gated latent layer against plain attention with the gate
    a head (equal), laid over the channels in turn, and left out."""
    z, v = ref._sizes(conf), case["v"]
    i = next(i for i, (kind, _) in enumerate(z["layers"]) if kind == ref.LATENT)
    w = ref.layer_weights(z, v["base"], i)["attn"]
    g = v["params"]["blocks"][i]
    x = case["aux"]["attn_in"][i]
    got = lm.latent_attention(TINY, w, g, x)
    want = _highest(ref.latent_attention, z, w, g, x, ref._Products(None), gate)
    gap = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert (gap > 0.05) if differs else (gap < 0.01), gap
    assert w["gate"].shape == (TINY.hidden, TINY.heads) and "q_a" not in w


def test_the_four_shares_add_up_to_the_uncut_layer(ref, conf):
    """Experts 0-3, 4-7, 8-11 and 12-15 (a group each), the shared expert
    counted once: the four chips' parts sum to the uncut reference's layer."""
    n, held = TINY.n_experts, TINY.held_experts
    uncut_conf = dict(conf, num_experts=n)
    z = ref._sizes(uncut_conf)
    base = ref.init(5, uncut_conf)["base"]
    layer = ref.layer_weights(z, base, 1)
    experts = {k: t[layer["first"]:layer["first"] + n]
               for k, t in layer["experts"].items()}
    router = 0.5 * jax.random.normal(jax.random.key(9), (n, TINY.hidden))
    x = jax.random.normal(jax.random.key(4), (40, TINY.hidden), jnp.float32)
    mm = ref._Products(None)
    idx_r, w_r = _highest(ref.route, z, router, layer["bias"], x)
    routed, _ = _highest(ref.held_experts, z, experts, x, idx_r, w_r, mm)
    shared = _highest(ref._glu, layer["shared"], x, mm)
    idx, weights = lm.route(TINY, router, layer["bias"], x)
    assert jnp.array_equal(jnp.sort(idx, -1), jnp.sort(idx_r, -1))
    assert jnp.allclose(jnp.sum(weights, -1), TINY.routed_scaling, atol=1e-5)
    # the choice is group-limited: a token's experts lie in 2 of the 4 groups
    assert int(jnp.max(jnp.sum(jnp.any(
        (idx // held)[:, :, None] == jnp.arange(4), 1), -1))) <= TINY.topk_group
    parts, pairs = [], 0
    for start in range(0, n, held):
        share = dataclasses.replace(TINY, held_start=start)
        w = {"experts": {k: t[start:start + held] for k, t in experts.items()},
             "bias": layer["bias"], "shared": layer["shared"]}
        y, load, _ = lm.experts.expert_layer(share, w, router, x[None])
        parts.append(y[0])
        pairs += int(jnp.sum(load))
    assert len(parts) == 4 and pairs == 40 * TINY.experts_per_tok
    mine = lm.experts.glu(layer["shared"], x)            # what every chip computes alike
    whole = sum(parts) - 3 * mine
    want = routed + shared
    assert float(jnp.max(jnp.abs(whole - want))) < 0.03 * float(jnp.std(want))
    # a share alone is not the layer
    assert float(jnp.max(jnp.abs(parts[0] - want))) > 0.2 * float(jnp.std(want))


def test_held_pairs_behind_the_front_go_through_the_blocks():
    """`_held_counted`: with a front smaller than the held pairs the rest is
    computed in blocks, and the result is the all-in-front one's; several
    layers' experts along one axis give the layer at `at` its own."""
    arch = dataclasses.replace(TINY, pair_front=32)
    t, d, f = 64, TINY.hidden, TINY.moe_intermediate
    ks = jax.random.split(jax.random.key(7), 5)
    w = {"gate_up": (0.1 * jax.random.normal(ks[0], (12, d, 2 * f))).astype(jnp.bfloat16),
         "down": (0.1 * jax.random.normal(ks[1], (12, f, d))).astype(jnp.bfloat16)}
    x = jax.random.normal(ks[2], (t, d))
    idx = jax.random.randint(ks[3], (t, 2), 0, 6)      # two thirds held
    weights = jax.random.uniform(ks[4], (t, 2))
    layer = {k: v[4:8] for k, v in w.items()}
    f_of = lambda a, w, at: lambda x, weights: lm.held_experts(  # noqa: E731
        a, w, x, idx, weights, at)[0]
    whole = dataclasses.replace(TINY, pair_front=0)
    y_all, vjp_all = jax.vjp(f_of(whole, layer, None), x, weights)
    y, vjp = jax.vjp(f_of(arch, w, 1), x, weights)
    assert int(jnp.sum((idx < 4))) > 32               # the blocks have work
    assert float(jnp.max(jnp.abs(y - y_all))) < 1e-2 * float(jnp.std(y_all))
    dy = jax.random.normal(jax.random.key(8), y.shape)
    for a, b in zip(vjp(dy), vjp_all(dy)):
        assert float(jnp.linalg.norm(a - b)) < 0.02 * float(jnp.linalg.norm(b))
    load = lm.held_experts(arch, w, x, idx, weights, 1)[1]
    assert np.array_equal(np.asarray(load), np.bincount(
        np.asarray(idx).ravel(), minlength=6)[:4])
    # and the order by counting is the stable sort's
    key = jnp.where(idx < 4, idx, 4).reshape(-1)
    pos, order, counts = lm.experts._counted_order(key, 5)
    assert np.array_equal(np.asarray(order), np.argsort(np.asarray(key),
                                                        kind="stable"))
    assert np.array_equal(np.asarray(order)[np.asarray(pos)], np.arange(t * 2))
    assert int(counts.sum()) == t * 2


def test_forward_flops_and_the_roofline_counts_are_the_models_own(ref):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "ling-3-flash-l6e128.json")) as f:
        conf = json.load(f)
    assert ref.kda_scan_flops(conf) == 6 * 128 * 128 * 32 == 3_145_728
    assert ref.kda_scan_bytes(conf) == (5 * 32 * 128 + 32) * 4 == 82_048
    got = ref.forward_flops(conf, 8192)
    kda = 2 * 2560 * (6 * 4096 + 32) + 2 * 4 * 3 * 4096 + 3_145_728
    mla = 2 * (2560 * 6144 + 2560 * 576 + 512 * 8192 + 4096 * 2560 + 2560 * 32) \
        + 2 * 32 * (192 + 128) * 8193 / 2
    experts = 8 * 128 / 512 * 6 * 2560 * 768 + 6 * 2560 * 768 + 2 * 512 * 2560
    want = (5 * kda + mla + 6 * 2560 * 6144 + 5 * experts + 2 * 2560 * 19648)
    assert got["total"] == pytest.approx(want, rel=1e-12)
    assert got["linear_recurrence"] == 3_145_728
    assert 1.1e9 < got["total"] < 1.25e9             # ISSUE 41: 1.18 GFLOP a token
    assert 5 * (got["linear_projections"] + got["linear_recurrence"]) > 0.5 * (
        got["total"])                                # the new mechanism decides
    import sys
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))   # its `device_scopes`
    reader = _load("layer_metrics", "kda_scan_roofline_pct")
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    conf["positions"] = 8192
    must = reader.must_take_s(conf, 2, peaks)
    assert must == pytest.approx(3 * 5 * 2 * 8192 * 82_048 / 819e9)   # the bytes
    assert must > 3 * 5 * 2 * 8192 * 3_145_728 / 197e12
    assert reader.configuration()["positions"] == 8192


PUBLISHED = {   # the catalog's row `Ling-3.0-flash`, `config`, key for key
    "expert_swiglu_limit_list": [0] * 35 + [4] * 7,
    "first_k_dense_replace": 2,
    "gated_attention_proj_granularity_type": "head_wise", "group_norm_size": 1,
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 6144, "kda_lower_bound": -5, "kda_safe_gate": True,
    "kv_lora_rank": 512, "layer_group_size": 6, "linear_silu": True,
    "max_position_embeddings": 262144, "max_window_layers": 20,
    "moe_intermediate_size": 768, "moe_router_enable_expert_bias": True,
    "moe_shared_expert_intermediate_size": 768, "mtp_loss_scaling_factor": 0,
    "mtp_use_kda": False, "n_group": 8, "no_kda_lora": True,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 512,
    "num_experts_per_tok": 8, "num_hidden_layers": 42,
    "num_key_value_heads": 32, "num_kv_heads_for_linear_attn": 0,
    "num_nextn_predict_layers": 1, "num_shared_experts": 1,
    "partial_rotary_factor": 0.5, "q_lora_rank": None, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 6000000,
    "rotary_dim": 64, "routed_scaling_factor": 2.5, "scale_router_input": False,
    "score_function": "sigmoid", "scoring_func": "sigmoid", "seq_aux": True,
    "share_expert_swiglu_limit_list": [0] * 34 + [5] * 6 + [7] * 2,
    "short_conv_kernel_size": 4, "tie_word_embeddings": False, "topk_group": 4,
    "topk_method": "noaux_tc", "up_proj_norm": False, "use_bias": False,
    "use_kda_lora": False, "use_mla_nope": False, "use_nGPT": False,
    "use_qk_norm": True, "use_qkv_bias": False, "v_head_dim": 128,
    "value_norm": False, "vocab_size": 157184, "model_type": "bailing_hybrid"}


def test_published_preset_is_the_configuration_file_and_the_catalogs_row(ref):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "ling-3-flash-l6e128.json")) as f:
        conf = json.load(f)
    cut = {k for k, v in PUBLISHED.items() if conf[k] != v}
    assert cut == set(conf["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size",
        "num_nextn_predict_layers"}
    assert {k: conf["published"][k] for k in cut} == {k: PUBLISHED[k] for k in cut}
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Ling-3.0-flash")
        assert row["config"] == PUBLISHED and row["source_url"] == conf["source"]
    a = lm.PRESETS["ling_3_flash"]
    for field, key in (
            ("hidden", "hidden_size"), ("heads", "num_attention_heads"),
            ("kda_head_dim", "head_dim"), ("kda_conv", "short_conv_kernel_size"),
            ("kda_lower_bound", "kda_lower_bound"), ("kv_lora_rank", "kv_lora_rank"),
            ("qk_nope_head_dim", "qk_nope_head_dim"),
            ("qk_rope_head_dim", "qk_rope_head_dim"), ("v_head_dim", "v_head_dim"),
            ("intermediate", "intermediate_size"),
            ("moe_intermediate", "moe_intermediate_size"),
            ("moe_intermediate", "moe_shared_expert_intermediate_size"),
            ("held_experts", "num_experts"),
            ("experts_per_tok", "num_experts_per_tok"), ("n_group", "n_group"),
            ("topk_group", "topk_group"),
            ("routed_scaling", "routed_scaling_factor"),
            ("rope_theta", "rope_theta"), ("eps", "rms_norm_eps"),
            ("shared_experts", "num_shared_experts"),
            ("mtp_modules", "num_nextn_predict_layers")):
        assert getattr(a, field) == conf[key], (field, key)
    assert a.q_lora_rank == 0 and conf["q_lora_rank"] is None
    assert a.n_experts == conf["held"]["router_width"] == 512
    assert a.held_start == conf["held"]["first_expert"] == 0
    assert a.kda_chunk == conf["held"]["chunk"] == 64 and a.kda_block == 16
    assert a.kda_block * -a.kda_lower_bound < 88          # float32 holds exp of it
    assert a.attn_gate and a.rope_scaling is None
    kinds = ref.layer_kinds(conf)
    assert [k for k, _ in kinds] == list(a.layer_pattern) == [1, 1, 1, 1, 0, 1]
    assert [d for _, d in kinds] == [True] + [False] * 5
    assert a.dense_layers == 1 and a.expert_layers == 5
    assert conf["deployment"]["chips_sharing_an_expert_layer"] == 4
    assert conf["deployment"]["chips_sharing_the_vocabulary"] == 8
    module = lm.FrozenBaseLM(num_classes=conf["vocab_size"], arch=a)
    count = lambda t: sum(int(np.prod(s.shape)) for s in  # noqa: E731
                          jax.tree_util.tree_leaves(t))
    trained = count(jax.eval_shape(module.init_trained))
    assert trained == conf["deployment"]["trained_parameters"] == 6_608_672
    assert -(-trained // 4096) == conf["deployment"]["ciphertexts_a_client"] == 1614
    base = jax.eval_shape(module.init_base)
    assert count(base) - 5 * 512 == 4_299_341_824           # 8.60 GB in bfloat16
    for key in ("source", "described", "published", "deployment", "assumed",
                "limits", "limit_reasons"):
        assert conf[key]
    assert set(conf["limits"]) <= set(conf["limit_reasons"])
    assert "env" not in conf
    assert conf["experiment"]["dataset"] == "tokens-v19648-s8192"


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmarks", "reference",
                           "ling_3_flash.py")) as f:
        src = f.read()
    assert "hefl_tpu" not in src.replace("`hefl_tpu", "").replace(
        "hefl_tpu/", "") and "import lm" not in src
    imports = [ln.split()[1].split(".")[0] for ln in src.splitlines()
               if ln.startswith(("import ", "from "))]
    assert set(imports) <= {"__future__", "functools", "math", "jax", "numpy"}
    assert "lax.scan(step" in src        # position by position


def test_encrypted_round_with_a_ragged_last_row_is_the_plain_mean():
    module, params = create_model("ling_3_flash_tiny", num_classes=64, seed=5)
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
    # 3 routers, 9 gains of 64, kv_norm, 3 x (A_log 4, dt_bias 64, o_norm 16)
    assert spec.total == total == 3 * 16 * 64 + 9 * 64 + 32 + 3 * (4 + 64 + 16)
    assert total % ctx.n == 92          # the last row is ragged
    assert ct.c0.shape[0] == spec.n_ct == -(-total // ctx.n) == 16
    avg = decrypt_average(ctx, sk, ct, 2, spec)
    assert jax.tree_util.tree_structure(avg) == jax.tree_util.tree_structure(params)
    host = lambda t: [np.asarray(a) for a in jax.tree_util.tree_leaves(t)]  # noqa: E731
    for a, b, p0 in zip(host(avg), host(plain), host(params)):
        assert float(np.max(np.abs(a - b))) < 5e-5
        assert float(np.max(np.abs(b - p0))) > 0         # every leaf trained,
    moved = [np.asarray(g["dt_bias"]) for g in plain["blocks"] if "dt_bias" in g]
    assert len(moved) == 3 and all(np.all(m != 0) for m in moved)  # the decay too
    assert int(np.sum(np.asarray(overflow))) == 0
    assert np.all(np.isfinite(np.asarray(mets)))
    for a, b in zip(jax.tree_util.tree_leaves(before),
                    jax.tree_util.tree_leaves(frozen_base(module))):
        assert np.array_equal(a, np.asarray(b))            # bit for bit
    set_frozen_base(module, None)
