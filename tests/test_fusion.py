"""Client-fusion primitives + backend resolution + prefetch (ISSUE 3).

Unit-level coverage of the fused cross-client backend's building blocks:

  * folded layer math — `folded_apply` / `folded_conv` against the
    vmapped flax reference (forward AND gradients, strides/padding);
  * backend resolution — pins, junk, unsupported-model fallback (the rule
    itself is tests/test_backends.py's table);
  * RoundPrefetcher — identity short-circuit, staged promotion, stale
    buffer retirement.

Trainer-level fused-vs-vmap equivalence lives in tests/test_perf.py; the
masked round engine on the fused backend in tests/test_faults.py.
"""

import dataclasses
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from hefl_tpu.models import LogReg, MedCNN, ResNet20, SmallCNN
from hefl_tpu.models.folded import (
    fold_clients,
    folded_conv,
    pack_clients,
    pack_size,
    packed_group_norm,
    stack_params,
    unfold_clients,
    unpack_clients,
)


def _stacked(model, shape, c, seed=0):
    p0 = model.init(jax.random.key(seed), jnp.zeros((1,) + shape))["params"]
    # distinct per-client weights: fusion must be exact for DIVERGED
    # clients, not just the all-identical round entry
    return jax.tree_util.tree_map(
        lambda t: jnp.stack([t * (1 + 0.05 * i) for i in range(c)]), p0
    )


@pytest.mark.parametrize(
    "model,shape,atol",
    [
        (SmallCNN(num_classes=10), (28, 28, 1), 1e-4),
        # 100x100: SmallCNN's first stage takes the polyphase form (block 4)
        # in both lowerings, through the one helper (models.cnn._conv_stages)
        (SmallCNN(num_classes=10), (100, 100, 1), 2e-3),
        (LogReg(num_classes=10), (28, 28, 1), 1e-6),
        # 20 bf16 layers accumulate reduction-order drift; tolerance, not
        # approximation (every layer is exact math — see models.folded).
        (ResNet20(num_classes=10), (32, 32, 3), 5e-2),
    ],
)
def test_folded_apply_matches_vmap_forward(model, shape, atol):
    c, b = 3, 4
    ps = _stacked(model, shape, c)
    x = jax.random.uniform(jax.random.key(1), (c, b) + shape)
    ref = jax.vmap(lambda p, xx: model.apply({"params": p}, xx))(ps, x)
    got = unfold_clients(
        jax.jit(
            lambda ps, xf: model.folded_apply(ps, xf, num_clients=c)
        )(ps, fold_clients(x)),
        c,
    )
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got), atol=atol)


def test_polyphase_stage_count_is_recorded():
    # `model.polyphase_stages`: how many conv stages took the polyphase form
    # when the model was last traced, in every run's record (obs.metrics).
    from hefl_tpu.models import create_model
    from hefl_tpu.obs import metrics as obs_metrics

    def stages():
        return obs_metrics.snapshot()["model.polyphase_stages"]

    create_model("medcnn", input_shape=(256, 256, 3))
    assert stages() == 2
    module, params = create_model("smallcnn", input_shape=(100, 100, 1))
    assert stages() == 1
    create_model("resnet20")
    assert stages() == 0
    jax.eval_shape(
        lambda p, x: module.folded_apply(p, x, num_clients=2),
        stack_params(params, 2), jnp.zeros((4, 100, 100, 1)),
    )
    assert stages() == 1


def test_folded_apply_matches_vmap_forward_medcnn():
    # The flagship model at its real 256x256 geometry (6 VALID conv/pool
    # stages collapse smaller inputs to nothing), tiny batch.
    c, b = 2, 2
    model = MedCNN()
    ps = _stacked(model, (256, 256, 3), c)
    x = jax.random.uniform(jax.random.key(2), (c, b, 256, 256, 3))
    ref = jax.vmap(lambda p, xx: model.apply({"params": p}, xx))(ps, x)
    got = unfold_clients(
        jax.jit(
            lambda ps, xf: model.folded_apply(ps, xf, num_clients=c)
        )(ps, fold_clients(x)),
        c,
    )
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got), atol=5e-3)


def test_folded_polyphase_stage_matches_vmap_loss_and_every_gradient():
    # PR 35: in a polyphase stage the bias is added inside the pool, which
    # returns the bias gradient itself. Under the `fused` lowering that bias
    # has a client axis over a client-folded batch: SmallCNN at 100x100 takes
    # block 4 in its first stage, and every leaf's gradient, the bias leaves
    # included, must be `vmap`'s (clients with distinct weights and biases).
    import optax

    c, b, shape = 3, 4, (100, 100, 1)
    model = SmallCNN(num_classes=10)
    ps = _stacked(model, shape, c)
    ps = jax.tree_util.tree_map(  # flax starts a bias at zero: move it
        lambda t: t + 0.1 * jax.random.normal(jax.random.key(t.size), t.shape)
        if t.ndim == 2 else t, ps)
    x = jax.random.uniform(jax.random.key(1), (c, b) + shape)
    labels = jax.random.randint(jax.random.key(2), (c, b), 0, 10)

    def xent(logits):  # [C, B, classes] -> the clients' mean losses, summed
        return optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean(-1).sum()

    vmapped = lambda ps: xent(jax.vmap(  # noqa: E731
        lambda p, xx: model.apply({"params": p}, xx))(ps, x))
    fused = lambda ps: xent(unfold_clients(  # noqa: E731
        model.folded_apply(ps, fold_clients(x), num_clients=c), c))
    (l_ref, g_ref), (l_got, g_got) = (
        jax.jit(jax.value_and_grad(f))(ps) for f in (vmapped, fused))
    np.testing.assert_allclose(float(l_got), float(l_ref), rtol=1e-3)
    flat_ref = jax.tree_util.tree_flatten_with_path(g_ref)[0]
    assert len(flat_ref) == 8
    for (path, ga), gb in zip(flat_ref, jax.tree_util.tree_leaves(g_got)):
        scale = float(jnp.max(jnp.abs(ga)))
        assert scale > 0, path
        np.testing.assert_allclose(
            np.asarray(ga) / scale, np.asarray(gb) / scale, atol=2e-2,
            err_msg=jax.tree_util.keystr(path))


# ------------------------------------------- ResNet-20, clients in the lanes


def _resnet_clients(c, b=2, hw=16, seed=0):
    """ResNet-20 with `c` clients of distinct weights (every leaf moved, the
    zero-started biases too), `b` images a client, labels."""
    model = ResNet20(num_classes=10)
    ps = _stacked(model, (hw, hw, 3), c, seed)
    ps = jax.tree_util.tree_map(
        lambda t: t + 0.05 * jax.random.normal(jax.random.key(t.size), t.shape), ps)
    x = jax.random.uniform(jax.random.key(seed + 1), (c, b, hw, hw, 3))
    labels = jax.random.randint(jax.random.key(seed + 2), (c, b), 0, 10)
    return model, ps, x, labels


def _vmapped_logits(model, ps, x):
    return jax.vmap(lambda p, xx: model.apply({"params": p}, xx))(ps, x)


def _packed_logits(model, ps, x):
    c = x.shape[0]
    return unfold_clients(
        model.folded_apply(ps, fold_clients(x), num_clients=c), c)


@pytest.mark.parametrize("c,packs", [
    (8, (8, 4, 2)),    # one full pack at stage 1, cut in two twice
    (16, (8, 4, 2)),   # two packs at stage 1
    (6, (6, 3, 2)),    # 3 -> 2: no cut of the lanes, clients dealt anew
    (7, (7, 1, 1)),    # a pack of 7, then no divisor but 1
    (11, (1, 1, 1)),   # no divisor but 1: the plain convolution a client
])
def test_packed_resnet_forward_matches_vmap(c, packs):
    assert tuple(pack_size(c, w) for w in (16, 32, 64)) == packs
    model, ps, x, _ = _resnet_clients(c)
    ref = jax.jit(partial(_vmapped_logits, model))(ps, x)
    got = jax.jit(partial(_packed_logits, model))(ps, x)
    assert got.dtype == jnp.float32 and got.shape == (c, 2, 10)
    # bfloat16 logits: one rounding step is 0.0625 at a logit of 8
    np.testing.assert_allclose(
        np.asarray(ref), np.asarray(got), atol=5e-2, rtol=1e-2)


@pytest.mark.parametrize("c", [8, 6])
def test_packed_resnet_loss_and_every_gradient_match_vmap(c):
    # The block-diagonal kernel is built inside the differentiated function:
    # each client's kernel gradient is its own diagonal block of the dense
    # convolution's, every leaf against `vmap(grad(model.apply))`.
    import optax

    model, ps, x, labels = _resnet_clients(c)

    def xent(logits):  # [C, B, classes] -> the clients' mean losses, summed
        return optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean(-1).sum()

    (l_ref, g_ref), (l_got, g_got) = (
        jax.jit(jax.value_and_grad(lambda ps: xent(f(model, ps, x))))(ps)
        for f in (_vmapped_logits, _packed_logits))
    np.testing.assert_allclose(float(l_got), float(l_ref), rtol=2e-3)
    flat_ref = jax.tree_util.tree_flatten_with_path(g_ref)[0]
    assert len(flat_ref) == 65
    # Two lowerings of 20 bfloat16 layers drift apart: `vmap` against one
    # `apply` a client reads up to 0.31 a leaf on these inputs, the packed
    # form 0.15; another client's gradient in a client's place reads 1.3.
    gap = lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(a))  # noqa: E731
    for (path, ga), gb in zip(flat_ref, jax.tree_util.tree_leaves(g_got)):
        assert ga.shape == gb.shape and gb.dtype == jnp.float32
        assert gap(ga, gb) < 0.3, (jax.tree_util.keystr(path), gap(ga, gb))
        assert gap(ga, jnp.roll(gb, 1, axis=0)) > 1.0, jax.tree_util.keystr(path)
    whole = lambda g: jnp.concatenate(  # noqa: E731
        [t.ravel() for t in jax.tree_util.tree_leaves(g)])
    assert gap(whole(g_ref), whole(g_got)) < 0.2


@pytest.mark.parametrize("what", ["images", "weights"])
def test_packed_resnet_clients_are_independent(what):
    # The off-diagonal blocks multiply exact zeros: changing one client's
    # images or weights leaves every other client's logits and gradients
    # BITWISE unchanged, in the same pack (clients 0-7) and outside it.
    c, moved = 16, 3
    model, ps, x, labels = _resnet_clients(c)

    @jax.jit
    def run(ps, x):
        def loss(ps):
            logits = _packed_logits(model, ps, x)
            picked = jnp.take_along_axis(
                jax.nn.log_softmax(logits), labels[..., None], -1)
            return -picked.mean(), logits
        return jax.grad(loss, has_aux=True)(ps)

    base = run(ps, x)
    if what == "images":
        pert = run(ps, x.at[moved].multiply(0.5))
    else:
        pert = run(jax.tree_util.tree_map(lambda t: t.at[moved].multiply(1.1), ps), x)
    others = np.arange(c) != moved
    for a, b in zip(jax.tree_util.tree_leaves(base), jax.tree_util.tree_leaves(pert)):
        a, b = np.asarray(a), np.asarray(b)
        np.testing.assert_array_equal(a[others], b[others])
        assert not np.array_equal(a[moved], b[moved])


@pytest.mark.parametrize("width", [16, 32, 64])
def test_packed_group_norm_matches_flax_a_client(width):
    import flax.linen as nn

    c, b, hw = 8, 3, 6
    g = pack_size(c, width)
    assert g * width == 128
    x = (2.0 * jax.random.normal(jax.random.key(0), (c, b, hw, hw, width)) + 0.5
         ).astype(jnp.bfloat16)
    scale = 1.0 + 0.3 * jax.random.normal(jax.random.key(1), (c, width))
    bias = 0.3 * jax.random.normal(jax.random.key(2), (c, width))
    norm = nn.GroupNorm(num_groups=8, dtype=jnp.float32)

    def ref(x, scale, bias):
        return jax.vmap(lambda xx, s, bb: norm.apply(
            {"params": {"scale": s, "bias": bb}}, xx))(x, scale, bias)

    def got(x, scale, bias):
        return unpack_clients(
            packed_group_norm(pack_clients(x, g), scale, bias, g, num_groups=8), g)

    want = ref(x, scale, bias)
    have = got(x, scale, bias)
    assert have.dtype == jnp.float32 and have.shape == want.shape
    np.testing.assert_allclose(np.asarray(want), np.asarray(have), atol=2e-5)
    cot = jax.random.normal(jax.random.key(3), want.shape)
    for ga, gb in zip(*(jax.grad(lambda *a: jnp.sum(f(*a) * cot), argnums=(0, 1, 2))(
            x.astype(jnp.float32), scale, bias) for f in (ref, got))):
        np.testing.assert_allclose(np.asarray(ga), np.asarray(gb), atol=2e-4)


def test_packed_conv_layer_count_is_recorded():
    # `model.packed_conv_layers`: the convolutions of the last traced
    # client-folded forward that ran lane-packed, in every run's record.
    from hefl_tpu.models import create_model
    from hefl_tpu.obs import metrics as obs_metrics

    def layers():
        return obs_metrics.snapshot()["model.packed_conv_layers"]

    module, params = create_model("resnet20")
    assert layers() == 0
    for c, want in ((8, 21), (7, 9), (11, 0)):
        jax.eval_shape(
            lambda p, x: module.folded_apply(p, x, num_clients=c),
            stack_params(params, c), jnp.zeros((2 * c, 32, 32, 3)))
        assert layers() == want
    create_model("medcnn", input_shape=(256, 256, 3))
    assert layers() == 0
    obs_metrics.gauge("model.packed_conv_layers").set(21)
    create_model("joyai_llm_flash_tiny", num_classes=64)  # a token model too
    assert layers() == 0


@pytest.mark.parametrize("strides,padding", [((1, 1), "VALID"), ((2, 2), "SAME")])
def test_folded_conv_matches_flax_forward_and_grad(strides, padding):
    import flax.linen as nn

    c, b, h, w, ch, f = 3, 4, 16, 16, 8, 16
    kern = jax.random.normal(jax.random.key(1), (c, 3, 3, ch, f)) * 0.1
    x = jax.random.uniform(jax.random.key(0), (c, b, h, w, ch))

    class Cv(nn.Module):
        @nn.compact
        def __call__(self, t):
            return nn.Conv(
                f, (3, 3), strides=strides, padding=padding, use_bias=False,
                dtype=jnp.bfloat16, param_dtype=jnp.float32,
            )(t)

    m = Cv()
    ref_fwd = lambda k: jax.vmap(  # noqa: E731
        lambda kk, xx: m.apply({"params": {"Conv_0": {"kernel": kk}}}, xx)
    )(k, x).astype(jnp.float32)
    fold_fwd = lambda k: unfold_clients(  # noqa: E731
        folded_conv(
            fold_clients(x), k, None, num_clients=c,
            strides=strides, padding=padding,
        ), c
    ).astype(jnp.float32)
    np.testing.assert_allclose(
        np.asarray(ref_fwd(kern)), np.asarray(fold_fwd(kern)), atol=1e-2
    )
    ga = jax.grad(lambda k: jnp.sum(ref_fwd(k)))(kern)
    gb = jax.grad(lambda k: jnp.sum(fold_fwd(k)))(kern)
    scale = float(jnp.max(jnp.abs(ga))) + 1e-9
    np.testing.assert_allclose(
        np.asarray(ga) / scale, np.asarray(gb) / scale, atol=1e-3
    )


def test_folded_conv_clients_are_independent():
    # Block structure: perturbing client 1's folded rows must leave client
    # 0's outputs BITWISE untouched (what the masked round engine's
    # same-program independence rests on).
    c, b = 3, 4
    kern = jax.random.normal(jax.random.key(1), (c, 3, 3, 2, 8)) * 0.1
    x = jax.random.uniform(jax.random.key(0), (c, b, 12, 12, 2))
    f = jax.jit(
        lambda xf: folded_conv(xf, kern, None, num_clients=c)
    )
    base = np.asarray(f(fold_clients(x)).astype(jnp.float32))
    x2 = x.at[1].multiply(3.0)
    pert = np.asarray(f(fold_clients(x2)).astype(jnp.float32))
    np.testing.assert_array_equal(base[:b], pert[:b])
    np.testing.assert_array_equal(base[2 * b :], pert[2 * b :])
    assert not np.array_equal(base[b : 2 * b], pert[b : 2 * b])


# ----------------------------------------------------- backend resolution


def test_resolve_fusion_backend_pins_and_errors():
    # What tests/test_backends.py::test_backend_rule's table does not say.
    from hefl_tpu.fl import fusion

    model = SmallCNN(num_classes=10)
    assert fusion.resolve_fusion_backend("vmap", model) == "vmap"
    assert fusion.resolve_fusion_backend(None, model) == "vmap"
    # a model whose client-folded forward is lane-packed says so, and a pin
    # is still taken as given
    packed = ResNet20(num_classes=10)
    assert fusion.resolve_fusion_backend(None, packed) == "fused"
    assert fusion.resolve_fusion_backend("vmap", packed) == "vmap"
    assert fusion.resolve_fusion_backend("fused", packed) == "fused"
    assert fusion.fusion_report(None, packed) == {
        "requested": "auto", "backend": "fused"}
    assert fusion.fusion_report("auto", MedCNN()) == {
        "requested": "auto", "backend": "vmap"}
    with pytest.raises(ValueError):
        fusion.resolve_fusion_backend("fancy", model)

    class NoFold:
        pass

    # a model without the client-folded forward still trains under "auto"
    assert fusion.resolve_fusion_backend("auto", NoFold()) == "vmap"
    assert fusion.fusion_report("fused", model) == {
        "requested": "fused", "backend": "fused"}
    assert fusion.fusion_report(None, NoFold()) == {
        "requested": "auto", "backend": "vmap"}


# ------------------------------------------------------------- prefetcher


def test_round_prefetcher_identity_short_circuit():
    from hefl_tpu.data import RoundPrefetcher

    xs = np.arange(24, dtype=np.uint8).reshape(2, 12)
    ys = np.arange(2, dtype=np.int32)
    pf = RoundPrefetcher()
    a = pf.get(xs, ys)
    np.testing.assert_array_equal(np.asarray(a[0]), xs)
    # same host arrays -> the SAME resident device buffers, no new copy
    b = pf.get(xs, ys)
    assert a[0] is b[0] and a[1] is b[1]
    pf.prefetch(xs, ys)  # no-op: already resident
    assert pf.get(xs, ys)[0] is a[0]


def test_round_prefetcher_stages_and_retires():
    from hefl_tpu.data import RoundPrefetcher

    pf = RoundPrefetcher()
    r0 = (np.zeros((2, 4), np.float32), np.zeros(2, np.int32))
    r1 = (np.ones((2, 4), np.float32), np.ones(2, np.int32))
    cur = pf.get(*r0)
    pf.prefetch(*r1)                    # async copy overlaps "round 0"
    staged = pf._next[0][0]
    nxt = pf.get(*r1)                   # promote the staged buffers
    assert nxt[0] is staged
    np.testing.assert_array_equal(np.asarray(nxt[0]), r1[0])
    # round 0's buffers were retired (deleted) on promotion
    assert cur[0].is_deleted()


def test_round_prefetcher_never_deletes_caller_arrays():
    # A caller-owned DEVICE-resident array passed straight through must
    # survive the ring's retirement (the ring only deletes buffers it
    # copied itself).
    from hefl_tpu.data import RoundPrefetcher

    pf = RoundPrefetcher()
    dev0 = jnp.arange(8, dtype=jnp.float32)   # already device-resident
    got = pf.get(dev0)
    r1 = (np.ones(8, np.float32),)
    pf.get(*r1)                               # retires round 0's entry
    assert not dev0.is_deleted()
    np.testing.assert_array_equal(np.asarray(dev0), np.asarray(got[0]))


# -------------------------------------------------- hoisted padding gather


def test_prepadded_round_matches_per_round_gather():
    from hefl_tpu.data import iid_contiguous, make_dataset, stack_federated
    from hefl_tpu.fl import TrainConfig, fedavg_round
    from hefl_tpu.fl.fedavg import pad_federated
    from hefl_tpu.parallel import client_mesh_size, make_mesh

    num_clients = 3  # does not divide the 4-device mesh -> 1 padding slot
    (x, y), _, _ = make_dataset("mnist", seed=0, n_train=48, n_test=8)
    xs, ys = stack_federated(x, y, iid_contiguous(48, num_clients))
    model = SmallCNN(num_classes=10)
    params = model.init(jax.random.key(0), jnp.zeros((1, 28, 28, 1)))["params"]
    cfg = TrainConfig(
        epochs=1, batch_size=8, num_classes=10, augment=False,
        val_fraction=0.25,
    )
    mesh = make_mesh(4)
    key = jax.random.key(9)
    p_legacy, m_legacy, meta_legacy = fedavg_round(
        model, cfg, mesh, params, jnp.asarray(xs), jnp.asarray(ys), key
    )
    xs_p, ys_p, num_real = pad_federated(xs, ys, client_mesh_size(mesh))
    assert num_real == num_clients
    p_pre, m_pre, meta_pre = fedavg_round(
        model, cfg, mesh, params, jnp.asarray(xs_p), jnp.asarray(ys_p), key,
        num_real_clients=num_real,
    )
    # identical program, identical inputs -> bitwise identical round
    for a, b in zip(jax.tree_util.tree_leaves(p_legacy),
                    jax.tree_util.tree_leaves(p_pre)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(m_legacy), np.asarray(m_pre))
    assert meta_pre.num_clients == num_clients
    assert meta_pre.surviving == meta_legacy.surviving == num_clients
    # wrong-shape contract violation fails loudly
    with pytest.raises(ValueError, match="pre-padded"):
        fedavg_round(
            model, cfg, mesh, params, jnp.asarray(xs), jnp.asarray(ys), key,
            num_real_clients=num_clients,
        )


def test_train_clients_prepadded_matches_gather():
    from hefl_tpu.data import iid_contiguous, make_dataset, stack_federated
    from hefl_tpu.fl import TrainConfig, train_clients
    from hefl_tpu.fl.fedavg import pad_federated
    from hefl_tpu.parallel import client_mesh_size, make_mesh

    num_clients = 3
    (x, y), _, _ = make_dataset("mnist", seed=1, n_train=48, n_test=8)
    xs, ys = stack_federated(x, y, iid_contiguous(48, num_clients))
    model = SmallCNN(num_classes=10)
    params = model.init(jax.random.key(0), jnp.zeros((1, 28, 28, 1)))["params"]
    cfg = TrainConfig(
        epochs=1, batch_size=8, num_classes=10, augment=False,
        val_fraction=0.25,
    )
    mesh = make_mesh(4)
    key = jax.random.key(3)
    p_a, m_a = train_clients(
        model, cfg, mesh, params, jnp.asarray(xs), jnp.asarray(ys), key
    )
    xs_p, ys_p, num_real = pad_federated(xs, ys, client_mesh_size(mesh))
    p_b, m_b = train_clients(
        model, cfg, mesh, params, jnp.asarray(xs_p), jnp.asarray(ys_p), key,
        num_real_clients=num_real,
    )
    for a, b in zip(jax.tree_util.tree_leaves(p_a),
                    jax.tree_util.tree_leaves(p_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(m_a), np.asarray(m_b))
