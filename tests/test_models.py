"""Model zoo contract tests (SURVEY.md §2.3 sizing is the HE contract)."""

import hashlib

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from hefl_tpu.models import MedCNN, ResNet20, SmallCNN, cnn, count_params, create_model
from hefl_tpu.obs import metrics as obs_metrics


def test_medcnn_parameter_count_matches_reference():
    # The reference CNN has exactly 222,722 params in 18 tensors
    # (verified arithmetic in SURVEY.md §2.3); encrypted-FedAvg packing
    # sizes ciphertext counts from this number.
    _, params = create_model("medcnn", num_classes=2, input_shape=(256, 256, 3))
    assert count_params(params) == 222_722
    assert len(jax.tree_util.tree_leaves(params)) == 18


def test_medcnn_per_layer_shapes():
    _, params = create_model("medcnn", num_classes=2, input_shape=(256, 256, 3))
    # Exact per-tensor size multiset derived from SURVEY §2.3's per-layer
    # totals (kernel + bias per parameterized layer) — this is the HE
    # ciphertext-packing contract, so check every tensor, not the sum.
    kernels = sorted(int(x.size) for x in jax.tree_util.tree_leaves(params) if x.ndim > 1)
    biases = sorted(int(x.size) for x in jax.tree_util.tree_leaves(params) if x.ndim == 1)
    assert kernels == sorted([864, 9216, 9216, 18432, 36864, 73728, 65536, 8192, 128])
    assert biases == sorted([32, 32, 32, 64, 64, 128, 128, 64, 2])


def test_medcnn_forward_shape_and_dtype():
    model, params = create_model("medcnn", num_classes=2, input_shape=(256, 256, 3))
    x = jnp.zeros((4, 256, 256, 3), jnp.float32)
    logits = jax.jit(lambda p, x: model.apply({"params": p}, x))(params, x)
    assert logits.shape == (4, 2)
    assert logits.dtype == jnp.float32


def test_medcnn_softmax_head_matches_keras_output():
    model = MedCNN(num_classes=2, apply_softmax=True)
    params = model.init(jax.random.key(0), jnp.zeros((1, 256, 256, 3)))["params"]
    probs = model.apply({"params": params}, jnp.ones((3, 256, 256, 3)) * 0.5)
    assert jnp.allclose(jnp.sum(probs, axis=-1), 1.0, atol=1e-5)


def test_smallcnn_forward():
    model = SmallCNN(num_classes=10)
    params = model.init(jax.random.key(0), jnp.zeros((1, 28, 28, 1)))["params"]
    logits = jax.jit(lambda p, x: model.apply({"params": p}, x))(
        params, jnp.zeros((8, 28, 28, 1))
    )
    assert logits.shape == (8, 10)


def test_smallcnn_softmax_option_is_live():
    model = SmallCNN(num_classes=10, apply_softmax=True)
    params = model.init(jax.random.key(0), jnp.zeros((1, 28, 28, 1)))["params"]
    probs = model.apply({"params": params}, jnp.ones((3, 28, 28, 1)) * 0.3)
    assert jnp.allclose(jnp.sum(probs, axis=-1), 1.0, atol=1e-5)


def test_create_model_rejects_unknown_name():
    with pytest.raises(ValueError, match="unknown model"):
        create_model("nope")


def test_resnet20_forward_and_size():
    model = ResNet20(num_classes=10)
    params = model.init(jax.random.key(0), jnp.zeros((1, 32, 32, 3)))["params"]
    n = count_params(params)
    assert 0.25e6 < n < 0.31e6, n   # canonical resnet-20 is ~0.27M
    logits = jax.jit(lambda p, x: model.apply({"params": p}, x))(
        params, jnp.zeros((2, 32, 32, 3))
    )
    assert logits.shape == (2, 10)


def test_models_are_deterministic_pure_functions():
    model, params = create_model("smallcnn", num_classes=10, input_shape=(28, 28, 1))
    x = jax.random.normal(jax.random.key(1), (2, 28, 28, 1))
    a = model.apply({"params": params}, x)
    b = model.apply({"params": params}, x)
    assert jnp.array_equal(a, b)


# --- the polyphase form of a conv + ReLU + 2x2 max-pool stage (PR 25) ---


def _conv_f32(x, kernel, bias):
    y = lax.conv_general_dilated(
        x, kernel, (1, 1), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision="highest",
    )
    return y if bias is None else y + bias


def _plain_stage(x, kernel, bias):
    """What the stage was before PR 25: nn.Conv + relu + nn.max_pool."""
    y = nn.Conv(kernel.shape[-1], (3, 3), padding="VALID", precision="highest").apply(
        {"params": {"kernel": kernel, "bias": bias}}, x
    )
    return nn.max_pool(nn.relu(y), (2, 2), strides=(2, 2))


def _polyphase(x, kernel, bias, block):
    """One stage in block x block polyphase form, plain map in and out."""
    hw = x.shape[1:3]
    hp, wp = (hw[0] - 2) // 2, (hw[1] - 2) // 2
    nb, mb = -(-hp // (block // 2)), -(-wp // (block // 2))
    xs = cnn._to_depth(x, 1, hw, block, nb + 1, mb + 1)
    out = cnn._polyphase_stage(_conv_f32, xs, kernel, bias, block)
    return cnn._to_depth(out, block // 2, (hp, wp), 1, hp, wp)


def _rel_err(got, want):
    return float(jnp.max(jnp.abs(got - want)) / (jnp.max(jnp.abs(want)) + 1e-12))


@pytest.mark.parametrize("block", [2, 4])
@pytest.mark.parametrize("ci", [1, 3, 32])
@pytest.mark.parametrize("width", [254, 125, 60, 26, 11])
def test_polyphase_stage_matches_conv_relu_maxpool(width, ci, block):
    # `width` is the conv's output map: even maps pool whole, odd ones
    # (125 -> 62, 11 -> 5) drop their last row and column, which the
    # polyphase form never computes. The height is the other parity; block
    # 4 pads whatever is not a whole block with zeros and crops it again.
    co = 8
    ks = jax.random.split(jax.random.key(1000 * width + ci), 4)
    x = jax.random.normal(ks[0], (2, 9 + width % 2, width + 2, ci))
    kernel = jax.random.normal(ks[1], (3, 3, ci, co)) / np.sqrt(9 * ci)
    bias = 0.1 * jax.random.normal(ks[2], (co,))
    want = _plain_stage(x, kernel, bias)
    got = _polyphase(x, kernel, bias, block)
    assert got.shape == want.shape == (2, (7 + width % 2) // 2, width // 2, co)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    ct = jax.random.normal(ks[3], want.shape)
    g_want = jax.grad(lambda *a: jnp.sum(_plain_stage(*a) * ct), argnums=(0, 1, 2))(
        x, kernel, bias
    )
    g_got = jax.grad(
        lambda *a: jnp.sum(_polyphase(*a, block) * ct), argnums=(0, 1, 2)
    )(x, kernel, bias)
    for name, a, b in zip(("input", "kernel", "bias"), g_got, g_want):
        assert _rel_err(a, b) < 1e-4, (name, _rel_err(a, b))


@pytest.mark.parametrize("block", [2, 4])
@pytest.mark.parametrize("level", [2.0, -1.0])
def test_polyphase_pool_gradient_goes_to_the_first_maximum(level, block):
    # Planted ties: whole-number inputs and weights make every sum exact in
    # float32, and a constant image makes all four values of every window
    # equal. reduce_window's backward gives the window's gradient to its
    # first maximum in window order; an even split between ties (jnp.max's
    # rule) would differ. At level -1 every window but a few is ReLU's zero,
    # which passes no gradient on.
    x = jnp.full((1, 10, 12, 2), level).at[0, 4:6, 5:8, 0].set(3.0)
    kernel = jnp.ones((3, 3, 2, 4)).at[1, 1, 0, 2].set(2.0)
    bias = jnp.zeros((4,))
    ct = jnp.arange(1.0, 1.0 + 4 * 5 * 4).reshape(1, 4, 5, 4)
    want = jax.grad(lambda x: jnp.sum(_plain_stage(x, kernel, bias) * ct))(x)
    got = jax.grad(lambda x: jnp.sum(_polyphase(x, kernel, bias, block) * ct))(x)
    assert float(jnp.max(jnp.abs(want))) > 0
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _relu_pool4_before_pr32_fwd(y):
    """`_relu_pool4_fwd` as PR 25 wrote it: the maximum, then the lowest
    phase equal to it, four compares and selects. The oracle."""
    g = y.shape[-1] // 4
    phases = [y[..., p * g : (p + 1) * g] for p in range(4)]
    top = jnp.maximum(
        jnp.maximum(phases[0], phases[1]), jnp.maximum(phases[2], phases[3])
    )
    first = jnp.full(top.shape, 4, jnp.int8)
    for p in (3, 2, 1, 0):
        first = jnp.where(phases[p] == top, jnp.int8(p), first)
    return nn.relu(top), jnp.where(top > 0, first, jnp.int8(4))


def _relu_pool4_before_pr32_bwd(first, g):
    return (jnp.concatenate([jnp.where(first == p, g, 0) for p in range(4)], axis=-1),)


def _pool_windows(case, n, key):
    """[2, 5, 7, 4, n] bf16: the four phases of 70 windows a lane."""
    shape = (2, 5, 7, 4, n)
    k_val, k_where, k_sign = jax.random.split(key, 3)
    # halves in [-4, 4): exact in bf16, so equal values are common anyway
    y = jnp.round(jax.random.uniform(k_val, shape, minval=-4.0, maxval=4.0) * 2) / 2
    order = jnp.argsort(jax.random.uniform(k_where, shape), axis=-2)  # a permutation of the phases a window
    if case.startswith("ties"):
        # the window's maximum planted at 2, 3 or 4 phases chosen at random
        planted = order < int(case[-1])
        y = jnp.where(planted, jnp.max(y, axis=-2, keepdims=True), y)
    elif case == "negative":
        y = -jnp.abs(y) - 0.5
    elif case == "zeros":
        # maxima that are exactly zero, of either sign, alone and tied
        zero = jnp.where(jax.random.bernoulli(k_sign, 0.5, shape), 0.0, -0.0)
        y = jnp.where(order < 2, zero, -jnp.abs(y))
    else:
        assert case == "random"
    return y.astype(jnp.bfloat16).reshape(2, 5, 7, 4 * n)


def _bits(a):
    """An array's bits as integers, so that -0.0 and 0.0 differ."""
    return np.asarray(a).view({1: np.int8, 2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


@pytest.mark.parametrize("lanes", [32, 128])
@pytest.mark.parametrize(
    "case", ["ties2", "ties3", "ties4", "negative", "zeros", "random"]
)
def test_relu_pool4_is_bit_for_bit_the_formulation_it_replaced(case, lanes):
    # PR 32 rewrote the pool for what XLA makes of it (one comparison tree
    # forward, one select backward); the numbers must not move: the pooled
    # map, the saved winning phase and the cotangent, every bit of them.
    y = _pool_windows(case, lanes, jax.random.key(lanes + len(case)))
    ct = jax.random.normal(jax.random.key(3), (2, 5, 7, lanes)).astype(jnp.bfloat16)
    # PR 35 made the bias the pool's second argument; -0.0 is the bias that
    # leaves every bit of `y` as it is, zeros of either sign included
    b = jnp.full((lanes,), -0.0, jnp.bfloat16)

    want_out, want_first = _relu_pool4_before_pr32_fwd(y)
    got_out, (got_first, kept_out, _) = cnn._relu_pool4_fwd(y, b)
    assert kept_out is got_out  # the second residual is the output itself
    assert got_out.dtype == want_out.dtype and got_first.dtype == jnp.int8
    np.testing.assert_array_equal(_bits(got_first), _bits(want_first))
    np.testing.assert_array_equal(_bits(got_out), _bits(want_out))
    np.testing.assert_array_equal(_bits(cnn._relu_pool4(y, b)), _bits(want_out))
    if case == "negative":
        assert np.all(np.asarray(got_first) == 4)
    elif case.startswith("ties"):
        # the first of k tied phases is one of the 5 - k lowest
        winners = set(np.unique(np.asarray(got_first)).tolist()) - {4}
        assert winners == set(range(5 - int(case[-1])))

    (want_dy,) = _relu_pool4_before_pr32_bwd(want_first, ct)
    vjp = lambda y, ct: jax.vjp(cnn._relu_pool4, y, b)[1](ct)[0]  # noqa: E731
    # eagerly and under jit: what XLA fuses may not change a bit either
    for got_dy in (vjp(y, ct), jax.jit(vjp)(y, ct)):
        assert got_dy.dtype == want_dy.dtype and got_dy.shape == y.shape
        np.testing.assert_array_equal(_bits(got_dy), _bits(want_dy))


@pytest.mark.parametrize("clients,positions", [(0, 1), (3, 1), (0, 4), (3, 4)])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("case", ["random", "ties4", "negative", "sign"])
def test_relu_pool4_with_the_bias_inside_is_the_pool_of_y_plus_bias(case, dtype, clients, positions):
    # PR 35: the bias is added inside the pool, which returns its cotangent
    # from the POOLED cotangent (n lanes). Against the pool of `y + bias`
    # (the PR 25 formulation above, which PR 32's form equals bit for bit):
    # output, winning phase and `dy` every bit, and `db` the old bias
    # cotangent (a sum over the 4n-lane `dy`) folded onto the channels.
    # `clients` > 0 is the `fused` lowering's layout: a [C*B, ...] batch
    # and a [C, c] bias, added client by client. `positions` is a block's
    # pooled positions (4 at block 4): the n lanes of a phase are (position,
    # channel) and the bias has the channels alone.
    n, dtype = 32, jnp.dtype(dtype)
    ky, kb, kc = jax.random.split(jax.random.key(len(case) + clients), 3)
    lead = (clients,) if clients else ()
    batch = 2 * max(clients, 1)
    y = _pool_windows(case if case != "sign" else "negative", n, ky)
    y = jnp.concatenate([y] * max(clients, 1)).astype(dtype)  # [batch, 5, 7, 4n]
    # halves, so every sum below is exact in float32
    b = jnp.round(jax.random.uniform(kb, (*lead, n // positions), minval=-2.0, maxval=2.0) * 2) / 2
    if case == "sign":  # all of `y` is negative; about half the windows turn positive
        b = b + 2.0
    elif case == "negative":
        b = -jnp.abs(b)
    b = b.astype(dtype)
    ct = (jnp.round(jax.random.normal(kc, (batch, 5, 7, n)) * 4) / 4).astype(dtype)

    per_client = lambda a: a.reshape(*lead, -1, *a.shape[1:])  # noqa: E731
    b4 = jnp.expand_dims(jnp.tile(b, 4 * positions), (-4, -3, -2))
    yb = (per_client(y) + b4).reshape(y.shape)
    want_out, want_first = _relu_pool4_before_pr32_fwd(yb)
    (want_dy,) = _relu_pool4_before_pr32_bwd(want_first, ct)
    fired = float(np.mean(np.asarray(want_first) < 4))
    if case == "negative":
        assert fired == 0
    elif case == "sign":
        assert 0.2 < fired < 0.8  # the bias decides the sign, both ways
        assert np.all(np.asarray(_relu_pool4_before_pr32_fwd(y)[1]) == 4)

    got_out, (got_first, _, _) = cnn._relu_pool4_fwd(y, b)
    np.testing.assert_array_equal(_bits(got_out), _bits(want_out))
    np.testing.assert_array_equal(_bits(got_first), _bits(want_first))
    # the old bias cotangent: `y + tile(bias)`'s transpose, a sum over `dy`
    old_db = np.asarray(per_client(want_dy).astype(jnp.float32)).astype(np.float64)
    old_db = old_db.sum(axis=(-4, -3, -2)).reshape(*lead, 4 * positions, -1).sum(axis=-2)
    vjp = lambda y, b, ct: jax.vjp(cnn._relu_pool4, y, b)[1](ct)  # noqa: E731
    for got_dy, got_db in (vjp(y, b, ct), jax.jit(vjp)(y, b, ct)):
        assert got_dy.dtype == dtype and got_dy.shape == y.shape
        np.testing.assert_array_equal(_bits(got_dy), _bits(want_dy))
        assert got_db.dtype == dtype and got_db.shape == b.shape
        got_db = np.asarray(got_db.astype(jnp.float32)).astype(np.float64)
        if dtype == jnp.float32:
            np.testing.assert_array_equal(got_db, old_db)
        else:  # one bfloat16 rounding (8 bits of significand) of the exact sum
            assert np.all(np.abs(got_db - old_db) <= 2.0**-8 * np.abs(old_db))


@pytest.mark.parametrize(
    "hw,widths,taken",
    [
        ((256, 200), (3, 32, 32, 32), 2),  # MedCNN's head: block 4, block 2, plain
        ((255, 203), (3, 32, 32), 2),      # odd maps, the last stage untrimmed
        ((128, 130), (1, 32, 32), 1),      # block 4 handed on to a plain stage
        ((202, 110), (3, 8), 1),           # a single stage, block 4, back to plain
        ((110, 111), (32, 16, 4), 1),      # 32 channels in: block 2
        ((28, 28), (1, 32, 64), 0),        # SmallCNN: maps too small, untouched
    ],
)
def test_conv_stages_match_the_plain_stack(hw, widths, taken):
    # The rule reads the form from each stage's shapes; whatever it chooses,
    # the stack computes the plain stack's function and gradients.
    ks = jax.random.split(jax.random.key(hw[0]), 2 * len(widths))
    x = jax.random.normal(ks[0], (1, *hw, widths[0]))
    layers = [
        (jax.random.normal(ks[2 * i + 1], (3, 3, ci, co)) / np.sqrt(9 * ci),
         0.1 * jax.random.normal(ks[2 * i + 2], (co,)))
        for i, (ci, co) in enumerate(zip(widths[:-1], widths[1:]))
    ]

    def plain(x, layers):
        for kernel, bias in layers:
            x = _plain_stage(x, kernel, bias)
        return x

    want = plain(x, layers)
    got = cnn._conv_stages(_conv_f32, x, layers)
    assert obs_metrics.snapshot()["model.polyphase_stages"] == taken
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    ct = jax.random.normal(ks[-1], want.shape)
    g_want = jax.grad(lambda *a: jnp.sum(plain(*a) * ct), argnums=(0, 1))(x, layers)
    g_got = jax.grad(
        lambda *a: jnp.sum(cnn._conv_stages(_conv_f32, *a) * ct), argnums=(0, 1)
    )(x, layers)
    for a, b in zip(jax.tree_util.tree_leaves(g_got), jax.tree_util.tree_leaves(g_want)):
        assert _rel_err(a, b) < 1e-4


def _tree_digest(params):
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize(
    "name,shape,leaves,count,digest",
    [
        ("medcnn", (256, 256, 3), 18, 222_722,
         "d79b997b1c1dd763383eea71307dae2d26cf388f0249474b4a7ff7b53967038f"),
        ("smallcnn", (28, 28, 1), 8, None,
         "8bf11747d31b04a95850006076ae107e40ee57817694f8a2b4284c6787134e5e"),
    ],
)
def test_parameter_tree_is_the_parents(name, shape, leaves, count, digest):
    # The tree is the HE packing contract and the checkpoint format: names,
    # shapes and dtypes as flax.linen.Conv / Dense declare them, and `init`
    # bit-equal to the tree of the commit before PR 25 (digests read there,
    # key 7).
    module, params = create_model(name, input_shape=shape, rng=jax.random.key(7))
    convs = len(module.features)
    assert sorted(params) == sorted(
        [f"Conv_{i}" for i in range(convs)]
        + [f"Dense_{j}" for j in range(len(module.dense) + 1)]
    )
    widths = (shape[-1], *module.features)
    for i in range(convs):
        assert set(params[f"Conv_{i}"]) == {"kernel", "bias"}
        assert params[f"Conv_{i}"]["kernel"].shape == (3, 3, widths[i], widths[i + 1])
        assert params[f"Conv_{i}"]["bias"].shape == (widths[i + 1],)
    flat = jax.tree_util.tree_leaves(params)
    assert len(flat) == leaves and all(t.dtype == jnp.float32 for t in flat)
    assert count is None or count_params(params) == count
    assert _tree_digest(params) == digest
