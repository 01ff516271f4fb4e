"""The Pallas kernels compile for the chip — the one file that describes it.

Every kernel entry point of `ckks/pallas_ntt.py` at its production shapes
(`chip_smoke.kernel_cases`: the seven at the training ring, key-switch and
hoisted rotations again at the serving ring) is lowered by Mosaic
(`interpret=False`) and compiled for a DESCRIBED TPU v5e, no chip
attached. A compile that passes is not a chip run — `chip_smoke.py` runs
the same cases on the chip, bitwise against their XLA twins — but a kernel
the chip's compiler refuses fails here, at no chip time.

The topology is described inside a module-scoped fixture and nowhere
else: describing it loads the TPU library, which only one process may
hold, so nothing here may run while any module is imported.
"""

import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_smoke  # noqa: E402

CASE_NAMES = [
    f"{name}@n4096x3"
    for name in (
        "ntt_forward", "ntt_inverse", "encrypt_fused", "decrypt_fused",
        "keyswitch_fused", "keyswitch_fused_eval_input",
        "hoisted_rotations", "transcipher_fused",
    )
] + ["keyswitch_fused_eval_input@n8192x5", "hoisted_rotations@n8192x5"]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def cases():
    return {case.name: case for case in chip_smoke.kernel_cases()}


def test_case_list_matches_chip_smoke(cases):
    # The parametrize ids below are literals (nothing may be computed from
    # the program at import time); keep them equal to what the chip runs.
    assert sorted(cases) == sorted(CASE_NAMES)


@pytest.mark.parametrize("name", CASE_NAMES)
def test_kernel_compiles_for_described_v5e(name, cases, one_chip):
    case = cases[name]
    args = [
        jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=one_chip)
        for shape, _ in case.shapes
    ]
    # The persistent cache cannot read a described-chip entry back
    # without a chip; keep these compiles out of it.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        lowered = jax.jit(lambda *a: case.kernel(False, *a)).lower(*args)
        assert "tpu_custom_call" in lowered.as_text()
        compiled = lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("transpose,k,n", [
    (False, 2048, 1536),   # gate and up of a held expert, forward
    (False, 768, 2048),    # down, forward
    (True, 1536, 2048),    # gate and up read transposed: the rows' gradient
    (True, 2048, 768),     # down read transposed
])
def test_grouped_expert_product_compiles_for_described_v5e(
        transpose, k, n, one_chip, monkeypatch):
    """`models/lm.grouped_matmul`'s Pallas call at JoyAI-LLM-Flash's widths
    and the benchmark's batch (2 x 4,096 tokens x 8 selections, 128 held
    experts), frozen matrix read as it lies in both directions."""
    from hefl_tpu.models import lm

    monkeypatch.setattr(lm.common, "_interpret", lambda: False)
    rows, held = 2 * 4096 * 8, 128
    w_shape = (held, n, k) if transpose else (held, k, n)
    args = [
        jax.ShapeDtypeStruct((rows, k), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct(w_shape, jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((held,), jnp.int32, sharding=one_chip),
    ]
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(
            lambda x, w, s: lm.experts._gmm_call(x, w, s, transpose)
        ).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


@pytest.mark.parametrize("gradient", [False, True], ids=["forward", "gradient"])
def test_one_block_expert_layer_moves_its_rows_by_gathers_on_a_described_v5e(
        gradient, one_chip, monkeypatch):
    """`joyai-flash.sync_s4k`'s expert layer alone (`models/lm.held_experts`
    where a chip holds half of the experts: 8,192 tokens x 8 selections, 128
    of 256 experts of 2048 x 768 held), forward and with its gradient by x
    and the pairs' weights, compiled for a described chip keeps the form PR
    40 gave it (PERF.md section 6): rows move between token order and expert
    order as gathers. No scatter takes 65,536 rows of updates (autodiff's
    un-sort and gather transposes did: into `f32[65536,2048]` and
    `bf16[8192,2048]`), no `select` passes over an `f32[65536, ...]` array
    (`_masked_rows` did, twice a pass), and the 65,536 keys are sorted twice
    and nothing else is (four times with autodiff's gradient). The forward
    reads and writes fewer bytes than the parent's branch by the compiler's
    count (13.81 GB against 15.67). With the gradient the count is no
    yardstick: it reads 32.68 GB here against the parent's 25.94, which the
    HBM could not move in the 21.4 ms the chip takes (36.9 for the parent's:
    PERF.md section 6, PR 40)."""
    from hefl_tpu.models import lm

    monkeypatch.setattr(lm.common, "_interpret", lambda: False)
    arch = lm.PRESETS["joyai_llm_flash"]
    t, k, d, f, held = (8192, arch.experts_per_tok, arch.hidden,
                        arch.moe_intermediate, arch.held_experts)
    pairs = t * k
    assert lm.experts.pair_blocks(arch, pairs) == (1, 65536)
    on_chip = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    w = {"gate_up": on_chip((held, d, 2 * f), jnp.bfloat16),
         "down": on_chip((held, f, d), jnp.bfloat16)}
    x, idx, weights = (on_chip((t, d), jnp.float32), on_chip((t, k), jnp.int32),
                       on_chip((t, k), jnp.float32))
    layer = lambda x, p, w, i: lm.held_experts(arch, w, x, i, p)[0]  # noqa: E731
    if gradient:
        layer = jax.grad(lambda x, p, w, i: jnp.sum(lm.held_experts(
            arch, w, x, i, p)[0] ** 2), argnums=(0, 1))
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(layer).lower(x, weights, w, idx).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= (4 if gradient else 2)
    shape_of = dict(re.findall(r"%([\w.\-]+) = (\w+\[[\d,]*\])", text))
    updates = [shape_of[m.group(1)] for m in re.finditer(
        r" scatter\(%[\w.\-]+, %[\w.\-]+, %([\w.\-]+)\)", text)]
    # (what is left: the grouped product's own group bookkeeping, and the
    # experts' loads, a `bincount` of 65,536 scalars into s32[129])
    assert updates and not [u for u in updates if "," in u], updates
    assert not re.findall(rf"= f32\[{pairs},\d+\]\S* select\(", text)
    sorts = re.findall(r"= \((\w+\[[\d,]*\])\{.*\) sort\(", text)
    assert sorts == [f"s32[{pairs}]"] * 2, sorts
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert gradient or cost["bytes accessed"] < 15.67e9, cost


def test_fused_attention_gradient_compiles_for_described_v5e(
        one_chip, monkeypatch):
    """`jax.grad` of `models/lm.causal_attention` at JoyAI-LLM-Flash's widths
    (32 heads of 192 / 128) and the benchmark's batch (2 x 4,096 positions),
    the preset's block: the forward kernel and the gradient's (`dkv`, which
    forms `dq` too) lower through Mosaic, and no score block reaches HBM (the
    blocked XLA form read 38.5 GB here; q, k, v, their gradients and the
    layout changes are under 4)."""
    from hefl_tpu.models import lm

    monkeypatch.setattr(lm.common, "_interpret", lambda: False)
    arch = lm.PRESETS["joyai_llm_flash"]
    dq = arch.qk_nope_head_dim + arch.qk_rope_head_dim
    args = [jax.ShapeDtypeStruct((2, 4096, arch.heads, d), jnp.bfloat16,
                                 sharding=one_chip)
            for d in (dq, dq, arch.v_head_dim)]
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(lm.causal_attention(q, k, v, arch.q_block)),
            (0, 1, 2))).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
    text = compiled.as_text()
    assert "splash_mha_fwd" in text and "splash_mha_dkv" in text
    assert text.count("tpu_custom_call") >= 2
    assert compiled.cost_analysis()["bytes accessed"] < 4e9


def test_linear_recurrence_and_sort_free_experts_compile_for_described_v5e(
        one_chip, monkeypatch):
    """`jax.grad` of `models/lm.kda_recurrence` at Ling-3.0-flash's widths
    (32 heads of 128 x 128 over 8,192 positions, chunks of 64 solved in
    sub-blocks of 16): one scan over the chunks in each direction, no kernel
    of this repo's in it, and what is made ahead of the scan stays under
    1.5 GB of temporaries (a chunk group at a time). And the held experts of
    the same preset (`_held_counted`, five layers' experts along one axis,
    the layer at 2): grouped products through Mosaic and **no sort** in
    either direction (a sort of 65,536 keys takes the chip's compiler 15 s
    wherever one stands)."""
    from hefl_tpu.models import lm

    monkeypatch.setattr(lm.common, "_interpret", lambda: False)
    arch = lm.PRESETS["ling_3_flash"]
    shape = lambda *s, dtype=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        s, dtype, sharding=one_chip)
    q = shape(1, 8192, arch.heads, arch.kda_head_dim)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        scan = jax.jit(jax.grad(
            lambda q, k, v, g, b: jnp.sum(lm.kda_recurrence(q, k, v, g, b) ** 2),
            (0, 1, 2, 3, 4))).lower(q, q, q, q, shape(1, 8192, arch.heads)).compile()
        t, k, d, f = 8192, arch.experts_per_tok, arch.hidden, arch.moe_intermediate
        n = arch.expert_layers * arch.held_experts
        w = {"gate_up": shape(n, d, 2 * f, dtype=jnp.bfloat16),
             "down": shape(n, f, d, dtype=jnp.bfloat16)}
        experts = jax.jit(jax.grad(
            lambda w, x, key, pw: jnp.sum(
                lm.experts._held_counted(arch, w, x, key, pw, at=2)[0] ** 2),
            (1, 3))).lower(w, shape(t, d), shape(t * k, dtype=jnp.int32),
                           shape(t, k)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
    text = scan.as_text()
    assert "while(" in text and "tpu_custom_call" not in text
    assert scan.memory_analysis().temp_size_in_bytes < 1.5e9
    text = experts.as_text()
    assert "gmm" in text and " sort(" not in text and "sort." not in text
    assert experts.memory_analysis().temp_size_in_bytes < 2.5e9


def test_linear_front_kernels_compile_for_described_v5e(one_chip, monkeypatch):
    """A linear layer's front at Ling-3.0-flash's widths (`lm.kda._kda_front`
    over `made` f32[1, 8192, 20480]: 32 heads of 128, chunks of 64, taps of
    4): the forward kernel and the gradient's lower through Mosaic
    (sublane rolls of 136- and 144-row tiles, a halo block of 8 rows on
    either side), and a whole layer's gradient through its checkpoint has
    the forward kernel twice and the gradient's once, with no array of
    `made`'s q, k, v or decay columns' size written between the
    projections' product and the kernels, nor between the kernels and the
    recurrence's loops: the kernels' operands are the product's fusion and
    bitcasts of the loops' results."""
    from hefl_tpu.models import lm

    monkeypatch.setattr(lm.common, "_interpret", lambda: False)
    arch = lm.PRESETS["ling_3_flash"]
    assert lm.kda.kda_front_kernel(arch)
    h, d, width, s = arch.heads, arch.kda_head_dim, arch.hidden, 8192
    shape = lambda *s, dtype=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        s, dtype, sharding=one_chip)
    w = {"in": shape(width, 5 * h * d, dtype=jnp.bfloat16),
         "beta": shape(width, h, dtype=jnp.bfloat16),
         "conv": shape(3 * h * d, arch.kda_conv, dtype=jnp.bfloat16),
         "o": shape(h * d, width, dtype=jnp.bfloat16)}
    g = {"A_log": shape(h), "dt_bias": shape(h * d), "o_norm": shape(d)}
    layer = jax.checkpoint(lambda g, x, w: lm.kda_layer(arch, w, g, x),
                           policy=lm.model._kept(arch))
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        front = jax.jit(lambda *a: lm.kda._kda_front(arch, *a)).lower(
            shape(1, s, 5 * h * d), w["conv"], g["A_log"], g["dt_bias"]).compile()
        grad = jax.jit(jax.grad(lambda g, x, w: jnp.sum(layer(g, x, w) ** 2),
                                (0, 1))).lower(g, shape(1, s, width), w).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
    assert len(re.findall(r"^ *%?kda_front_fwd[.0-9]* = ", front.as_text(), re.M)) == 1
    text = grad.as_text()
    sites = lambda kernel: re.findall(  # noqa: E731
        rf"^ *%?{kernel}[.0-9]* = .*? custom-call\(([^)]*)\)", text, re.M)
    assert len(sites("kda_front_fwd")) == 2 and len(sites("kda_front_bwd")) == 1
    for operands in sites("kda_front_fwd") + sites("kda_front_bwd"):
        made = operands.split(",")[0].strip()
        assert re.match(r"%?convolution_bitcast_fusion", made), made
    by_chunk = [re.sub(r"/\*.*?\*/", "", name).strip()
                for name in sites("kda_front_bwd")[0].split(",")[12:19]]
    assert all(re.match(r"%?bitcast", name) for name in by_chunk), by_chunk
    assert grad.memory_analysis().temp_size_in_bytes < 4.0e9


@pytest.mark.parametrize("kind", [0, 1], ids=["global", "window"])
def test_grouped_attention_gradient_compiles_for_described_v5e(
        kind, one_chip, monkeypatch):
    """`jax.grad` of `models/lm.grouped_heads` at MiMo-V2-Flash's widths (64
    query heads of 192 / 128 over 4 KV heads, causal, or over 8 with a window
    of 128 and a sink a head) and the benchmark's 8,192 positions: splash
    attention's multi-query kernels lower through Mosaic (forward, and the
    gradient in one kernel for the global kind, in `dq` and `dkv` for the
    window), K and V stay as many heads wide as they come, and nothing of
    [heads, S, S] reaches HBM (64 x 8192^2 float32 would be 17 GB): the
    temporaries are q, k, v, their gradients and the layout changes, and one
    KV head's float32 dq a key block in the global kind."""
    from hefl_tpu.models import lm

    monkeypatch.setattr(lm.common, "_interpret", lambda: False)
    arch = lm.PRESETS["mimo_v2_flash"]
    dq, s = arch.qk_nope_head_dim + arch.qk_rope_head_dim, 8192
    shape = lambda *dims, dt=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        dims, dt, sharding=one_chip)
    kv = arch.kv_heads[kind]
    args = [shape(1, s, arch.heads, dq), shape(1, s, kv, dq),
            shape(1, s, kv, arch.v_head_dim), shape(arch.heads, dt=jnp.float32)]
    window = arch.window if kind else 0

    def loss(q, k, v, sinks):
        return jnp.sum(lm.grouped_heads(q, k, v, sinks if kind else None,
                                        window, arch.q_block, dq ** -0.5))

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(jax.grad(loss, (0, 1, 2, 3) if kind else (0, 1, 2))
                           ).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
    text = compiled.as_text()
    assert "splash_mqa_fwd" in text and "splash_mqa_dkv" in text
    assert ("splash_mqa_dq" in text) == bool(kind)
    grads = jax.tree_util.tree_leaves(compiled.out_info)
    assert [g.shape for g in grads][:3] == [a.shape for a in args[:3]]
    assert compiled.memory_analysis().temp_size_in_bytes < 3.0e9
    assert compiled.cost_analysis()["bytes accessed"] < 12e9


def test_selection_compiles_for_described_v5e_without_a_heads_by_keys_array(
        one_chip):
    """`models/lm.select_keys` at DeepSeek-V3.2-Exp's indexer (64 heads of
    128, the 2,048 largest) and the benchmark's 8,192 positions: nothing of
    [heads, S, S] in HBM (64 x 8192^2 float32 would be 17 GB) and no float32
    [S, S] either: the program's temporaries are a slice of 256 queries'."""
    from hefl_tpu.models import lm

    arch = lm.PRESETS["deepseek_v32"]
    s = 8192
    shape = lambda *dims, dt=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        dims, dt, sharding=one_chip)
    w = {"q": shape(arch.q_lora_rank, arch.index_heads * arch.index_head_dim),
         "k": shape(arch.hidden, arch.index_head_dim),
         "k_gain": shape(arch.index_head_dim, dt=jnp.float32),
         "k_bias": shape(arch.index_head_dim, dt=jnp.float32),
         "w": shape(arch.hidden, arch.index_heads)}
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(lambda w, x, c_q: lm.select_keys(arch, w, x, c_q)).lower(
            w, shape(1, s, arch.hidden, dt=jnp.float32),
            shape(1, s, arch.q_lora_rank, dt=jnp.float32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
    m = compiled.memory_analysis()
    assert m.output_size_in_bytes == s * s          # one byte a (query, key)
    assert m.temp_size_in_bytes < 1.2e9


def test_sparse_round_program_fits_a_described_v5e(one_chip, monkeypatch):
    """The encrypted round of the benchmark's `deepseek-v32.sync_s8k` (two
    clients, one step of one sequence of 8,192 positions, validation, 2 x
    2,271 ciphertext rows) at the published widths, compiled whole for a
    described chip: the selection's attention and the grouped product lower
    through Mosaic. Since PR 42 the six attention layers keep their packed
    selection and their kernel's output and log-sum-exp for the gradient
    (`lm.model._kept`), so the forward kernel with residuals has six call sites
    (the step's forward; twelve with nothing kept), validation's without
    residuals six and the gradient's six, and arguments (the 7.84 GB base),
    outputs and temporaries read 16.67 GB by `memory_analysis()` (13.94
    with nothing kept), under the 15.75 GiB = 16.91e9 bytes the compiler
    gives a program on this chip. That count holds a kept array twice
    (PERF.md, PR 42): the compiler's own total, which it holds against the
    limit, is 14.91 GB (13.53 with nothing kept)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    import hefl_tpu.fl.fedavg as fedavg
    import hefl_tpu.fl.secure as secure
    from hefl_tpu.ckks.keys import keygen
    from hefl_tpu.experiment import HEConfig
    from hefl_tpu.fl import TrainConfig
    from hefl_tpu.models import lm

    monkeypatch.setattr(lm.common, "_interpret", lambda: False)
    # the program's base is its last argument: no 7.84 GB made here
    monkeypatch.setattr(fedavg, "with_frozen_base", lambda module, fn: fn)
    mesh = Mesh(np.array([one_chip._device]), ("clients",))
    whole, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("clients"))
    module = lm.FrozenBaseLM(num_classes=16160, arch=lm.PRESETS["deepseek_v32"])
    shapes = lambda tree, sh: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), tree)
    cfg = TrainConfig(epochs=1, batch_size=1, num_classes=16160,
                      val_fraction=0.5, lr=1e-3, lr_decay=0.0, augment=False)
    ctx = HEConfig().build()
    _, pk = keygen(ctx, jax.random.key(0))
    keys = jax.eval_shape(lambda: jax.random.split(jax.random.key(0), 2))
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        fn = secure._build_secure_round_fn.__wrapped__(
            module, cfg, mesh, ctx, False, None, 2)
        compiled = fn.lower(
            shapes(jax.eval_shape(module.init_trained), whole), shapes(pk, whole),
            jax.ShapeDtypeStruct((2, 2, 8194), jnp.int32, sharding=split),
            jax.ShapeDtypeStruct((2, 2), jnp.int32, sharding=split),
            shapes(keys, split), shapes(keys, split),
            shapes(jax.eval_shape(module.init_base), whole)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
    text = compiled.as_text()
    sites = lambda kernel: len(re.findall(  # noqa: E731
        rf"^ *%{kernel}[.0-9]* = ", text, re.M))
    assert sites("splash_mha_fwd_residuals") == 6
    assert sites("splash_mha_fwd_no_residuals") == 6
    assert sites("splash_mha_dkv_no_residuals") == 6
    assert "gmm" in text
    m = compiled.memory_analysis()
    assert 7.8e9 < m.argument_size_in_bytes < 7.95e9
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes) < 16.8e9


def test_window_round_program_fits_a_described_v5e(one_chip, monkeypatch):
    """The encrypted round of the benchmark's `mimo-v2-flash.sync_s8k` (two
    clients, one step of one sequence of 8,192 positions, validation, 2 x
    1,552 ciphertext rows) at the published widths, compiled whole for a
    described chip: the grouped attention's multi-query kernels (forward,
    the global kind's fused gradient, the window kind's `dq` and `dkv`) and
    the grouped expert product lower through Mosaic, and arguments (the
    6.85 GB base), outputs and temporaries stay under ISSUE 34's 14.0 GB by
    the compiler's count (13.51 with the expert layer's front of 24,576
    rows, 13.02 with blocks alone, which the chip read to the byte, PERF.md
    PR 34; with attention's output kept for the gradient it was 14.77)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    import hefl_tpu.fl.fedavg as fedavg
    import hefl_tpu.fl.secure as secure
    from hefl_tpu.ckks.keys import keygen
    from hefl_tpu.experiment import HEConfig
    from hefl_tpu.fl import TrainConfig
    from hefl_tpu.models import lm

    monkeypatch.setattr(lm.common, "_interpret", lambda: False)
    # the program's base is its last argument: no 6.85 GB made here
    monkeypatch.setattr(fedavg, "with_frozen_base", lambda module, fn: fn)
    mesh = Mesh(np.array([one_chip._device]), ("clients",))
    whole, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("clients"))
    module = lm.FrozenBaseLM(num_classes=19072, arch=lm.PRESETS["mimo_v2_flash"])
    shapes = lambda tree, sh: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), tree)
    cfg = TrainConfig(epochs=1, batch_size=1, num_classes=19072,
                      val_fraction=0.5, lr=1e-3, lr_decay=0.0, augment=False)
    ctx = HEConfig().build()
    _, pk = keygen(ctx, jax.random.key(0))
    keys = jax.eval_shape(lambda: jax.random.split(jax.random.key(0), 2))
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        fn = secure._build_secure_round_fn.__wrapped__(
            module, cfg, mesh, ctx, False, None, 2)
        compiled = fn.lower(
            shapes(jax.eval_shape(module.init_trained), whole), shapes(pk, whole),
            jax.ShapeDtypeStruct((2, 2, 8194), jnp.int32, sharding=split),
            jax.ShapeDtypeStruct((2, 2), jnp.int32, sharding=split),
            shapes(keys, split), shapes(keys, split),
            shapes(jax.eval_shape(module.init_base), whole)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
    text = compiled.as_text()
    for name in ("splash_mqa_fwd", "splash_mqa_dkv", "splash_mqa_dq", "gmm"):
        assert name in text, name
    m = compiled.memory_analysis()
    assert 6.85e9 < m.argument_size_in_bytes < 6.9e9
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes) < 13.7e9


def test_owner_decode_program_compiles_for_described_v5e(one_chip):
    """The owner's compiled decode + unpack (`fl.secure._decode_unpack`, PR
    30) at the benchmark's ring and about resnet20's size, 64 rows into 65 leaves:
    one program with no host callback and no custom call, its outputs the
    parameter tree's leaves."""
    import numpy as np

    from hefl_tpu.ckks.keys import CkksContext
    from hefl_tpu.ckks.packing import PackSpec
    from hefl_tpu.fl.secure import _decode_unpack

    ctx = CkksContext.create()
    tree = {f"w{i}": np.zeros((3, 3, 16, 28), np.float32) for i in range(64)}
    tree["head"] = np.zeros((64, 10), np.float32)
    spec = PackSpec.for_params(tree, ctx.n)
    assert spec.n_ct == 64 and len(tree) == 65
    res = jax.ShapeDtypeStruct((spec.n_ct, 3, ctx.n), jnp.uint32, sharding=one_chip)
    coeffs = jax.ShapeDtypeStruct((3,), jnp.float32, sharding=one_chip)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = _decode_unpack.lower(ctx.ntt, spec, res, coeffs).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
    text = compiled.as_text()
    assert "custom-call" not in text and "callback" not in text
    shapes = jax.tree_util.tree_leaves(compiled.out_info)
    assert len(shapes) == 65 and all(s.dtype == jnp.float32 for s in shapes)


def _hlo_computations(text):
    """{computation name: its instruction lines} of an optimized HLO module's
    text; the entry computation under "ENTRY"."""
    comps, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            cur = comps.setdefault("ENTRY" if head.group(1) else head.group(2), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None and " = " in line:
            cur.append(line.strip())
    return comps


def test_polyphase_pool_is_one_pass_each_way_on_a_described_v5e(one_chip):
    """`medcnn.sync_e10`'s step (4 clients x 32 images of 256x256x3 under
    `vmap`, forward and gradient) compiled for a described chip keeps the
    form PRs 32 and 35 gave `models/cnn._relu_pool4` (PERF.md section 5):
    the step reads and writes under 6.7 GB by the compiler's count (6.58;
    6.905 before PR 35, 9.92 before PR 32; the two forms of PR 35 that lost
    read 7.42 and 7.48) with under 0.9 GB of temporaries (0.852; 1.27 where
    the conv output is kept for the backward); each polyphase stage's conv
    output has ONE consumer, the forward fusion that makes the pooled map
    and the winning phase together; the backward's select on the winning
    phase is fused into the convolutions that take it, so that no fusion but
    the forward's writes a pooled-map-sized array from a select; and the
    stage's bias gradient is a second output of the input-gradient
    convolution that makes the pooled cotangent, not a reduction of its own
    over the 4n-lane select (PR 35: `bf16[4,512]` and `bf16[4,128]` loop
    fusions, 12% of the step on the chip). A JAX or XLA upgrade that undoes
    any of these costs the cell a tenth of its round."""
    import optax

    from hefl_tpu.models import MedCNN

    model = MedCNN()
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 256, 256, 3)))["params"])

    def loss(p, x, y):
        logits = model.apply({"params": p}, x)
        return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

    on_chip = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(jax.vmap(jax.value_and_grad(loss))).lower(
            jax.tree_util.tree_map(lambda a: on_chip((4, *a.shape), a.dtype), params),
            on_chip((4, 32, 256, 256, 3), jnp.bfloat16),
            on_chip((4, 32), jnp.int32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert cost["bytes accessed"] < 6.7e9, cost["bytes accessed"]
    assert compiled.memory_analysis().temp_size_in_bytes < 0.9e9

    comps = _hlo_computations(compiled.as_text())
    entry = comps["ENTRY"]
    name_of = lambda line: re.match(r"(?:ROOT )?(%[\w.\-]+) = ", line).group(1)  # noqa: E731
    for conv_out, pooled in (("bf16[4,32,63,63,512]", "bf16[4,32,63,63,128]"),
                             ("bf16[4,32,62,62,128]", "bf16[4,32,62,62,32]")):
        made = [name_of(l) for l in entry
                if l.split(" = ", 1)[1].startswith(conv_out + "{")]
        assert len(made) == 1, (conv_out, made)  # the conv output; no [., 4n] cotangent
        users = [l for l in entry
                 if re.search(re.escape(made[0]) + r"[,)]", l.split(" = ", 1)[1])]
        assert len(users) == 1 and " fusion(" in users[0], (conv_out, users)
        forward = name_of(users[0])
        assert "s8[" in users[0].split(" fusion(")[0]  # it makes `first` as well
        for line in entry:
            call = re.search(r" fusion\(.*calls=%([\w.\-]+)", line)
            if not call or pooled not in line.split(" fusion(")[0]:
                continue
            body = {name_of(l): l for l in comps[call.group(1)]}
            root = next(l for l in comps[call.group(1)] if l.startswith("ROOT"))
            outs = (re.findall(r"%[\w.\-]+", root.split(" tuple(")[1])
                    if " tuple(" in root else [name_of(root)])
            selects = [o for o in outs
                       if body[o].split(" = ", 1)[1].startswith(pooled + "{")
                       and re.search(r"\} select\(", body[o])]
            assert not selects or name_of(line) == forward, (pooled, line[:200])

    def fusions_making(shape):
        """(entry line, body) of each entry fusion with an output of `shape`."""
        for line in entry:
            call = re.search(r" fusion\(.*calls=%([\w.\-]+)", line)
            if call and shape + "{" in line.split(" fusion(")[0]:
                yield line, " ".join(comps[call.group(1)])

    # the 4n-lane bias gradient was one; the tiled bias is a reshape
    assert not list(fusions_making("bf16[4,512]"))
    for db, g in (("bf16[4,128]", "bf16[4,32,63,63,128]"),
                  ("bf16[4,32]", "bf16[4,32,62,62,32]")):
        made = [body for line, body in fusions_making(db)
                if g + "{" in line.split(" fusion(")[0]]
        assert len(made) == 1, (db, g, len(made))  # with the pooled cotangent
        assert " convolution(" in made[0] and " reduce(" in made[0], db


def test_packed_resnet_step_fills_the_lanes_on_a_described_v5e(one_chip):
    """`resnet20.sync_e1`'s step (32 clients x 32 images through
    `ResNet20.folded_apply`, forward and gradient) compiled for a described
    chip keeps the form PR 37 gave it (PERF.md section 6): the clients are
    packed into the lanes, so every full-size array (a stage-3 activation's
    4,194,304 elements or more) has 128 or more elements in its minor
    dimension (under `vmap` they are `[32,32,32,32,16]` with 32 clients or 32
    images in the lanes); the step reads and writes under 20 GB by the
    compiler's count (14.35; 29.5 under `vmap`, 264 through `folded_conv`'s
    nine products a convolution) with under 1 GB of temporaries (0.644; 2.45
    under `vmap`). On the chip that is a step of 11.8 ms against 37.8."""
    import optax

    from hefl_tpu.models import ResNet20
    from hefl_tpu.models.folded import fold_clients, unfold_clients

    c, b = 32, 32
    model = ResNet20(num_classes=10)
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 32, 32, 3)))["params"])

    def loss(ps, x, y):
        logits = unfold_clients(
            model.folded_apply(ps, fold_clients(x), num_clients=c), c)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean(-1).sum()

    on_chip = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(jax.value_and_grad(loss)).lower(
            jax.tree_util.tree_map(lambda a: on_chip((c, *a.shape), a.dtype), params),
            on_chip((c, b, 32, 32, 3), jnp.float32),
            on_chip((c, b), jnp.int32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert cost["bytes accessed"] < 20e9, cost["bytes accessed"]
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9

    thin = set()
    arrays = re.findall(r"\b(?:bf16|f32)\[([\d,]+)\]\{([\d,]+)[:}]", compiled.as_text())
    assert len(arrays) > 1000
    for dims, layout in arrays:
        dims = [int(d) for d in dims.split(",")]
        if math.prod(dims) >= 4 * 32 * 32 * 32 * 32 and dims[int(layout.split(",")[0])] < 128:
            thin.add((tuple(dims), layout))
    assert not thin, sorted(thin)
