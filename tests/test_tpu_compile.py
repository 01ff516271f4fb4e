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

import os
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

    monkeypatch.setattr(lm, "_interpret", lambda: False)
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
            lambda x, w, s: lm._gmm_call(x, w, s, transpose)
        ).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


def test_fused_attention_gradient_compiles_for_described_v5e(
        one_chip, monkeypatch):
    """`jax.grad` of `models/lm.causal_attention` at JoyAI-LLM-Flash's widths
    (32 heads of 192 / 128) and the benchmark's batch (2 x 4,096 positions),
    the preset's block: the forward kernel and the gradient's (`dkv`, which
    forms `dq` too) lower through Mosaic, and no score block reaches HBM (the
    blocked XLA form read 38.5 GB here; q, k, v, their gradients and the
    layout changes are under 4)."""
    from hefl_tpu.models import lm

    monkeypatch.setattr(lm, "_interpret", lambda: False)
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
