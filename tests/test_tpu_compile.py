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
