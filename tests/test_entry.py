"""Driver-contract tests for `__graft_entry__`.

The driver compile-checks `entry()` single-chip and runs
`dryrun_multichip(N)` on a virtual N-device CPU mesh; these tests exercise
both contracts in CI (conftest pins an 8-device CPU platform) so a broken
entry point is caught before the driver ever runs it.
"""

import sys
from pathlib import Path

import jax

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import __graft_entry__  # noqa: E402


def test_entry_compiles():
    fn, args = __graft_entry__.entry()
    compiled = jax.jit(fn).lower(*args).compile()
    out_shape = jax.eval_shape(fn, *args)
    assert out_shape.shape == (4, 2)
    assert compiled is not None


def test_dryrun_multichip_8():
    # conftest provisions 8 virtual CPU devices, so this takes the
    # in-process path — the same _dryrun_impl the subprocess re-exec runs.
    __graft_entry__.dryrun_multichip(8)


def test_dryrun_subprocess_reexec():
    # Force the subprocess path even though this process has 8 devices:
    # ask for more devices than exist. The child must self-provision a
    # 16-device CPU mesh and run the full encrypted step.
    __graft_entry__.dryrun_multichip(16)
