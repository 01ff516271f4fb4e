"""No path reports a chip it did not run on.

`chip_smoke.py` and the measurement drivers must FAIL without a TPU (no CPU
fallback, no `"ok": true`, no metric line), and the compile cache must sit
where `JAX_COMPILATION_CACHE_DIR` says or at one fixed absolute path in
the checkout, whatever the working directory. Every child here is pinned
to the CPU, so none needs a device.
"""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _run(args, cwd=REPO, **env_overrides):
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_COMPILATION_CACHE_DIR", "BENCH_SMOKE",
                     "BENCH_PLATFORM")
    }
    env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO, **env_overrides})
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300,
    )


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]], ids=["1chip", "4chips"])
def test_chip_smoke_refuses_without_a_tpu(argv):
    proc = _run([os.path.join(REPO, "chip_smoke.py"), *argv])
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs the tpu backend" in proc.stderr


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    # The contract's second negative: the script without the program.
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = _run(["chip_smoke.py"], cwd=tmp_path, PYTHONPATH="")
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_bench_without_smoke_flag_or_tpu_fails():
    proc = _run([os.path.join(REPO, "bench.py")])
    assert proc.returncode != 0
    assert "metric" not in proc.stdout and "fallback" not in proc.stdout
    assert "needs the tpu backend" in proc.stderr


_PRINT_CACHE = (
    "from hefl_tpu.utils.device import setup_compile_cache; "
    "print(setup_compile_cache())"
)


def test_compile_cache_honours_environment(tmp_path):
    want = str(tmp_path / "elsewhere")
    proc = _run(["-c", _PRINT_CACHE], JAX_COMPILATION_CACHE_DIR=want)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip().splitlines()[-1] == want


def test_compile_cache_default_is_fixed_and_absolute(tmp_path):
    got = [
        _run(["-c", _PRINT_CACHE], cwd=cwd).stdout.strip().splitlines()[-1]
        for cwd in (REPO, str(tmp_path))
    ]
    assert got[0] == got[1] == os.path.join(REPO, ".jax_cache")


def test_select_platform_pins_cpu_or_requires_tpu():
    from hefl_tpu.utils.device import select_platform

    # conftest pins this process to the CPU: the smoke flag's pin is a
    # no-op that reports it, and a run without the flag must refuse.
    assert select_platform("t.py", cpu=True) == "cpu"
    with pytest.raises(SystemExit, match="needs the tpu backend"):
        select_platform("t.py", cpu=False)
