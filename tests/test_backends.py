"""The three backend decisions are rules over what the code can observe.

HE kernels (`ckks.backend`), the augment shear (`data.augment`) and the
client lowering (`fl.fusion`) each resolve from the platform, the ring, the
model and the configuration: no probe, no persisted winner, no environment
switch. One table, a case a row; the platform is set by patching
`ckks.ntt.on_tpu_backend`.
"""

import os
import re

import jax
import pytest

from hefl_tpu.ckks import backend as he_backend
from hefl_tpu.ckks import ntt as ntt_mod
from hefl_tpu.ckks.keys import CkksContext
from hefl_tpu.data import augment
from hefl_tpu.fl import fusion
from hefl_tpu.models import LogReg, MedCNN, ResNet20, SmallCNN, lm
from hefl_tpu.obs import metrics as obs_metrics

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


class _NoFold:
    """An image model without the client-folded forward."""


def _he(n, override=None):
    return lambda: he_backend.resolve_he_backend(
        CkksContext.create(n=n), override)


def _lowering(setting, module):
    return lambda: fusion.resolve_fusion_backend(setting, module())


def _token_model():
    return lm.JoyAIFlash(num_classes=64,
                         arch=lm.PRESETS["joyai_llm_flash_tiny"], seed=0)


def _image_model():
    return SmallCNN(num_classes=10)


# (id, on a TPU?, the resolution, what it gives: a backend or an error)
RULE = [
    ("he-tpu-tiling-ring", True, _he(4096), "pallas"),
    ("he-tpu-n256", True, _he(256), "xla"),
    ("he-cpu", False, _he(4096), "xla"),
    ("he-tpu-override-xla", True, _he(4096, "xla"), "xla"),
    ("he-override-pallas-n256", False, _he(256, "pallas"), "xla"),
    ("he-unknown-name", True, _he(4096, "nope"), ValueError),
    ("augment-tpu", True, augment.resolve_shift_backend, "dft"),
    ("augment-cpu", False, augment.resolve_shift_backend, "gather"),
    ("augment-override-fft", True,
     lambda: augment.resolve_shift_backend("fft"), "fft"),
    ("augment-unknown-name", False,
     lambda: augment.resolve_shift_backend("fancy"), ValueError),
    ("lowering-image-auto", True, _lowering("auto", _image_model), "vmap"),
    ("lowering-medcnn-auto", True, _lowering("auto", MedCNN), "vmap"),
    ("lowering-logreg-auto", True, _lowering("auto", LogReg), "vmap"),
    ("lowering-without-folded-apply-auto", True, _lowering("auto", _NoFold),
     "vmap"),
    # its client-folded forward packs the clients into the lanes, and says so
    ("lowering-resnet20-auto", True, _lowering("auto", ResNet20), "fused"),
    ("lowering-resnet20-auto-cpu", False, _lowering(None, ResNet20), "fused"),
    ("lowering-resnet20-pinned-vmap", True, _lowering("vmap", ResNet20),
     "vmap"),
    ("lowering-image-fused", False, _lowering("fused", _image_model), "fused"),
    ("lowering-fused-without-folded-apply", False,
     _lowering("fused", _NoFold), ValueError),
    ("lowering-token-auto", True, _lowering("auto", _token_model), "serial"),
    ("lowering-token-pinned", True, _lowering("vmap", _token_model),
     ValueError),
]


@pytest.mark.parametrize("on_tpu,resolve,want",
                         [row[1:] for row in RULE],
                         ids=[row[0] for row in RULE])
def test_backend_rule(monkeypatch, on_tpu, resolve, want):
    monkeypatch.setattr(ntt_mod, "on_tpu_backend", lambda: on_tpu)
    monkeypatch.setattr(ntt_mod, "_BACKEND", "auto")
    if isinstance(want, str):
        assert resolve() == want
    else:
        with pytest.raises(want):
            resolve()


def test_resolving_runs_nothing(monkeypatch, tmp_path):
    # On a "TPU" (where the probes ran, at a module's first resolution):
    # the three resolutions compile nothing and leave no file beside the
    # compile cache.
    obs_metrics.install_jax_listeners()
    prev_dir = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    try:
        monkeypatch.setattr(ntt_mod, "on_tpu_backend", lambda: True)
        monkeypatch.setattr(ntt_mod, "_BACKEND", "auto")
        ctx = CkksContext.create(n=4096)
        before = obs_metrics.snapshot().get("jax.new_executables", 0)
        assert he_backend.resolve_he_backend(ctx) == "pallas"
        assert augment.resolve_shift_backend() == "dft"
        assert fusion.resolve_fusion_backend("auto", _image_model()) == "vmap"
        assert obs_metrics.snapshot().get("jax.new_executables", 0) == before
        assert os.listdir(tmp_path) == []
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_dir)


def test_no_selection_switch_is_read():
    switch = re.compile(
        "HEFL_HE|HEFL_AUG_SHIFT|HEFL_CLIENT_FUSION|HEFL_AUTOSELECT_CACHE")
    hits = []
    for root, _, files in os.walk(os.path.join(REPO, "hefl_tpu")):
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(root, name)
                with open(path) as f:
                    if switch.search(f.read()):
                        hits.append(os.path.relpath(path, REPO))
    assert hits == []
