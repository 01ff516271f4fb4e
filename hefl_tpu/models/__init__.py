"""Model zoo — TPU-first flax.linen modules.

The reference has exactly one model: a Sequential Keras 6-conv CNN built by
`create_model` (/root/reference/FLPyfhelin.py:118-146, SURVEY.md §2.3). We
reproduce it bit-for-bit in architecture (`MedCNN`: 222,722 params at
256x256x3) and add the two models the baseline configs call for
(BASELINE.json): `SmallCNN` (2-conv MNIST) and `ResNet20` (CIFAR-10).

All models are pure functions of (params, batch) under jit; compute runs in
bfloat16 on the MXU with float32 parameters/accumulation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from hefl_tpu.models.cnn import LogReg, MedCNN, SmallCNN, count_params
from hefl_tpu.models.lm import (
    PRESETS as LM_PRESETS,
    FrozenBaseLM,
    JoyAIFlash,
    _set_layer_gauges,
    frozen_base,
    is_token_model,
    set_frozen_base,
)
from hefl_tpu.models.resnet import ResNet20
from hefl_tpu.obs import metrics as obs_metrics

# name -> (module class, default num_classes, default input shape): each
# model's defaults are the dataset it was designed for, so
# create_model("smallcnn") alone builds the right MNIST-shaped network.
MODEL_REGISTRY: dict[str, tuple[type, int, tuple[int, int, int]]] = {
    "medcnn": (MedCNN, 2, (256, 256, 3)),
    "smallcnn": (SmallCNN, 10, (28, 28, 1)),
    "logreg": (LogReg, 10, (28, 28, 1)),
    "resnet20": (ResNet20, 10, (32, 32, 3)),
}
# Token models (models/lm/): a frozen bfloat16 base that stays on the
# client and a trained subset, which is what `create_model` returns as
# `params`. name -> (widths and the share held, default vocabulary held).
TOKEN_MODELS: dict[str, int] = {
    "joyai_llm_flash": 16160,
    "joyai_llm_flash_tiny": 64,
    "deepseek_v32": 16160,
    "deepseek_v32_tiny": 64,
    "mimo_v2_flash": 19072,
    "mimo_v2_flash_tiny": 64,
    "ling_3_flash": 19648,
    "ling_3_flash_tiny": 64,
}


def create_model(
    name: str = "medcnn",
    num_classes: int | None = None,
    input_shape: tuple[int, int, int] | None = None,
    rng: jax.Array | None = None,
    seed: int = 0,
):
    """Build (module, params) — the analog of `create_model()` at
    FLPyfhelin.py:118 (minus the load-path branch, which lives in
    utils.checkpoint where loading belongs). num_classes/input_shape
    default per model from MODEL_REGISTRY.

    For a token model `params` is the trained subset alone; its frozen base
    is made from `seed` on the device at first use and held once a process
    (`frozen_base(module)`), never returned here.
    """
    # ResNet20's client-folded forward sets it as it is traced; 0 in every
    # other model's record.
    obs_metrics.gauge("model.packed_conv_layers").set(0)
    if name in TOKEN_MODELS:
        module = FrozenBaseLM(
            num_classes=num_classes or TOKEN_MODELS[name],
            arch=LM_PRESETS[name], seed=int(seed),
        )
        return module, module.init_trained(rng)
    if name not in MODEL_REGISTRY:
        raise ValueError(
            f"unknown model {name!r}; available: "
            f"{sorted(MODEL_REGISTRY) + sorted(TOKEN_MODELS)}"
        )
    cls, default_classes, default_shape = MODEL_REGISTRY[name]
    module = cls(num_classes=num_classes if num_classes is not None else default_classes)
    if rng is None:
        rng = jax.random.key(0)
    # MedCNN's stages set it as they are traced; no other model has any.
    obs_metrics.gauge("model.polyphase_stages").set(0)
    # a token model's forward sets them as it is traced (models/lm/model.py)
    _set_layer_gauges()
    dummy = jnp.zeros(
        (1, *(input_shape if input_shape is not None else default_shape)), jnp.float32
    )
    params = module.init(rng, dummy)["params"]
    return module, params


__all__ = [
    "LogReg",
    "MedCNN",
    "SmallCNN",
    "ResNet20",
    "FrozenBaseLM",
    "JoyAIFlash",
    "frozen_base",
    "set_frozen_base",
    "is_token_model",
    "TOKEN_MODELS",
    "create_model",
    "count_params",
    "MODEL_REGISTRY",
]
