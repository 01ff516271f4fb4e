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


def create_model(
    name: str = "medcnn",
    num_classes: int | None = None,
    input_shape: tuple[int, int, int] | None = None,
    rng: jax.Array | None = None,
):
    """Build (module, params) — the analog of `create_model()` at
    FLPyfhelin.py:118 (minus the load-path branch, which lives in
    utils.checkpoint where loading belongs). num_classes/input_shape
    default per model from MODEL_REGISTRY.
    """
    if name not in MODEL_REGISTRY:
        raise ValueError(f"unknown model {name!r}; available: {sorted(MODEL_REGISTRY)}")
    cls, default_classes, default_shape = MODEL_REGISTRY[name]
    module = cls(num_classes=num_classes if num_classes is not None else default_classes)
    if rng is None:
        rng = jax.random.key(0)
    # MedCNN's stages set it as they are traced; no other model has any.
    obs_metrics.gauge("model.polyphase_stages").set(0)
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
    "create_model",
    "count_params",
    "MODEL_REGISTRY",
]
