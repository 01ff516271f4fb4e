"""The token models (`model.FrozenBaseLM` over a `common.LMArch`), a package
whose imports point one way: `common` <- `attention`, `kda`, `experts` <-
`model`. What the repository and the benchmark import is re-exported here by
name; a test reaches any other name, and patches any, through its module."""

from hefl_tpu.models.lm.attention import (
    causal_attention, grouped_attention, grouped_heads, latent_attention,
    select_keys, selected_attention)
from hefl_tpu.models.lm.common import PRESETS, LMArch, is_token_model
from hefl_tpu.models.lm.experts import grouped_matmul, held_experts, route
from hefl_tpu.models.lm.kda import kda_layer, kda_recurrence, short_conv
from hefl_tpu.models.lm.model import (
    BoundLM, FrozenBaseLM, JoyAIFlash, _set_layer_gauges, frozen_base,
    hybrid_layers, record_expert_load, set_frozen_base)

__all__ = [
    "PRESETS", "LMArch", "FrozenBaseLM", "JoyAIFlash", "BoundLM",
    "is_token_model", "frozen_base", "set_frozen_base", "record_expert_load",
    "route", "select_keys", "selected_attention", "latent_attention",
    "causal_attention", "grouped_attention", "grouped_heads",
    "grouped_matmul", "held_experts", "short_conv", "kda_layer",
    "kda_recurrence", "hybrid_layers"]
