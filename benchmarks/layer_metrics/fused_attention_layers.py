"""Model (`models/lm/attention.causal_attention`): attention layers of the last traced
forward that went through the fused Pallas kernel (no float32 score block in
HBM): the program's gauge `model.fused_attention_layers`. 6 for the
`joyai-llm-flash-l5e128` cut (1 dense + 4 expert layers + the prediction
module); an image model resets it to 0 and its line leaves the metric out,
as does a program that has no such gauge."""


def read(record, trace):
    try:
        from hefl_tpu.obs import metrics
    except ImportError:
        return None
    value = metrics.gauge("model.fused_attention_layers").value
    return float(value) if value else None
