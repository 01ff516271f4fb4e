"""Model (`models/lm/attention.grouped_heads`): attention layers of the last traced
forward that attend a window of keys alone (a query's `window` last keys,
its own among them): the program's gauge `model.window_attention_layers`. 5
for the `mimo-v2-flash-l7e16` cut (published layers 0-6: five window layers
to two global ones); a model without window layers sets it to 0 and its line
leaves the metric out, as does a program that has no such gauge."""


def read(record, trace):
    try:
        from hefl_tpu.obs import metrics
    except ImportError:
        return None
    value = metrics.gauge("model.window_attention_layers").value
    return float(value) if value else None
