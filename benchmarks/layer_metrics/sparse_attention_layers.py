"""Model (`models/lm/attention.selected_attention`): attention layers of the last
traced forward whose softmax ran over an indexer's selection alone: the
program's gauge `model.sparse_attention_layers`. 6 for the
`deepseek-v32-exp-l5e8` cut (1 dense + 4 expert layers + the prediction
module); a model without an indexer sets it to 0 and its line leaves the
metric out, as does a program that has no such gauge."""


def read(record, trace):
    try:
        from hefl_tpu.obs import metrics
    except ImportError:
        return None
    value = metrics.gauge("model.sparse_attention_layers").value
    return float(value) if value else None
