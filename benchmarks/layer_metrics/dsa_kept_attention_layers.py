"""Model (`models/lm/model._kept`): selected-attention layers of the last
traced forward whose checkpoint keeps the attention kernel's output and
log-sum-exp for the gradient, so that the gradient runs no second forward
kernel: the program's gauge `dsa.kept_attention_layers`. 6 for the
`deepseek-v32-exp-l5e8` cut (every attention layer, the prediction module's
among them); 0 for every other model and where the checkpoint keeps nothing
(the form before PR 42): the line then leaves the metric out, as does a
program without the gauge."""


def read(record, trace):
    try:
        from hefl_tpu.obs import metrics
    except ImportError:
        return None
    value = metrics.gauge("dsa.kept_attention_layers").value
    return float(value) if value else None
