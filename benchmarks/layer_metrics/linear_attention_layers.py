"""Model (`models/lm/model.hybrid_layers`): layers of the last traced forward whose
attention is the delta-rule recurrence: the program's gauge
`model.linear_attention_layers`. 5 for the `ling-3-flash-l6e128` cut
(published layers 1-6: five linear layers to one latent layer); a model
without linear layers sets it to 0 and its line leaves the metric out, as
does a program that has no such gauge."""


def read(record, trace):
    try:
        from hefl_tpu.obs import metrics
    except ImportError:
        return None
    value = metrics.gauge("model.linear_attention_layers").value
    return float(value) if value else None
