"""Model (`models/lm/kda.kda_front_kernel`): linear-attention layers of the
last traced forward whose front (short convolutions, SiLU, head norms, decay
gate, the move into chunks) is the Pallas kernel pair `kda_front_fwd` /
`kda_front_bwd` and not XLA's operations: the program's gauge
`model.kda_front_kernel_layers`. 5 for the `ling-3-flash-l6e128` cut (every
linear layer: a head is 128 lanes wide); a model without such layers, or
whose heads are no whole lanes, sets it to 0 and its line leaves the metric
out, as does a program that has no such gauge."""


def read(record, trace):
    try:
        from hefl_tpu.obs import metrics
    except ImportError:
        return None
    value = metrics.gauge("model.kda_front_kernel_layers").value
    return float(value) if value else None
