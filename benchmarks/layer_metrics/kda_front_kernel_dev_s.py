"""Token model (`models/lm/kda._kda_front`): device seconds per traced round
of the custom calls of the families `kda_front_fwd` and `kda_front_bwd`,
wherever they ran (the step, validation, evaluation): the linear layers'
front as one Pallas pass over the projections' output each way. Inside
`kda_dev_s` where the step runs them (their ops carry `hefl.kda`). A program
whose front is XLA's operations has no such call and leaves the metric out."""

import device_scopes as ds


def read(record, trace):
    return ds.family(trace, "kda_front_")
