"""Token model (`models/lm/kda.kda_layer`): device seconds per traced round of the
training step's ops under `hefl.kda` (inside `hefl.sgd_core`: a part of
`sgd_dev_s`; validation's and evaluation's are in `val_dev_s` and
`evaluate_dev_s`): the linear-attention layers whole (projections, short
convolutions, gates, the chunked recurrence, output norm and gate), forward,
the forward made again and backward. A program without the scope (a model
without linear layers, a parent commit) leaves the metric out."""

import device_scopes as ds


def read(record, trace):
    return ds.under(trace, "hefl.kda", within=ds.STEP)
