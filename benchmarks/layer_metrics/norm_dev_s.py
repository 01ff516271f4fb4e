"""Image model (`models/resnet`): device seconds per traced round of the
training step's ops under `hefl.norm` (inside `hefl.sgd_core`: a part of
`sgd_dev_s`): GroupNorm, forward and backward."""

import device_scopes as ds


def read(record, trace):
    return ds.under(trace, "hefl.norm", within=ds.STEP)
