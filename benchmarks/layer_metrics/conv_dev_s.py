"""Image model (`models/cnn._conv_stages`, `models/resnet`): device seconds per
traced round of the training step's ops under `hefl.conv` (inside
`hefl.sgd_core`: a part of `sgd_dev_s`; validation's and evaluation's
convolutions are in `val_dev_s` and `evaluate_dev_s`): medcnn's conv stages
with their bias, ReLU and pool, resnet20's convolutions."""

import device_scopes as ds


def read(record, trace):
    return ds.under(trace, "hefl.conv", within=ds.STEP)
