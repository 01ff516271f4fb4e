"""Token model (`models/lm/attention.grouped_attention`): device seconds per
traced round of the training step's ops under `hefl.swa.attend` (inside
`hefl.sgd_core`; a part of `attention_dev_s`): a window layer's fused
attention calls."""

import device_scopes as ds


def read(record, trace):
    return ds.under(trace, "hefl.swa.attend", within=ds.STEP)
