"""Token model (`models/lm/`): device seconds per traced round of the training
step's ops under `hefl.mla`, `hefl.gqa`, `hefl.dsa.attend` or
`hefl.swa.attend` (inside `hefl.sgd_core`: a part of `sgd_dev_s`;
validation's and evaluation's are in `val_dev_s` and `evaluate_dev_s`):
attention whole (projections, RoPE, the indexer, the fused kernels and what
XLA runs around them), the prediction module's own included."""

import device_scopes as ds


def read(record, trace):
    return ds.under(trace, *ds.ATTENTION, within=ds.STEP)
