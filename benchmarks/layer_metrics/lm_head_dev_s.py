"""Token model (`models/lm/`): device seconds per traced round of the training
step's ops under `hefl.lm_head` or `hefl.mtp` (inside `hefl.sgd_core`: a
part of `sgd_dev_s`) and under no attention or expert-layer scope: head
logits and cross-entropy, and of the multi-token-prediction module (joyai,
deepseek) its own projection and norms. The module's attention and expert
layer are in `attention_dev_s` and `moe_dev_s`, so the three add up."""

import device_scopes as ds


def read(record, trace):
    return ds.under(trace, "hefl.lm_head", "hefl.mtp", within=ds.STEP,
                    outside=ds.ATTENTION + ds.MOE)
