"""Token model (`models/lm/`): device seconds per traced round of the training
step's ops under `hefl.moe.route`, `hefl.moe.experts` or `hefl.moe_gmm`
(inside `hefl.sgd_core`: a part of `sgd_dev_s`): the expert layer whole
(router, sort, grouped product, un-sort), the prediction module's own
included."""

import device_scopes as ds


def read(record, trace):
    return ds.under(trace, *ds.MOE, within=ds.STEP)
