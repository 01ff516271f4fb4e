"""Token model (`models/lm/attention.select_keys`): device seconds per traced
round of the training step's ops under `hefl.dsa.index` (inside
`hefl.sgd_core`; a part of `attention_dev_s`): the indexer's scores and its
selection."""

import device_scopes as ds


def read(record, trace):
    return ds.under(trace, "hefl.dsa.index", within=ds.STEP)
