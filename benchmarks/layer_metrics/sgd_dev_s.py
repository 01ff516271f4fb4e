"""Round program (`fl/client`): device seconds per traced round of the ops under
`hefl.sgd_core` at any depth: local SGD whole, the model's forward and
backward passes, the optimizer, the batch and its augmentation. Self time
of the ops by their own `tf_op`, not by overlap with a host annotation."""

import device_scopes as ds


def read(record, trace):
    return ds.under(trace, "hefl.sgd_core")
