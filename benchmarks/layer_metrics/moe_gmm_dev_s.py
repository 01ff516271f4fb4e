"""Token model (`models/lm/experts.grouped_matmul`): device seconds per traced round of
the custom calls of family `gmm`: megablox's grouped product alone."""

import device_scopes as ds


def read(record, trace):
    return ds.family(trace, "gmm")
