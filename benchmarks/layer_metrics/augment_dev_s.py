"""Image data (`data/augment.random_augment`): device seconds per traced round
of the ops under `hefl.augment`: the shear, zoom and flip of every batch."""

import device_scopes as ds


def read(record, trace):
    return ds.under(trace, "hefl.augment")
