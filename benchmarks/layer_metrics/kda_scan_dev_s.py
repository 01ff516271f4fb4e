"""Token model (`models/lm/kda.kda_recurrence`): device seconds per traced round
of the training step's ops under `hefl.kda.scan` (inside `hefl.sgd_core`; a
part of `kda_dev_s`): the chunked delta-rule recurrence alone, what is made
ahead of the scan over the chunks and the scan's steps, forward, made again
and backward."""

import device_scopes as ds


def read(record, trace):
    return ds.under(trace, "hefl.kda.scan", within=ds.STEP)
