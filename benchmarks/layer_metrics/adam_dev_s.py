"""Round program (`fl/client`, `fl/optimizer.adam_update`): device seconds per
traced round of the ops under `hefl.adam`: the optimizer's update of every
step. Listed in the image cells (0.002-0.003 s a round); in the token cells
the scope is there and reads 0.00002 s (PERF.md, PR 36)."""

import device_scopes as ds


def read(record, trace):
    return ds.under(trace, "hefl.adam")
