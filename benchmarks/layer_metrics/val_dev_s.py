"""Round program (`fl/client`): device seconds per traced round of the ops under
`hefl.val`: the per-epoch validation forward and the callbacks' state."""

import device_scopes as ds


def read(record, trace):
    return ds.under(trace, "hefl.val")
