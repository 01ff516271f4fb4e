"""Round program (`fl/client`): device seconds per traced round of the ops under
`hefl.batch`: a step's gather of its batch by index and the rescale."""

import device_scopes as ds


def read(record, trace):
    return ds.under(trace, "hefl.batch")
