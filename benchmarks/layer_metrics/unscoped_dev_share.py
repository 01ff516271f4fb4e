"""Device: percent of the traced rounds' busy seconds spent in ops under no
`hefl.*` scope (parameter copies, the loops' own bookkeeping, the driver's
small programs): what the scope metrics cannot place."""

import device_scopes as ds


def read(record, trace):
    return ds.unscoped_share(trace)
