"""Owner decrypt (`ckks/ops.decrypt`, `fl/secure._decode_unpack`): device seconds
per traced round of the ops under `hefl.decrypt`: c0 + c1*s, the inverse
NTT, the decode and the unpack, by the ops' own scope. `decrypt_dev_s`
takes the overlap of device ops with a host annotation a few milliseconds
long, which on a short phase reads the launch's timing and not the work."""

import device_scopes as ds


def read(record, trace):
    return ds.under(trace, "hefl.decrypt")
