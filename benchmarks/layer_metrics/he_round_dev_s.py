"""Round program (`fl/secure`, `ckks/*`): device seconds per traced round of the
ops under `hefl.encrypt`, `hefl.psum_aggregate`, `hefl.aggregate`,
`hefl.sanitize` and `hefl.transcipher`: the HE kernels AND the XLA ops
around them (packing, encoding, sampling, the modular sum), which
`he_kernel_dev_s` leaves out."""

import device_scopes as ds


def read(record, trace):
    return ds.under(trace, "hefl.encrypt", "hefl.psum_aggregate", "hefl.aggregate",
                    "hefl.sanitize", "hefl.transcipher")
