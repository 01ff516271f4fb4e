"""Owner decrypt (`fl/secure.decrypt_average`, `ckks/ops`): host seconds to
bring the aggregate onto one device and launch the decrypt kernel (the
`hefl.phase.decrypt.kernel` span), median over the window's rounds."""

import span_metrics as sm


def read(record, trace):
    return sm.window_median_s(sm.DECRYPT_STEP + "kernel")
