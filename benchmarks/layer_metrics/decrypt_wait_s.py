"""Owner decrypt: host seconds the driver waits for the new parameters after
`decrypt_average` has returned (the `hefl.phase.decrypt.wait` span around
its `block_until_ready`), median over the window's rounds."""

import span_metrics as sm


def read(record, trace):
    return sm.window_median_s(sm.DECRYPT_STEP + "wait")
