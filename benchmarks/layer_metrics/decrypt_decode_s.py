"""Owner decrypt (`fl/secure.decrypt_average`, `ckks/encoding`,
`ckks/packing`): host seconds of the op-by-op decode and of the unpack into
the parameter pytree (the `hefl.phase.decrypt.decode` and `.unpack` spans,
summed in a round), median over the window's rounds."""

import span_metrics as sm


def read(record, trace):
    return sm.window_median_s(sm.DECRYPT_STEP + "decode",
                              sm.DECRYPT_STEP + "unpack")
