"""Owner decrypt (`fl/secure.decrypt_average`, `ckks/encoding`,
`ckks/packing`): host seconds of the decode and of the unpack into the
parameter pytree (the `hefl.phase.decrypt.decode` and `.unpack` spans,
summed in a round), median over the window's rounds. Since PR 30 `.decode`
is the host forming the float32 coefficients and launching ONE compiled
program (`fl/secure._decode_unpack`) until the launch returns, and `.unpack`
what the host does after it (nothing on the float path); before, the
op-by-op `encoding.decode` and `unpack_blocks`."""

import span_metrics as sm


def read(record, trace):
    return sm.window_median_s(sm.DECRYPT_STEP + "decode",
                              sm.DECRYPT_STEP + "unpack")
