"""Driver layer: seconds of set-up spent running rounds: the `hefl.round`
spans that ended before the window opened (the warm-up call's two rounds,
which load or compile every program, and the measured call's lead-in)."""

import span_metrics as sm


def read(record, trace):
    return sm.setup_sum_s(lambda name: name == sm.ROUND)
