"""Model (`models/lm/model.frozen_base`): seconds of set-up spent making the frozen
base on the device from the seed, leaf by leaf in bfloat16: the sum of the
`hefl.setup.base` spans that ended before the window opened (a process
makes a seed's base once; the later calls' spans find it made). A program
without the span, or a model without a base, has nothing to read."""

import span_metrics as sm


def read(record, trace):
    total = sm.setup_sum_s(lambda name: name == sm.SETUP_STEP + "base")
    # a model without a base records spans of microseconds: nothing to read
    return total if total is not None and total > 1e-3 else None
