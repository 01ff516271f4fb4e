"""Driver layer: milliseconds the host spends inside the round's entry point
(`secure_fedavg_round` / `engine.run_round`) until it returns, i.e. building
the call and handing the round program to the device: the
`hefl.phase.train+encrypt+aggregate.dispatch` span, median over the window's
rounds. The chip has nothing to do while this runs (a late launch)."""

import span_metrics as sm


def read(record, trace):
    med = sm.window_median_s(sm.TRAIN_STEP + "dispatch")
    return med * 1e3 if med is not None else None
