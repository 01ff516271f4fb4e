"""Driver layer: seconds of set-up in `run_experiment`'s start, both calls:
the `hefl.setup.*` spans other than the data's (staging to the device,
model, the frozen base, HE context, pre-flight, keys, the stream engine;
the roofline's cost-analysis compile went in PR 29), summed over those that
ended before the window opened."""

import span_metrics as sm


def read(record, trace):
    return sm.setup_sum_s(lambda name: name.startswith(sm.SETUP_STEP)
                          and name != sm.SETUP_STEP + "data")
