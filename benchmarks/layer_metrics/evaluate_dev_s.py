"""Evaluation (`fl/fedavg.evaluate`): device seconds per traced round of the ops
under `hefl.evaluate`: the test-set forward. Against `evaluate_s` it says
how much of the phase is the host."""

import device_scopes as ds


def read(record, trace):
    return ds.under(trace, "hefl.evaluate")
