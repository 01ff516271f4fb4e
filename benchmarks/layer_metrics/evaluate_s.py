"""Evaluation (`fl/fedavg.evaluate`): median PhaseTimer seconds of
`evaluate` (the test-set forward and the host's metrics) over the window."""

import statistics


def read(record, trace):
    vals = [r["phases"]["evaluate"] for r in record["rounds"]
            if "evaluate" in r["phases"]]
    return float(statistics.median(vals)) if vals else None
