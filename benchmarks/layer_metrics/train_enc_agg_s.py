"""Round program (`fl/secure`, `fl/client`, `fl/fedavg`): median PhaseTimer
seconds of `train+encrypt+aggregate` over the window's rounds."""

import statistics

PHASE = "train+encrypt+aggregate"


def read(record, trace):
    vals = [r["phases"][PHASE] for r in record["rounds"] if PHASE in r["phases"]]
    return float(statistics.median(vals)) if vals else None
