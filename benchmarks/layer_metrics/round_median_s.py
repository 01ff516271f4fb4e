"""Driver layer: the median wall-clock of the window's rounds (differences
of consecutive `round_end` stamps). Steadier than the end-to-end `round_s`,
which is the whole window over its rounds and so pays for every stall; the
two apart say that some rounds stalled."""

import statistics


def read(record, trace):
    walls = [r["wall_s"] for r in record["rounds"]]
    return float(statistics.median(walls)) if walls else None
