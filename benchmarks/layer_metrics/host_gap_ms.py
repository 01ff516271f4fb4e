"""Driver layer (`experiment.run_experiment`, `data/prefetch`): per round,
the wall-clock between `round_end` stamps less the round's three PhaseTimer
phases; the median over the window's rounds, in milliseconds."""

import statistics


def read(record, trace):
    gaps = [(r["wall_s"] - sum(r["phases"].values())) * 1e3
            for r in record["rounds"]]
    return float(statistics.median(gaps)) if gaps else None
