"""Owner decrypt (`fl/secure.decrypt_average`, `ckks/ops`): median
PhaseTimer seconds of `decrypt` over the window's rounds."""

import statistics


def read(record, trace):
    vals = [r["phases"]["decrypt"] for r in record["rounds"]
            if "decrypt" in r["phases"]]
    return float(statistics.median(vals)) if vals else None
