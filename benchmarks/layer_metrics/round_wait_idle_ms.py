"""Driver layer: milliseconds a traced round's chip is idle while the host
waits on it. Host seconds of the `...train+encrypt+aggregate.device_wait`
span (the driver's `block_until_ready`) over the traced rounds, less the
device-busy seconds the trace puts under that annotation: gaps between the
round program's operations and a late collection of its result."""

import span_metrics as sm

NAME = sm.TRAIN_STEP + "device_wait"


def read(record, trace):
    if not trace:
        return None
    waits = sm.per_round_s((NAME,), last=trace["rounds_traced"])
    busy = trace["phase_busy_s"].get(NAME)
    if not waits or busy is None:
        return None
    idle_ms = (sum(waits) - busy) / len(waits) * 1e3
    return idle_ms if idle_ms > 0 else None
