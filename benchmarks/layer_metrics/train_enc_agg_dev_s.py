"""Round program (`fl/secure`, `fl/client`, `fl/fedavg`): seconds per round
in which an operation ran on the device while the driver's
`hefl.phase.train+encrypt+aggregate` annotation was open, from the trace."""

PHASE = "hefl.phase.train+encrypt+aggregate"


def read(record, trace):
    if not trace:
        return None
    busy = trace["phase_busy_s"].get(PHASE, 0.0)
    return busy / trace["rounds_traced"] if busy > 0 else None
