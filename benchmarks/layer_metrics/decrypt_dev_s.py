"""Owner decrypt (`fl/secure.decrypt_average`, `ckks/ops`): seconds per
round in which an operation ran on the device while `hefl.phase.decrypt`
was open; against `decrypt_s` it says how much of the phase is the host."""

PHASE = "hefl.phase.decrypt"


def read(record, trace):
    if not trace:
        return None
    busy = trace["phase_busy_s"].get(PHASE, 0.0)
    return busy / trace["rounds_traced"] if busy > 0 else None
