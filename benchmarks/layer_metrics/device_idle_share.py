"""Device: the share of the traced window in which no operation ran on the
chip (1 - union of device-op intervals / traced window), percent."""


def read(record, trace):
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
