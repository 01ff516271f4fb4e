"""Device: peak bytes on the fullest chip (`reduce.memory_peak`: the
allocator's live peak, or the largest program's temporaries on top of what
is in use once the measured call has returned, whichever is larger), read
when the window closes and before the checks run, in GB (1e9 bytes)."""


def read(record, trace):
    peak = record.get("memory_peak_bytes", 0)
    return peak / 1e9 if peak > 0 else None
