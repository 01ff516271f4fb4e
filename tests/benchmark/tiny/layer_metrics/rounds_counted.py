"""A per-layer metric added as a file: rounds the window held."""


def read(record, trace):
    return float(len(record["rounds"])) or None
