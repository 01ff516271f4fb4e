"""The reader of the entry that `tests/benchmark/test_benchmark_additions.py`
appends last to a copy of BENCHMARK.json: what a later PR's addition brings
beside its entry, a file of its own under `paths`. No BENCHMARK.json of the
repository lists it, and none may (the copy would then name it twice). It
finds nothing to read."""


def read(record, trace):
    return None
