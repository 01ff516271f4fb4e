"""Programs (XLA, `utils/device.setup_compile_cache`): executables the
process made, compiled or loaded from the cache, the program's
`jax.new_executables` counter when the window has closed."""

import span_metrics as sm


def read(record, trace):
    return sm.counter("jax.new_executables")
