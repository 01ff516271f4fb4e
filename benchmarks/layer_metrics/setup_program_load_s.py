"""Programs (XLA, `utils/device.setup_compile_cache`): seconds the backend
spent compiling executables or loading them from the compile cache, the
program's `jax.compile_seconds` counter when the window has closed (no
executable is made inside the window). Most of it lies inside
`setup_rounds_s`."""

import span_metrics as sm


def read(record, trace):
    return sm.counter("jax.compile_seconds")
