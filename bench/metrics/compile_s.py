"""XLA backend compile seconds during set-up (``jax.monitoring``'s
``backend_compile_duration``): near 0 once the persistent cache is warm."""


def read(run):
    return run.counters.get("compile_s")
