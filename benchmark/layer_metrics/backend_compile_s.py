"""Seconds JAX spent in backend compiles (or loading them from the
persistent cache) during set-up, from ``compile_cache.counters()``."""


def read(run):
    v = run["setup_counters"].get("cache.backend_compile_s")
    return None if v is None else float(v)
