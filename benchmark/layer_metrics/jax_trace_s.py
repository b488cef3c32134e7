"""Seconds JAX spent during set-up tracing Python to jaxprs and lowering
them to MLIR modules (``compile_cache.counters()``'s ``trace_s`` +
``lower_s``): what no compile cache removes.  ``None`` when the program
does not count them."""


def read(run):
    c = run["setup_counters"]
    if "cache.trace_s" not in c and "cache.lower_s" not in c:
        return None
    return float(c.get("cache.trace_s", 0.0) + c.get("cache.lower_s", 0.0))
