"""Compiles inside the window: new jitted-program compiles plus requests
to the persistent cache (hits included).  0 is the expectation."""


def read(run):
    c = run["window_counters"]
    return float(sum(v for k, v in c.items()
                     if k.startswith("jit_compiles."))
                 + c.get("cache.requests", 0))
