"""Share of the rows each wave scans that are padding: 100 x (1 -
``grow.rows_real`` / ``grow.rows_scanned``) over the window.  ``None``
when the program has no such counters."""


def read(run):
    c = run["window_counters"]
    if "span_n.train.chunk" not in c or not c.get("grow.rows_scanned"):
        return None
    return 100.0 * (1.0 - c.get("grow.rows_real", 0)
                    / c["grow.rows_scanned"])
