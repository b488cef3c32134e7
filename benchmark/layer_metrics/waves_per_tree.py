"""Passes over the rows per tree in the window: the scan's own wave
count (``grow.waves`` / ``grow.trees``, counted on the device and
drained into the registry).  ``None`` when the program has no such
counters (``span_n.train.chunk`` moves in every window of one that
has)."""


def read(run):
    c = run["window_counters"]
    if "span_n.train.chunk" not in c or not c.get("grow.trees"):
        return None
    return c.get("grow.waves", 0) / c["grow.trees"]
