"""Share of the wave histogram's leaf slots that ended as a split in
the window: 100 x (``grow.leaves`` - ``grow.trees``) /
``grow.wave_slots`` (each wave contracts its stage's full width of stat
columns, however many leaves are pending).  ``None`` when the program
has no such counters."""


def read(run):
    c = run["window_counters"]
    if "span_n.train.chunk" not in c or not c.get("grow.wave_slots"):
        return None
    return 100.0 * (c.get("grow.leaves", 0) - c.get("grow.trees", 0)) \
        / c["grow.wave_slots"]
