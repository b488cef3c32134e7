"""Share of the real rows that a wave's histogram has to contract: 100 x
``grow.rows_live`` / ``grow.rows_real`` over the window, both summed over
waves by the scan's own work counters.  A row is live in a wave when its
leaf is one of the wave's pending leaves and it is in the bag; a program
that brings those rows to the front scans them and no others.  A plain
mean over waves, not weighted by their widths.  ``None`` when the
program has no such counter."""


def read(run):
    c = run["window_counters"]
    if "grow.rows_live" not in c or not c.get("grow.rows_real"):
        return None
    return 100.0 * c["grow.rows_live"] / c["grow.rows_real"]
