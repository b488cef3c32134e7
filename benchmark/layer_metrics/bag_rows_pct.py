"""Share of the real rows that a tree's histograms count: 100 x
``grow.rows_in_bag`` a tree over ``grow.rows_real`` a wave, both from the
scan's own work counters over the window (80 under ``bagging_fraction``
0.8; 100 is a program that does not sample).  ``None`` when the program
has no such counter."""


def read(run):
    c = run["window_counters"]
    if not (c.get("grow.rows_in_bag") and c.get("grow.trees")
            and c.get("grow.rows_real") and c.get("grow.waves")):
        return None
    return 100.0 * (c["grow.rows_in_bag"] / c["grow.trees"]) \
        / (c["grow.rows_real"] / c["grow.waves"])
