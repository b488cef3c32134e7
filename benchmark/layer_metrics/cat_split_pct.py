"""Share of the window's splits made on a categorical feature: 100 x
``grow.cat_splits`` over the splits (``grow.leaves`` - ``grow.trees``),
the program's own counters over the window.  ``None`` when the program
has no such counter (none counts categorical splits, or no feature is
categorical) or the window split nothing."""


def read(run):
    c = run.get("window_counters") or {}
    splits = c.get("grow.leaves", 0) - c.get("grow.trees", 0)
    if "grow.cat_splits" not in c or splits <= 0:
        return None
    return 100.0 * c["grow.cat_splits"] / splits
