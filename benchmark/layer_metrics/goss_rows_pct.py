"""Share of the keyed rows a GOSS tree takes: 100 x (``grow.goss_top`` +
``grow.goss_sampled``) over ``grow.goss_keys``, the scan's own work
counters over the window (about 10 at a = b = 0.05: the top 5% by
|g*h|, ties included, and a 5% sample of the rest).  ``None`` when the
program has no such counter or no tree of the window sampled."""


def read(run):
    c = run.get("window_counters") or {}
    if not c.get("grow.goss_keys") or "grow.goss_top" not in c:
        return None
    return 100.0 * (c["grow.goss_top"] + c.get("grow.goss_sampled", 0)) \
        / c["grow.goss_keys"]
