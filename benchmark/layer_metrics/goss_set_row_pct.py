"""Share of the keyed rows a GOSS tree's waves may scan: 100 x
``grow.goss_set_rows`` over ``grow.goss_keys``, the scan's own work
counters over the window.  The fused scan brings a tree's row set (the
top and the sampled rows) to the front once and every wave reads only
those, so this is ``goss_rows_pct`` plus the all-zero rows that fill the
compaction's last tile of each block (about 10.7 at a = b = 0.05); a
tree that scans every real row reads 100.  ``None`` when the program
has no such counter or no tree of the window sampled."""


def read(run):
    c = run.get("window_counters") or {}
    if not c.get("grow.goss_keys") or "grow.goss_set_rows" not in c:
        return None
    return 100.0 * c["grow.goss_set_rows"] / c["grow.goss_keys"]
