"""Share of the window's waves whose histogram brought its live rows to
the front before contracting them: 100 x ``grow.waves_gathered`` /
``grow.waves``, both counted by the scan itself (on a mesh the first is
a mean over the shards, each deciding from its own count).  A wave
compacts when fewer than a fixed share of the rows it would scan are
live, so the waves left out are the root waves, where (nearly) every
row is.  ``None`` when the program has no such counter."""


def read(run):
    c = run["window_counters"]
    if "grow.waves_gathered" not in c or not c.get("grow.waves"):
        return None
    return 100.0 * c["grow.waves_gathered"] / c["grow.waves"]
