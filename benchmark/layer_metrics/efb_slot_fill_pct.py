"""Share of the histogram's slots that hold a bin: the program's gauge
``bin.slots_used`` (the bins its groups hold, each group's slot 0
included) over ``bin.groups`` x 256, the slots the groups may hold.
``None`` when the run hands over no gauges or the program sets none of
these."""


def read(run):
    g = run.get("gauges") or {}
    if not g.get("bin.groups") or g.get("bin.slots_used") is None:
        return None
    return 100.0 * g["bin.slots_used"] / (g["bin.groups"] * 256)
