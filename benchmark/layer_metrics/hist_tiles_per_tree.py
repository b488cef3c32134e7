"""Tiles of 128 stat columns the wave histogram's matmul contracted, per
tree in the window: ``grow.hist_tiles`` / ``grow.trees``, both counted
by the scan itself (one shard's on a mesh: replicated state decides the
tiles, so every shard counts the same).  A wave costs its tiles, not its
columns, and contracts those its pending leaves reach: a 255-leaf tree
on the ladder 4/4/4/16/16/32/64/128 of three columns takes 9 where the
stages' full widths are 11; a stage of one tile counts one a wave.
``None`` when the program has no such counter."""


def read(run):
    c = run["window_counters"]
    if "grow.hist_tiles" not in c or not c.get("grow.trees"):
        return None
    return c["grow.hist_tiles"] / c["grow.trees"]
