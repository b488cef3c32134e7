"""Device milliseconds a tree spent bringing the waves' live rows to the
front (``lgb.wave_gather``: the MXU compaction inside every wave but a
tree's root wave).  From ``run["scopes"]``; ``None`` as ``phase_scopes``
says."""

from benchmark import phase_scopes


def read(run):
    return phase_scopes.phase_ms(run, "lgb.wave_gather")
