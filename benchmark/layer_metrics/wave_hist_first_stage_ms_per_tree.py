"""Device milliseconds a tree in the histogram of the plan's first stage
(``lgb.wave_hist.s0``): the stage that holds the root wave, whose rows are
scanned where they lie.  From ``run["scopes"]``; ``None`` as
``phase_scopes`` says."""

from benchmark import phase_scopes


def read(run):
    return phase_scopes.phase_ms(run, phase_scopes.STAGE + "0")
