"""Device milliseconds a tree in the histogram of the plan's closing stage:
the highest ``lgb.wave_hist.s<i>`` the window's trace reaches (the widest
waves: what a stat-column tile more or less costs).  From
``run["scopes"]``; ``None`` as ``phase_scopes`` says."""

from benchmark import phase_scopes


def read(run):
    scopes = phase_scopes.reduction(run)
    if scopes is None:
        return None
    stages = phase_scopes.stages(scopes)
    return phase_scopes.ms_per(run, stages[-1][1]) if stages else None
