"""Device milliseconds a tree in the wave histogram: self time under
``lgb.wave_hist`` and under every stage's ``lgb.wave_hist.s<i>`` inside it
(the compaction, ``lgb.wave_gather``, is inner and not in it), from
``run["scopes"]``.  ``None`` as ``phase_scopes`` says, and when the trace
reaches the histogram under no stage name: the per-stage readers would
then disagree with this one, and the program is not the one measured."""

from benchmark import phase_scopes


def read(run):
    scopes = phase_scopes.reduction(run)
    if scopes is None:
        return None
    stages = phase_scopes.stages(scopes)
    if not stages:
        return None
    bare = phase_scopes.self_s(scopes, phase_scopes.HIST) or 0.0
    return phase_scopes.ms_per(run, bare + sum(s for _, s in stages))
