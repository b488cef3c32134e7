"""Device milliseconds a tree under ``lgb.split_apply``: the waves' top-k
selection, the routing of every row to its new leaf and the record writes.
From ``run["scopes"]``; ``None`` as ``phase_scopes`` says."""

from benchmark import phase_scopes


def read(run):
    return phase_scopes.phase_ms(run, "lgb.split_apply")
