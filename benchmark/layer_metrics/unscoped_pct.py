"""Share of the device's busy time under no name of the program: self time
the per-scope reduction could give to no ``jax.named_scope``
(``run["scopes"]["unscoped"]``, whose ``top`` lists the largest such
instructions with their source lines) over all self time.  0.0 when every
instruction has a name; ``None`` when the run has no such reduction."""

from benchmark import phase_scopes


def read(run):
    scopes = phase_scopes.reduction(run)
    if scopes is None:
        return None
    unscoped = phase_scopes.self_s(scopes, "unscoped") or 0.0
    return 100.0 * unscoped / scopes["busy_s"]
