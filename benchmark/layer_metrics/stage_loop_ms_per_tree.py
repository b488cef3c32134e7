"""Device milliseconds a tree that the stages' ``while`` loops take for
themselves (``lgb.stage_loop``): a ``while``'s own self time — the copies
of its carried state — and its condition; the body's phases have inner
names and are not in it.  From ``run["scopes"]``; ``None`` as
``phase_scopes`` says."""

from benchmark import phase_scopes


def read(run):
    return phase_scopes.phase_ms(run, "lgb.stage_loop")
