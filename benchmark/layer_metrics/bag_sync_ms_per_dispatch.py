"""Device milliseconds a dispatch under ``lgb.bag_sync``: the bag drawn
again as a permutation buffer (an argsort of the row bucket) that the
boosting driver enqueues behind every fused dispatch of a bagged run.
From ``run["scopes"]`` over the window's dispatches; ``None`` as
``phase_scopes`` says (a run without a bag never reaches the name)."""

from benchmark import phase_scopes


def read(run):
    return phase_scopes.phase_ms(run, "lgb.bag_sync", per="dispatches")
