"""Share of the device's busy time spent selecting GOSS's rows: self time
under ``lgb.goss_select`` (each tree's top |g*h| rows, its sample of the
rest and their weights) over all self time, from the per-scope reduction
of the window's trace (``run["scopes"]``).  ``None`` when the run has no
such reduction or the trace never reaches the scope (a program without
the name)."""


def read(run):
    scopes = run.get("scopes")
    if not scopes or not scopes.get("busy_s") \
            or "lgb.goss_select" not in scopes:
        return None
    return 100.0 * scopes["lgb.goss_select"]["self_s"] / scopes["busy_s"]
