"""Share of the device's busy time spent in the categorical half of
find-best (the sorted-subset and one-hot scans): self time under
``lgb.find_best_cat`` over all self time, from the per-scope reduction of
the window's trace (``run["scopes"]``).  ``None`` when the run has no
such reduction or the trace never reaches the scope (a program without
categorical features, or without the name)."""


def read(run):
    scopes = run.get("scopes")
    if not scopes or not scopes.get("busy_s") \
            or "lgb.find_best_cat" not in scopes:
        return None
    return 100.0 * scopes["lgb.find_best_cat"]["self_s"] / scopes["busy_s"]
