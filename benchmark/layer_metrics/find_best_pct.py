"""Share of the device's busy time spent choosing splits: self time under
``lgb.find_best`` over all self time, from the per-scope reduction of the
window's trace (``run["scopes"]``).  ``None`` when the run has no such
reduction or the trace never reaches the scope."""


def read(run):
    scopes = run.get("scopes")
    if not scopes or not scopes.get("busy_s") \
            or "lgb.find_best" not in scopes:
        return None
    return 100.0 * scopes["lgb.find_best"]["self_s"] / scopes["busy_s"]
