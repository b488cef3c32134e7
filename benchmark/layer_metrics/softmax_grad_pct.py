"""Share of the device's busy time spent on the softmax gradient: self
time under ``lgb.softmax_grad`` (each iteration's normaliser and each
class tree's g and h) over all self time, from the per-scope reduction of
the window's trace (``run["scopes"]``).  ``None`` when the run has no
such reduction or the trace never reaches the scope (a program without
the name)."""


def read(run):
    scopes = run.get("scopes")
    if not scopes or not scopes.get("busy_s") \
            or "lgb.softmax_grad" not in scopes:
        return None
    return 100.0 * scopes["lgb.softmax_grad"]["self_s"] / scopes["busy_s"]
