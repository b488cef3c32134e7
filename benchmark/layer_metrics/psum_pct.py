"""Share of the device's busy time spent in the mesh's collectives: self
time under ``lgb.psum`` (the wave histograms' all-reduce, the fullest
shard's ``pmax`` beside it, the per-tree sums) over all self time, from
the per-scope reduction of the window's trace (``run["scopes"]``, a mean
over the device planes).  A chip that waits at a collective for a fuller
shard spends the wait here.  ``None`` when the run has no such reduction
or the trace never reaches the scope (one chip: nothing to reduce over)."""


def read(run):
    scopes = run.get("scopes")
    if not scopes or not scopes.get("busy_s") \
            or "lgb.psum" not in scopes:
        return None
    return 100.0 * scopes["lgb.psum"]["self_s"] / scopes["busy_s"]
