"""The histogram all-reduce's achieved rate: the bytes one chip hands the
wave histograms' psums over the window (``grow.psum_bytes``, counted by
the program from its stage plan's shapes) over the self seconds under
``lgb.psum`` in the window's trace (``run["scopes"]``, a mean over the
device planes), in GB/s.  The seconds hold every collective of the scope
and any wait for a fuller shard, so this is what the program gets, not
what the links can do.  ``None`` without the counter or the scope."""


def read(run):
    scopes = run.get("scopes")
    sent = run["window_counters"].get("grow.psum_bytes")
    if not scopes or not sent or "lgb.psum" not in scopes \
            or not scopes["lgb.psum"]["self_s"]:
        return None
    return sent / scopes["lgb.psum"]["self_s"] / 1e9
