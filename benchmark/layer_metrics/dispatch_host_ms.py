"""Host time per dispatch that the device did not cover: the harness's
spans around ``update_chunked`` and ``block_until_ready`` minus the device
busy time inside them, mean over the window's dispatches."""


def read(run):
    tr = run.get("trace")
    if not tr or tr.get("busy_s") is None:
        return None
    spans = [s for s in tr["spans"]
             if s["name"] in ("dispatch", "block_until_ready")]
    n = sum(1 for s in spans if s["name"] == "dispatch")
    if not n or any(s["busy_s"] is None for s in spans):
        return None
    uncovered = sum(s["dur_s"] - s["busy_s"] for s in spans)
    return 1000.0 * uncovered / n
