"""The least time the chip's memory needs for the window's softmax
gradients (``roofline_softmax.py``: ``4K + 4 + 8K`` bytes a real row an
iteration, rows from the program's ``grow.softmax_rows`` counter) over
the self time under ``lgb.softmax_grad`` in the window's trace.  ``None``
when the program has no such counter or the run no per-scope
reduction."""

from benchmark import roofline, roofline_softmax


def read(run):
    scopes = run.get("scopes")
    rows = (run.get("window_counters") or {}).get("grow.softmax_rows")
    num_class = (run.get("shapes") or {}).get("num_class")
    if not rows or not num_class or not scopes \
            or "lgb.softmax_grad" not in scopes \
            or not scopes["lgb.softmax_grad"].get("self_s"):
        return None
    least = roofline_softmax.least_seconds(
        rows, num_class, roofline.peaks_for(run["device_kind"]))
    return 100.0 * least / scopes["lgb.softmax_grad"]["self_s"]
