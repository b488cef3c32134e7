"""The least time the chip's memory needs to hand find-best the slots of
every leaf it evaluated in the window (``roofline_find_best.py``, from the
program's ``grow.find_slots`` counter) over the self time under
``lgb.find_best`` in the window's trace.  ``None`` when the program has
no such counter or the run no per-scope reduction."""

from benchmark import roofline, roofline_find_best


def read(run):
    scopes = run.get("scopes")
    slots = run["window_counters"].get("grow.find_slots")
    if not slots or not scopes or "lgb.find_best" not in scopes \
            or not scopes["lgb.find_best"].get("self_s"):
        return None
    least = roofline_find_best.least_seconds(
        slots, roofline.peaks_for(run["device_kind"]))
    return 100.0 * least / scopes["lgb.find_best"]["self_s"]
