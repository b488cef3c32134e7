"""The least time the chip's memory needs to select the window's GOSS rows
(``roofline_goss.py``: 12 bytes a keyed row, from the program's
``grow.goss_keys`` counter) over the self time under ``lgb.goss_select``
in the window's trace.  ``None`` when the program has no such counter or
the run no per-scope reduction."""

from benchmark import roofline, roofline_goss


def read(run):
    scopes = run.get("scopes")
    keys = (run.get("window_counters") or {}).get("grow.goss_keys")
    if not keys or not scopes or "lgb.goss_select" not in scopes \
            or not scopes["lgb.goss_select"].get("self_s"):
        return None
    least = roofline_goss.least_seconds(
        keys, roofline.peaks_for(run["device_kind"]))
    return 100.0 * least / scopes["lgb.goss_select"]["self_s"]
