"""The least time the chip needs for the window's trees (``roofline.py``,
from the cell's shapes alone; bytes bound it on the v5e) over the device's
busy time in the traced window: the fused growth program as a whole."""

from benchmark import roofline


def read(run):
    tr = run.get("trace")
    if not tr or not tr.get("busy_s"):
        return None
    sh = run["shapes"]
    least = roofline.least_seconds(
        sh["rows"], sh["features"], sh["num_leaves"], run["window"]["trees"],
        roofline.peaks_for(run["device_kind"]))
    return 100.0 * least["seconds"] / tr["busy_s"]
