"""Seconds of set-up moving the binned matrix to the device and
transposing it there: the program's ``grow.upload`` span
(``DeviceGrower._upload_binned``, ended by ``block_until_ready``).
``None`` when the program has no such span."""


def read(run):
    c = run["setup_counters"]
    if "span_n.dataset.construct" not in c:
        return None
    return float(c.get("span_s.grow.upload", 0.0))
