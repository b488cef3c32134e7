"""Host seconds mapping every value to its bin during set-up: the
program's ``bin.apply`` span (``BinnedDataset._build_group_matrix``).
``None`` when the program has no such span."""


def read(run):
    c = run["setup_counters"]
    if "span_n.dataset.construct" not in c:
        return None
    return float(c.get("span_s.bin.apply", 0.0))
