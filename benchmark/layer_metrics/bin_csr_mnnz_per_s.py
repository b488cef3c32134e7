"""Recorded CSR entries binned a second during set-up, in millions:
the program's ``bin.csr_nnz`` counter over its ``bin.apply`` span.
``None`` when the program counts no such entries."""


def read(run):
    c = run["setup_counters"]
    if not c.get("bin.csr_nnz") or not c.get("span_s.bin.apply"):
        return None
    return c["bin.csr_nnz"] / c["span_s.bin.apply"] / 1e6
