"""Rows x features over the host seconds around
``lgb.Dataset(...).construct()``, in millions of values a second."""


def read(run):
    s = run["seconds"].get("bin_s")
    if not s:
        return None
    sh = run["shapes"]
    return sh["rows"] * sh["features"] / s / 1e6
