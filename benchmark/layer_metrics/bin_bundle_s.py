"""Seconds of set-up spent deciding which columns share a group
(exclusive feature bundling): the program's ``bin.bundle`` span.
``None`` when the program has no such span."""


def read(run):
    return run["setup_counters"].get("span_s.bin.bundle")
