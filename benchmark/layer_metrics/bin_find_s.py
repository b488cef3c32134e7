"""Host seconds finding bin boundaries and bundling features during
set-up: the program's ``bin.find`` + ``bin.bundle`` spans
(``data/dataset.py``), which reach the run as ``span_s.*`` counters.
``None`` when the program has no such spans (``span_n.dataset.construct``
moves in every set-up of a program that has them)."""


def read(run):
    c = run["setup_counters"]
    if "span_n.dataset.construct" not in c:
        return None
    return float(c.get("span_s.bin.find", 0.0)
                 + c.get("span_s.bin.bundle", 0.0))
