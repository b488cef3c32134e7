"""Each judged number beside its limit."""

from __future__ import annotations


def compare(readings: dict, limits: dict) -> dict:
    """``{name: {"value", "limit", "sense", "ok"}}`` for every number the
    limits name.  A limit names its sense, ``{"max": x}`` or ``{"min": x}``;
    a number the run did not read fails."""
    out = {}
    for name, lim in limits.items():
        (sense, bound), = lim.items()
        value = readings.get(name)
        if sense not in ("max", "min"):
            raise ValueError(f"limit of {name}: {lim}")
        ok = value is not None and (value <= bound if sense == "max"
                                    else value >= bound)
        out[name] = {"value": value, "limit": bound, "sense": sense,
                     "ok": bool(ok)}
    return out
