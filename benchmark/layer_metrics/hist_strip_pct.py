"""Share of the wave contraction's strips that a chunk contracts: the
program's gauge ``grow.hist_strips`` (the strip x group products, a
group contracting the 64-bin strips its bins reach) over
``grow.hist_strips_full`` (groups x the 64-bin strips of the pitch, what
the full contraction takes).  ``None`` when the run hands over no gauges
or the program sets neither."""


def read(run):
    g = run.get("gauges") or {}
    if not g.get("grow.hist_strips_full") or g.get("grow.hist_strips") \
            is None:
        return None
    return 100.0 * g["grow.hist_strips"] / g["grow.hist_strips_full"]
