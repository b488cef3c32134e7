"""Host seconds of the ``lgb.train`` first chunk: init, stage-plan probes
on a cold cache, tracing of ``ops/grow.py``, compile or cache load, and
the chunk's trees."""


def read(run):
    return run["seconds"].get("first_dispatch_s")
