"""Device-to-host copies of the device programs' outputs
(``backend.fetch`` spans, ``whatif/backend.py``), seconds per search.
Moves ``search_s``."""
from bench.readers import per_call


def read(rec):
    return per_call(rec, "backend.fetch", "searches")
