"""The replay inside a search (``whatif/sweep.py`` evaluate, every
round), per search: the summed ``whatif.evaluate`` spans. Moves
``search_s``."""
from bench.readers import per_call


def read(rec):
    return per_call(rec, "whatif.evaluate", "searches")
