"""Per cent of the traced window of the search cell in which the device
ran no operation. Moves ``search_s``."""
from bench.readers import idle_share


def read(rec):
    return idle_share(rec)
