"""Per cent of the traced window of a sweep cell in which the device
ran no operation. Moves ``configs_per_s``."""
from bench.readers import idle_share


def read(rec):
    return idle_share(rec)
