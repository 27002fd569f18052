"""Megabytes (10^6 bytes) the program put on the chips per sweep, once per
chip copy: the window's ``repro_backend_placed_bytes_total``
(``whatif/backend.py`` ``_place``) over its sweeps. With the packed tables
resident on every chip this is the config-axis arrays alone. Moves
``configs_per_s``."""


def read(rec):
    n = rec["counts"].get("sweeps", 0)
    placed = rec["counts"].get("placed_bytes")
    if not n or placed is None:
        return None
    return placed / 1e6 / n
