"""Config lanes launched beyond the real count per sweep: the window's
``repro_backend_pad_configs_total`` (``whatif/backend.py``
``_config_pad``: a power of two, then a multiple of the mesh's chips)
over its sweeps. Moves ``configs_per_s``."""


def read(rec):
    n = rec["counts"].get("sweeps", 0)
    lanes = rec["counts"].get("pad_configs")
    if not n or lanes is None:
        return None
    return lanes / n
