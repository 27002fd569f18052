"""Plain policy-config dicts, in the form the program's policies describe
themselves (``Policy.describe()``), with the program's default knobs."""

THRESHOLDS = {"interval_eps_s": 1.0, "activity_threshold": 0.05,
              "comm_threshold_gbs": 1.0}


def noop() -> dict:
    return {"policy": "noop"}


def downscale(x: float, y: float, mode: str) -> dict:
    return {"policy": "downscale", "threshold_x_s": x, "cooldown_y_s": y,
            "mode": mode, "switch_latency_s": 0.2,
            "compute_bound_fraction": 0.7, **THRESHOLDS}


def parking(n_devices: int, n_active: int, resume_s: float) -> dict:
    return {"policy": "parking", "n_devices": n_devices,
            "n_active": n_active, "resume_latency_s": resume_s,
            **THRESHOLDS}


def powercap(frac: float) -> dict:
    return {"policy": "powercap", "cap_fraction": frac, **THRESHOLDS}
