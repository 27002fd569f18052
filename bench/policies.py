"""Policy configs as plain data.

A grid or a search result names each config by the ``params`` dict the
program's policies describe themselves by. The reference reads these dicts
as they are; :func:`build` turns one into the program's policy object.
"""
from __future__ import annotations


def build(spec: dict):
    from repro.core.controller import ControllerConfig, DownscaleMode
    from repro.core.imbalance import PoolConfig, PoolPolicy
    from repro.whatif import (CompositePolicy, DownscalePolicy, NoOpPolicy,
                              ParkingPolicy, PowerCapPolicy)

    kind = spec["policy"]
    if kind == "composite":
        return CompositePolicy(tuple(build(p) for p in spec["parts"]))
    if kind == "noop":
        return NoOpPolicy()
    thresholds = {"activity_threshold": spec["activity_threshold"],
                  "comm_threshold_gbs": spec["comm_threshold_gbs"],
                  "interval_eps_s": spec["interval_eps_s"]}
    if kind == "downscale":
        return DownscalePolicy(
            config=ControllerConfig(
                threshold_x_s=spec["threshold_x_s"],
                cooldown_y_s=spec["cooldown_y_s"],
                mode=DownscaleMode(spec["mode"]), **thresholds),
            switch_latency_s=spec["switch_latency_s"],
            compute_bound_fraction=spec["compute_bound_fraction"])
    if kind == "parking":
        return ParkingPolicy(
            pool=PoolConfig(n_devices=spec["n_devices"],
                            policy=PoolPolicy.CONSOLIDATED,
                            n_active=spec["n_active"]),
            resume_latency_s=spec["resume_latency_s"],
            config=ControllerConfig(**thresholds))
    if kind == "powercap":
        return PowerCapPolicy(cap_fraction=spec["cap_fraction"],
                              config=ControllerConfig(**thresholds))
    raise ValueError(f"unknown policy {kind!r}")
