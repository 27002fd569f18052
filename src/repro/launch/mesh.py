"""Production mesh construction (single-pod 16x16 and multi-pod 2x16x16).

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state. The dry-run entry point sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 BEFORE importing jax;
normal runs (tests, benches) see the container's single CPU device.
"""
from __future__ import annotations

from repro.distributed.compat import make_mesh
from repro.distributed.context import DistContext


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_dist(*, multi_pod: bool = False) -> DistContext:
    mesh = make_production_mesh(multi_pod=multi_pod)
    batch_axes = ("pod", "data") if multi_pod else ("data",)
    return DistContext(mesh=mesh, batch_axes=batch_axes, model_axis="model")


def make_local_dist(data: int = 1, model: int = 1) -> DistContext:
    """Small mesh over however many (host) devices exist — used by tests."""
    if data * model == 1:
        return DistContext()
    mesh = make_mesh((data, model), ("data", "model"))
    return DistContext(mesh=mesh, batch_axes=("data",), model_axis="model")
