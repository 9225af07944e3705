"""Production mesh construction.

A function (NOT a module-level constant) so importing this module never
touches jax device state — the dry-run must set
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """jax.make_mesh with every axis Auto (GSPMD-partitioned), the mode the
    repo's sharding rules are written for."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod.

    Axes: "data" = DP/FSDP, "model" = TP/EP/SP. "pod" is a pure outer data
    axis (gradients cross pods once per step — DCN-friendly; all other
    collectives stay on intra-pod ICI).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — tests."""
    return make_mesh((data, model), ("data", "model"))


def make_host_smoke_mesh():
    """data×model mesh over ALL available host devices — the CI smoke
    topology shared by `launch.dryrun --mesh host` and `launch.serve
    --mesh host` (REPRO_DRYRUN_DEVICES / REPRO_SERVE_DEVICES set the
    placeholder device count before first jax init). Returns
    (mesh, data, model): model is the largest of 4/2/1 dividing the device
    count, so EP/TP shards exist whenever more than one device does."""
    n = jax.device_count()
    model = next(m for m in (4, 2, 1) if n % m == 0)
    return make_host_mesh(n // model, model), n // model, model
