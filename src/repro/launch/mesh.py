"""Mesh construction for the model steps and the sharded sweep.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state.
"""
from __future__ import annotations

import jax


def make_test_mesh(dp: int = 1, tp: int = 1):
    """Small mesh over however many devices the test environment has.

    Its axes are Auto: the model steps shard by ``with_sharding_constraint``,
    which asserts under the Explicit axes ``jax.make_mesh`` defaults to."""
    auto = jax.sharding.AxisType.Auto
    return jax.make_mesh((dp, tp), ("data", "model"), axis_types=(auto, auto))


def make_sweep_mesh(n_params: int, n_channels: int, devices=None):
    """("params", "channel") mesh for the sharded sweep orchestrator.

    Axis sizes are the largest divisors of the batch extents that fit the
    available device count, so every shard divides evenly — no padding, and
    sharding stays a pure placement decision (bitwise-invariant, DESIGN.md
    §14).  A single-device environment degrades to a (1, 1) mesh, which is
    exactly the unsharded computation.
    """
    import numpy as np
    from jax.sharding import Mesh
    devs = list(jax.devices()) if devices is None else list(devices)

    def best_divisor(n: int, cap: int) -> int:
        for d in range(min(n, cap), 0, -1):
            if n % d == 0:
                return d
        return 1

    p = best_divisor(max(n_params, 1), len(devs))
    c = best_divisor(max(n_channels, 1), len(devs) // p)
    return Mesh(np.array(devs[:p * c]).reshape(p, c), ("params", "channel"))


def mesh_axes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def dp_axes(mesh):
    """Axes used for data parallelism (batch + ZeRO)."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
