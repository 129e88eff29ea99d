"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state.  Shapes: 16x16 = one v5e pod (256 chips);
(2,16,16) = two pods, 512 chips — the ``pod`` axis is pure data
parallelism (weights replicated per pod, gradients all-reduced across
pods), which is the elastic unit for 1000+-node deployments.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    devs = jax.devices()
    assert len(devs) >= need, (
        f"need {need} devices, have {len(devs)} — the dry-run entrypoint "
        "must set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
        "before any jax import")
    # the model places activations with with_sharding_constraint, which
    # needs Auto axes; jax.make_mesh defaults to Explicit ones
    return jax.make_mesh(shape, axes, devices=devs[:need],
                         axis_types=(AxisType.Auto,) * len(axes))


def batch_axes(mesh) -> tuple:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
