"""Production mesh builders (assignment-mandated shapes).

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    """Every mesh this program builds has Auto axes: the model code pins
    activations with ``with_sharding_constraint``, which refuses the
    Explicit axes that bare ``jax.make_mesh`` gives."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod (256 chips) or 2×16×16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1):
    """Small mesh over whatever devices exist (tests / CPU examples)."""
    n = len(jax.devices())
    assert n % model_axis == 0
    return _make_mesh((n // model_axis, model_axis), ("data", "model"))


def abstract_mesh(axis_sizes, axis_names):
    """Device-free ``jax.sharding.AbstractMesh`` — safe for sharding-rule
    tests and dry-run planning on any host."""
    return jax.sharding.AbstractMesh(tuple(axis_sizes), tuple(axis_names))
