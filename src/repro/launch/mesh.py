"""Production mesh factory.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state. The dry-run entry point sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import so 512 placeholder host devices exist; everything else (tests,
benches, examples) sees the real single CPU device.
"""

from __future__ import annotations

import jax


def _auto(n):
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_local_mesh(model_parallel: int = 1):
    """Mesh over whatever devices actually exist (tests / examples)."""
    n = jax.device_count()
    assert n % model_parallel == 0
    return jax.make_mesh((n // model_parallel, model_parallel),
                         ("data", "model"), axis_types=_auto(2))
