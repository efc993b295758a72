"""Production meshes.

Single pod: 16×16 = 256 chips, axes (data, model).
Multi-pod:  2×16×16 = 512 chips, axes (pod, data, model) — the `pod` axis
is outer data parallelism by default and the pipeline-stage axis for the
B&B pipeline runtime.

A function, not a module constant: importing this module must never touch
jax device state (device count is locked at first jax init).
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def _auto_mesh(shape, axes):
    """A mesh whose axes are all ``Auto``: the compiler places the
    operations between the annotated shardings, as the model code
    assumes.  ``jax.make_mesh`` defaults to ``Explicit`` axes, under
    which an embedding gather on a sharded table has no output
    sharding."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_debug_mesh(n_devices: int | None = None):
    """Tiny mesh over whatever devices exist (tests on CPU)."""
    n = n_devices or len(jax.devices())
    model = 2 if n % 2 == 0 and n > 1 else 1
    return _auto_mesh((n // model, model), ("data", "model"))
