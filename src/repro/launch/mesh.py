"""Production mesh definition (deliverable e).

Single pod: (16, 16) = ("data", "model") — 256 v5e chips.
Multi-pod:  (2, 16, 16) = ("pod", "data", "model") — 512 chips; the
"pod" axis is pure data parallelism across pods (weights replicated
over it, gradients and the paper's (U, V) merge all-reduced over it).

Every mesh here has ``Auto`` axis types: the sharded fleet code places
arrays with ``NamedSharding`` and leaves propagation to the compiler,
which ``jax.make_mesh``'s default ``Explicit`` axes refuse (a gather on
a sharded stack raises ``ShardingTypeError``).

Defined as FUNCTIONS so importing this module never touches jax device
state (jax locks the device count on first backend init — dryrun.py
must set XLA_FLAGS before any jax usage).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with ``Auto`` axis types on ``devices`` (default:
    all of this process's devices)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(n_data: int | None = None, n_model: int = 1):
    """Small mesh over however many host devices exist (tests/examples)."""
    n = len(jax.devices())
    n_data = n_data or (n // n_model)
    return make_mesh((n_data, n_model), ("data", "model"))


def data_axes(mesh) -> tuple[str, ...]:
    """The federation/batch axes: ("pod","data") on multi-pod meshes."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
