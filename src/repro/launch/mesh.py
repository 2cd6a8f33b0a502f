"""Production mesh construction (task brief, MULTI-POD DRY-RUN §1).

A FUNCTION, not a module-level constant: importing this module never touches
JAX device state, so tests/benches see one CPU device unless dryrun.py set
XLA_FLAGS first.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # Auto axes: the program places arrays with NamedSharding and
    # with_sharding_constraint and lets the partitioner propagate the rest
    # (jax.make_mesh's default, Explicit, would type every array's sharding)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(dp: int = 1, tp: int = 1):
    """Mesh over the first ``dp * tp`` local devices."""
    return _auto_mesh((dp, tp), ("data", "model"))


def data_axes(mesh) -> tuple[str, ...]:
    """The data-parallel axes of a mesh (pod folds into DP when present)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh) -> str | None:
    return "model" if "model" in mesh.axis_names else None
