"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — required because the
dry-run must set XLA_FLAGS before any jax initialization.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.config import MeshConfig


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the partitioner places what the
    program leaves unconstrained, and ``with_sharding_constraint`` accepts
    the mesh (Explicit axes, ``make_mesh``'s default, refuse it)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """The assignment's production mesh: one pod = (16, 16) chips over
    ("data", "model"); two pods add an outer "pod" axis -> (2, 16, 16)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(cfg: MeshConfig):
    """Generic mesh from a MeshConfig (small meshes for tests)."""
    return _auto_mesh(cfg.shape, cfg.axis_names)


def make_node_mesh(n_devices: int | None = None):
    """1-D mesh over the ``"node"`` axis for the VM fleet runtime.

    The fleet shards the leading node axis of its stacked ``VMState`` over
    this mesh (``sharding.rules.make_fleet_rules``); thousand-node sensor
    networks then span every local device.  Defaults to all devices: on a
    four-chip v5e host that is a 4-way node axis."""
    n = n_devices or len(jax.devices())
    return _auto_mesh((n,), ("node",))
