"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state — the dry-run sets
XLA_FLAGS for 512 host devices before any jax import; tests and benches
see the 1 real CPU device.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """A mesh whose axes are ``Auto``: the sharding rules here annotate
    parameters and constrain activations, and leave the rest (gathers,
    the loss) to the partitioner.  ``jax.make_mesh`` now defaults to
    ``Explicit`` axes, under which an embedding gather from a sharded
    table has no unambiguous output sharding."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e production mesh: one pod = (data=16, model=16) = 256 chips;
    multi-pod adds a leading pod axis (2, 16, 16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1-device mesh for CPU tests/examples."""
    return _auto_mesh((1, 1), ("data", "model"))
