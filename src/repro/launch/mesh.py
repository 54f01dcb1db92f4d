"""Production mesh definitions (TPU v5e pods; host-platform stand-ins here).

Defined as FUNCTIONS so importing this module never touches jax device
state — ``dryrun.py`` must set ``XLA_FLAGS`` before anything initializes
the backend.

Axis semantics (see repro.distribution.sharding):
  single-pod : (16, 16)      -> ("data", "model")        = 256 chips
  multi-pod  : (2, 16, 16)   -> ("pod", "data", "model") = 512 chips

In the federated mapping, the ``pod`` axis is the hospital-silo axis:
FedAvg aggregation across silos is an all-reduce over ``pod``.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with Auto axis types (sharding by annotation)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1-device mesh for CPU smoke tests of the sharded paths."""
    return make_mesh((1, 1), ("data", "model"))


def make_data_mesh(num_devices: int | None = None):
    """1-D ``("data",)`` mesh over the local devices — the federated client
    axis.  On CPU, force multiple devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before* jax
    initializes (CI's multi-device matrix leg does exactly this)."""
    return make_mesh((num_devices or jax.device_count(),), ("data",))


def data_axes(mesh) -> tuple[str, ...]:
    """Axes that carry the batch: ('pod','data') on multi-pod, ('data',) else."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def axis_size(mesh, name: str) -> int:
    if name not in mesh.axis_names:
        return 1
    return mesh.shape[name]
