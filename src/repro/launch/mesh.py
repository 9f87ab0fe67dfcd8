"""Production mesh definitions (TPU v5e pods; host-device placeholders in
the dry-run).

Defined as FUNCTIONS so importing this module never touches jax device
state — the dry-run sets XLA_FLAGS before any jax import, everything else
sees the real (single-CPU) topology.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with Auto axes: the executors shard_map over the
    mesh and then reshape/slice the gathered result in plain jnp, which the
    (default) Explicit axis types reject."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def batch_axes(mesh) -> tuple[str, ...]:
    """Mesh axes the global batch shards over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def data_axis_size(mesh) -> int:
    n = mesh.shape["data"]
    if "pod" in mesh.axis_names:
        n *= mesh.shape["pod"]
    return n


def make_host_mesh():
    """Degenerate 1x1 mesh on the real local device (CPU smoke tests)."""
    return _mesh((1, 1), ("data", "model"))


def make_data_mesh(n_shards: int | None = None):
    """All (or ``n_shards``) local devices on the ``data`` axis — the shape
    the ``repro.api`` sharded backends consume: circuit-bank lanes shard
    over ``data``, so a multi-device host parallelizes by default while the
    single-CPU container degenerates to ``make_host_mesh()``."""
    n = n_shards or len(jax.devices())
    return _mesh((n, 1), ("data", "model"))
