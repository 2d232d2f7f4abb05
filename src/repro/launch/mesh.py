"""Production mesh builders (functions, never module-level constants, so
importing this module never touches jax device state). Axes are
``AxisType.Auto``: sharding is placed by ``NamedSharding`` and left to
jit's propagation, as in ``repro.sharding``."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def production_mesh_shape(*, multi_pod: bool = False) -> tuple:
    """TPU v5e: 16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    return (2, 16, 16) if multi_pod else (16, 16)


def production_chip_count(*, multi_pod: bool = False) -> int:
    n = 1
    for v in production_mesh_shape(multi_pod=multi_pod):
        n *= v
    return n


def make_production_mesh(*, multi_pod: bool = False):
    shape = production_mesh_shape(multi_pod=multi_pod)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes))


def make_host_mesh(model_axis: int = 1):
    """Degenerate mesh over however many real devices exist (CPU tests)."""
    n = len(jax.devices())
    assert n % model_axis == 0
    return jax.make_mesh((n // model_axis, model_axis), ("data", "model"),
                         (AxisType.Auto,) * 2)


def mesh_chips(mesh) -> int:
    n = 1
    for v in mesh.shape.values():
        n *= v
    return n


def mesh_label(mesh) -> str:
    return "x".join(str(v) for v in mesh.shape.values())
