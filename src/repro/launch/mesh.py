"""Production meshes. Functions only — importing never touches jax device
state (the dry-run sets XLA_FLAGS before first jax init)."""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_host_mesh", "mesh_by_name",
           "use_mesh"]


_AUTO = jax.sharding.AxisType.Auto


def use_mesh(mesh):
    """Context manager installing `mesh` as the ambient mesh."""
    return jax.set_mesh(mesh)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(_AUTO,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over local devices (CPU tests / smoke runs)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(_AUTO, _AUTO))


def mesh_by_name(name: str):
    if name == "pod":
        return make_production_mesh(multi_pod=False)
    if name == "multipod":
        return make_production_mesh(multi_pod=True)
    if name == "host":
        return make_host_mesh()
    raise KeyError(name)
