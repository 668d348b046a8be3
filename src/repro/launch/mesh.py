"""Production mesh construction (function, not module constant — importing
this module never touches jax device state).

Single pod: (data=16, model=16) = 256 v5e chips.
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the 'pod' axis is
data-parallel (slow cross-pod links carry only gradient all-reduce, which
optim/compression.py can quantize).
"""
from __future__ import annotations

import jax

from repro.parallel import ParallelCtx

__all__ = ["make_mesh", "make_production_mesh", "make_parallel_ctx",
           "make_debug_mesh", "parallel_ctx_from_spec"]


def make_mesh(shape, axes):
    """jax.make_mesh with every axis Auto (GSPMD-propagated shardings, which
    the sharding rules in launch/sharding.py are written for).  Shared by
    launch and runtime mesh builders."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh for CI-grade machinery tests (8 fake devices)."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


def make_parallel_ctx(mesh, sp: bool = False) -> ParallelCtx:
    dp_axes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    return ParallelCtx(mesh=mesh, dp_axes=dp_axes, tp_axis="model", sp=sp)


def parallel_ctx_from_spec(spec: str) -> ParallelCtx:
    """CLI mesh spec -> ParallelCtx: 'model=4' or 'data=2,model=4'.

    The serving convention (launch/serve.py ``--mesh``): a ('data',
    'model') mesh with omitted axes defaulting to 1 — 'model=4' is a pure
    tensor-parallel serving mesh; needs data*model visible jax devices."""
    sizes = {"data": 1, "model": 1}
    for part in spec.split(","):
        axis, _, n = part.partition("=")
        if axis not in sizes or not n.isdigit() or int(n) < 1:
            raise ValueError(f"bad mesh spec {spec!r}; want e.g. 'model=4' "
                             "or 'data=2,model=4'")
        sizes[axis] = int(n)
    mesh = make_mesh((sizes["data"], sizes["model"]), ("data", "model"))
    return make_parallel_ctx(mesh)
