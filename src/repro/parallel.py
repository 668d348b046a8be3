"""Parallelism context threaded through model code.

Carries the mesh + axis-name conventions so layers can issue explicit
collectives (MoE all_to_all, FSDP all-gathers) where GSPMD propagation is
not the right tool.  ``None`` everywhere means single-device (smoke tests).
"""
from __future__ import annotations

import dataclasses

import jax

__all__ = ["ParallelCtx"]


@dataclasses.dataclass(frozen=True)
class ParallelCtx:
    mesh: jax.sharding.Mesh
    dp_axes: tuple = ("data",)   # ('pod','data') on the multi-pod mesh
    tp_axis: str = "model"
    sp: bool = False             # sequence-parallel residual stream (opt-in)

    @property
    def batch_spec(self):
        from jax.sharding import PartitionSpec as P
        return P(self.dp_axes)

    @property
    def dp_size(self) -> int:
        return int(
            __import__("numpy").prod([self.mesh.shape[a] for a in self.dp_axes]))

    @property
    def tp_size(self) -> int:
        return self.mesh.shape[self.tp_axis]
