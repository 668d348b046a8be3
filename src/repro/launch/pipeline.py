"""Optional pipeline parallelism over the 'pod' axis (GPipe schedule).

The default multi-pod deployment is pod-DP (DESIGN.md §5); this module
provides the PP alternative for regimes where cross-pod gradient all-reduce
dominates (very large models / many pods): each pod owns a contiguous stage
of layers, microbatches stream through `ppermute` handoffs, and the bubble
fraction is (P-1)/(P-1+M).

Implementation: `shard_map` over ('pod',); within a pod, the stage body is
the ordinary pjit-style layer stack (TP/FSDP inside the stage would nest via
the remaining mesh axes — demonstrated here with the stage body running on
the pod's full device slice).  `pp_dryrun` compiles a 2-stage pipeline for
an arch to prove the schedule lowers on the production mesh.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["pipeline_forward", "pp_dryrun"]


def pipeline_forward(stage_params, x_mb, *, stage_fn, mesh,
                     axis: str = "pod"):
    """GPipe forward over `axis`.

    stage_params: pytree stacked over stages on dim 0 — stage i's slice
    lives on pod i (sharded over `axis`).
    x_mb: (M, mb, S, D) microbatches (replicated across pods at entry).
    stage_fn(params_slice, x) -> x.
    Returns final-stage activations (M, mb, S, D) (valid on the last pod).
    """
    n_stage = mesh.shape[axis]

    def body(params_sl, xs):
        # params_sl: this pod's stage slice (leading stage dim of size 1)
        params_sl = jax.tree.map(lambda a: a[0], params_sl)
        rank = jax.lax.axis_index(axis)
        M = xs.shape[0]
        n_clock = M + n_stage - 1

        def clock(carry, t):
            buf = carry            # (mb, S, D): activation entering this pod
            # stage 0 injects microbatch t; others consume the handoff
            mb_idx = jnp.clip(t - rank, 0, M - 1)
            inject = jax.lax.dynamic_index_in_dim(xs, mb_idx, 0,
                                                  keepdims=False)
            x_in = jnp.where(rank == 0, inject, buf)
            y = stage_fn(params_sl, x_in)
            # hand off to the next stage (ring; last->0 wraps, ignored)
            nxt = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stage) for i in range(n_stage)])
            # collect the finished microbatch on the last stage
            done_idx = t - (n_stage - 1)
            out = jnp.where((rank == n_stage - 1) & (done_idx >= 0), y, 0.0)
            return nxt, (out, done_idx)

        _, (outs, idxs) = jax.lax.scan(
            clock, jnp.zeros_like(xs[0]), jnp.arange(n_clock))
        # scatter outs back into microbatch order
        result = jnp.zeros_like(xs)
        valid = idxs >= 0
        result = result.at[jnp.clip(idxs, 0, M - 1)].add(
            jnp.where(valid[:, None, None, None], outs, 0.0))
        return result

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis), stage_params,
                               is_leaf=lambda x: False), P()),
        out_specs=P(), check_vma=False,
    )(stage_params, x_mb)


def pp_dryrun(d_model: int = 1024, d_ff: int = 4096, layers_per_stage: int = 4,
              microbatches: int = 8, mb_size: int = 2, seq: int = 512):
    """Compile the 2-stage pipeline on the multi-pod mesh; returns record."""
    from repro.launch.mesh import make_production_mesh
    mesh = make_production_mesh(multi_pod=True)

    def stage_fn(params, x):
        def layer(h, w):
            return jax.nn.gelu(h @ w[0]) @ w[1], None
        h, _ = jax.lax.scan(layer, x, params)
        return h

    p_struct = (jax.ShapeDtypeStruct(
        (2, layers_per_stage, d_model, d_ff), jnp.bfloat16),
        jax.ShapeDtypeStruct(
        (2, layers_per_stage, d_ff, d_model), jnp.bfloat16))
    x_struct = jax.ShapeDtypeStruct((microbatches, mb_size, seq, d_model),
                                    jnp.bfloat16)

    def stage_fn_pair(p, h):
        w1, w2 = p

        def layer(hh, ws):
            a, b = ws
            return jax.nn.gelu(hh @ a) @ b, None
        hh, _ = jax.lax.scan(layer, h, (w1, w2))
        return hh

    def run(w1, w2, x):
        return pipeline_forward((w1, w2), x, stage_fn=stage_fn_pair,
                                mesh=mesh)

    lowered = jax.jit(run).lower(*p_struct, x_struct)
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    return {
        "ok": True,
        "stages": 2,
        "microbatches": microbatches,
        "bubble_fraction": (2 - 1) / (2 - 1 + microbatches),
        "temp_bytes": int(ma.temp_size_in_bytes) if ma else None,
        "collective_permutes": compiled.as_text().count("collective-permute"),
    }
