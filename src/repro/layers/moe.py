"""Mixture-of-Experts with production expert parallelism.

Dispatch is *sort-based with fixed capacity* (the Megatron/MaxText dropping
implementation), not the GShard one-hot einsum — at 1M-token batches the
(tokens, E, capacity) dispatch tensor would be ~16 GB/device, while the
sort-based path is O(tokens·k) index arithmetic plus two `all_to_all`s.

Topology: inside the pjit'd layer, activations are replicated over the
'model' axis; the MoE block (a) splits the sequence across 'model' (so each
EP rank routes a distinct token slice), (b) scatters tokens into per-expert
capacity buffers, (c) `all_to_all`s them to the expert owners, (d) runs the
expert FFNs (experts are sharded over 'model'), (e) `all_to_all`s back and
combines, (f) `all_gather`s the sequence slices.  DeepSeek-MoE style shared
experts run densely on every token.

When ``ep_axis is None`` (single-device smoke tests) the same code runs with
ep=1 and no collectives.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .mlp import init_mlp, mlp

__all__ = ["init_moe", "moe", "moe_local"]


def init_moe(key, d_model: int, d_ff: int, n_experts: int, top_k: int,
             n_shared: int = 0, dtype=jnp.float32):
    kr, ke, ks = jax.random.split(key, 3)
    s_in = d_model ** -0.5
    s_out = d_ff ** -0.5
    p = {
        "router": jax.random.normal(kr, (d_model, n_experts), dtype) * s_in,
        "experts": {
            "w_gate": jax.random.normal(jax.random.fold_in(ke, 0),
                                        (n_experts, d_model, d_ff), dtype) * s_in,
            "w_up": jax.random.normal(jax.random.fold_in(ke, 1),
                                      (n_experts, d_model, d_ff), dtype) * s_in,
            "w_down": jax.random.normal(jax.random.fold_in(ke, 2),
                                        (n_experts, d_ff, d_model), dtype) * s_out,
        },
    }
    if n_shared:
        p["shared"] = init_mlp(ks, d_model, n_shared * d_ff, "swiglu", dtype)
    return p


def _expert_ffn(we, x):
    """x (E_loc, C', D) through per-expert SwiGLU FFNs."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x, we["w_gate"])) \
        * jnp.einsum("ecd,edf->ecf", x, we["w_up"])
    return jnp.einsum("ecf,efd->ecd", h, we["w_down"])


def _route(xs, router_w, top_k: int):
    """xs (T, D) -> (ids (T,k) int32, weights (T,k) f32, aux loss scalar)."""
    logits = (xs.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, ids = jax.lax.top_k(probs, top_k)
    weights = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance aux: E * mean(frac_tokens_e * mean_prob_e)
    E = logits.shape[-1]
    frac = jnp.mean(jax.nn.one_hot(ids[:, 0], E), axis=0)
    aux = E * jnp.sum(frac * jnp.mean(probs, axis=0))
    return ids, weights, aux


def moe(params, x, *, top_k: int, capacity_factor: float = 1.25,
        ep_axis: str | None = None, has_shared: bool = False,
        linear=None, salt=None):
    """x (B, S, D) -> (out (B, S, D), aux).  See module docstring.

    ``linear``/``salt``: optional DS-CIM operator for the *shared* expert's
    dense matmuls (it runs on every token — same hot-path class as the MLP
    block); the routed experts stay on the exact einsum path."""
    B, S, D = x.shape
    E = params["router"].shape[-1]
    ep = jax.lax.axis_size(ep_axis) if ep_axis else 1
    assert E % ep == 0, (E, ep)

    split_seq = bool(ep_axis) and ep > 1 and S % ep == 0
    if split_seq:
        rank = jax.lax.axis_index(ep_axis)
        S_loc = S // ep
        xs = jax.lax.dynamic_slice_in_dim(x, rank * S_loc, S_loc, axis=1)
    else:
        # decode-style tiny S: every EP rank routes the same tokens; the
        # all_to_all still delivers each expert's buffer to its owner and
        # every rank reconstructs identical outputs (no gather needed).
        S_loc = S
        xs = x
    xt = xs.reshape(B * S_loc, D)
    T = B * S_loc

    ids, weights, aux = _route(xt, params["router"], top_k)

    # ---- sort-based capacity dispatch ----
    C = max(int(T * top_k / E * capacity_factor), top_k)
    flat_ids = ids.reshape(-1)                             # (T*k,)
    order = jnp.argsort(flat_ids)                          # stable
    sorted_ids = flat_ids[order]
    ones = jnp.ones_like(sorted_ids)
    # position within expert among the sorted sequence
    seg_pos = jnp.cumsum(ones) - 1
    starts = jnp.searchsorted(sorted_ids, jnp.arange(E), side="left")
    pos_in_e = seg_pos - starts[sorted_ids]
    keep = pos_in_e < C                                    # dropped beyond cap
    slot = jnp.where(keep, sorted_ids * C + pos_in_e, E * C)
    tok_idx = order // top_k
    buf = jnp.zeros((E * C + 1, D), xt.dtype).at[slot].set(
        xt[tok_idx], mode="drop")
    buf = buf[:-1].reshape(E, C, D)

    # ---- EP all_to_all: experts to owners ----
    if ep_axis and ep > 1:
        buf = jax.lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=1,
                                 tiled=True)               # (E_loc, ep*C, D)
    out_buf = _expert_ffn(params["experts"], buf)
    if ep_axis and ep > 1:
        out_buf = jax.lax.all_to_all(out_buf, ep_axis, split_axis=1,
                                     concat_axis=0, tiled=True)  # (E, C, D)

    # ---- combine ----
    flat_out = out_buf.reshape(E * C, D)
    gathered = jnp.where(keep[:, None],
                         flat_out[jnp.clip(slot, 0, E * C - 1)], 0.0)
    contrib = gathered * weights.reshape(-1)[order][:, None]
    out_t = jnp.zeros_like(xt).at[tok_idx].add(contrib)

    if has_shared:
        out_t = out_t + mlp(params["shared"], xt, "swiglu", linear=linear,
                            salt=salt)
    out = out_t.reshape(B, S_loc, D)

    if split_seq:
        out = jax.lax.all_gather(out, ep_axis, axis=1, tiled=True)  # (B,S,D)
    return out, aux


def moe_local(params, x, *, top_k: int, capacity_factor: float = 2.0,
              has_shared: bool = False, linear=None, salt=None):
    """Single-device convenience (smoke tests + single-device serving —
    the path that accepts prepared shared-expert weights)."""
    return moe(params, x, top_k=top_k, capacity_factor=capacity_factor,
               ep_axis=None, has_shared=has_shared, linear=linear, salt=salt)
