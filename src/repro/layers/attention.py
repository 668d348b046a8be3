"""GQA attention with flash-style chunked softmax (pure JAX) + KV-cache decode.

Production posture: the prefill/train path never materializes (S, S) scores;
it scans q-chunks and kv-chunks with an online-softmax accumulator (running
max / running sum), so activation memory is O(S * chunk) — this is what makes
the 32k-prefill cells compile with sane per-device memory.  Causality is
enforced by masking (the masked-out upper-triangle blocks still burn MXU
FLOPs in the baseline; EXPERIMENTS.md §Perf hillclimbs this).

DS-CIM scope: the q/k/v/o projections stay on the exact path by default
(DESIGN.md §6 — the MLP matmuls and LM head dominate).  A ``linear``
operator (DSCIMLinear) can be passed to route the projections through the
macro too — the opt-in '<mode>+attn' dscim spec (models/lm.py) — in which
case the projection weights may also be prepared ``QuantizedLinearWeight``
pytrees (core/qweights.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.qweights import QuantizedLinearWeight

from .norms import qk_norm
from .rope import apply_rope, rope_angles

__all__ = ["init_attention", "attention", "decode_attention",
           "decode_attention_multi", "decode_attention_paged",
           "decode_attention_paged_multi", "AttnParams"]

NEG_INF = -1e30


def _mm(x, w, linear, salt):
    """Projection matmul: exact by default, DS-CIM when ``linear`` given."""
    if linear is None:
        if isinstance(w, QuantizedLinearWeight):
            raise TypeError("prepared attention weights need a DS-CIM "
                            "`linear` operator (the '+attn' dscim mode)")
        return x @ w
    return linear(x, w, salt=salt).astype(x.dtype)


def init_attention(key, d_model: int, n_heads: int, n_kv: int, head_dim: int,
                   qk_norm_flag: bool = False, dtype=jnp.float32,
                   pad_to: int = 0):
    """``pad_to``: pad the q-head count (e.g. 36 -> 48) so heads shard
    cleanly over the TP axis.  Pad heads have zero wo rows, so the function
    computed is *exactly* unchanged (§Perf cell B iter-2); without the pad,
    GSPMD partial-shards the head dim and all-reduces attention internals."""
    kq, kk, kv, ko = jax.random.split(key, 4)
    hp = max(n_heads, pad_to or n_heads)
    s = d_model ** -0.5
    wq = jax.random.normal(kq, (d_model, hp * head_dim), dtype) * s
    wo = jax.random.normal(ko, (hp * head_dim, d_model), dtype) \
        * (n_heads * head_dim) ** -0.5
    if hp > n_heads:
        live = n_heads * head_dim
        wq = wq.at[:, live:].set(0.0)
        wo = wo.at[live:, :].set(0.0)
    p = {
        "wq": wq,
        "wk": jax.random.normal(kk, (d_model, n_kv * head_dim), dtype) * s,
        "wv": jax.random.normal(kv, (d_model, n_kv * head_dim), dtype) * s,
        "wo": wo,
    }
    if qk_norm_flag:
        p["q_norm"] = {"scale": jnp.ones((head_dim,), jnp.float32)}
        p["k_norm"] = {"scale": jnp.ones((head_dim,), jnp.float32)}
    return p


def _qkv(params, x, n_heads, n_kv, head_dim, positions, rope_theta,
         use_qk_norm, linear=None, salt=None):
    B, S, _ = x.shape
    n_heads = params["wq"].shape[1] // head_dim   # includes TP head padding
    s = (lambda i: None) if salt is None else (lambda i: salt + i)
    q = _mm(x, params["wq"], linear, s(4)).reshape(B, S, n_heads, head_dim)
    k = _mm(x, params["wk"], linear, s(5)).reshape(B, S, n_kv, head_dim)
    v = _mm(x, params["wv"], linear, s(6)).reshape(B, S, n_kv, head_dim)
    if use_qk_norm:
        q = qk_norm(q, params.get("q_norm"))
        k = qk_norm(k, params.get("k_norm"))
    cos, sin = rope_angles(positions, head_dim, rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    return q, k, v


def _flash(q, k, v, q_pos, kv_pos, q_chunk: int, kv_chunk: int, n_rep: int):
    """Online-softmax attention. q (B,S,H,D); k/v (B,T,Hkv,D); GQA grouped.

    §Perf cell B: (a) kv heads are never materialized n_rep-fold — q is
    reshaped to (Hkv, n_rep) groups and contracted against kv directly;
    (b) the QK/AV einsums run in bf16 with f32 accumulation (MXU path) —
    the running max/sum statistics stay f32.

    q_pos (S,), kv_pos (T,): absolute positions for causal masking (kv_pos
    may include cache prefix).  Returns (B,S,H,D).
    """
    B, S, H, D = q.shape
    T = k.shape[1]
    G = k.shape[2]                        # kv heads
    scale = D ** -0.5
    q_chunk = min(q_chunk, S)
    kv_chunk = min(kv_chunk, T)
    nq, nk = S // q_chunk, T // kv_chunk
    assert S % q_chunk == 0 and T % kv_chunk == 0, (S, T, q_chunk, kv_chunk)

    qc = q.reshape(B, nq, q_chunk, G, n_rep, D).transpose(1, 0, 3, 4, 2, 5)
    # (nq, B, G, n_rep, cq, D)
    kc = k.reshape(B, nk, kv_chunk, G, D).transpose(1, 0, 3, 2, 4)
    vc = v.reshape(B, nk, kv_chunk, G, D).transpose(1, 0, 3, 2, 4)
    qp = q_pos.reshape(nq, q_chunk)
    kp = kv_pos.reshape(nk, kv_chunk)
    bf = jnp.bfloat16

    causal_dense = (S == T)   # train/prefill: q and kv cover the same span

    def _block(qi, qpi, kcj, vcj, kpj, acc, m, l):
        s = jnp.einsum("bgrqd,bgkd->bgrqk", qi.astype(bf), kcj.astype(bf),
                       preferred_element_type=jnp.float32) * scale
        mask = qpi[None, None, None, :, None] >= kpj[None, None, None,
                                                     None, :]
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bgrqk,bgkd->bgrqd", p.astype(bf), vcj.astype(bf),
            preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    if causal_dense and q_chunk == kv_chunk and nq == nk:
        # §Perf cell B iter-3: scan only the nq(nq+1)/2 lower-triangle
        # (q-chunk, kv-chunk) pairs — the upper triangle is fully masked
        # and would burn MXU flops + HBM bytes for nothing.
        import numpy as _np
        ii, jj = _np.tril_indices(nq)
        pairs = (jnp.asarray(ii, jnp.int32), jnp.asarray(jj, jnp.int32))

        def body(carry, ij):
            acc_all, m_all, l_all = carry
            i, j = ij
            qi = jax.lax.dynamic_index_in_dim(qc, i, 0, keepdims=False)
            qpi = jax.lax.dynamic_index_in_dim(qp, i, 0, keepdims=False)
            kcj = jax.lax.dynamic_index_in_dim(kc, j, 0, keepdims=False)
            vcj = jax.lax.dynamic_index_in_dim(vc, j, 0, keepdims=False)
            kpj = jax.lax.dynamic_index_in_dim(kp, j, 0, keepdims=False)
            acc = jax.lax.dynamic_index_in_dim(acc_all, i, 0, keepdims=False)
            m = jax.lax.dynamic_index_in_dim(m_all, i, 0, keepdims=False)
            l = jax.lax.dynamic_index_in_dim(l_all, i, 0, keepdims=False)
            acc, m, l = _block(qi, qpi, kcj, vcj, kpj, acc, m, l)
            acc_all = jax.lax.dynamic_update_index_in_dim(acc_all, acc, i, 0)
            m_all = jax.lax.dynamic_update_index_in_dim(m_all, m, i, 0)
            l_all = jax.lax.dynamic_update_index_in_dim(l_all, l, i, 0)
            return (acc_all, m_all, l_all), None

        acc0 = jnp.zeros((nq, B, G, n_rep, q_chunk, D), jnp.float32)
        m0 = jnp.full((nq, B, G, n_rep, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((nq, B, G, n_rep, q_chunk), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), pairs)
        out = acc / jnp.maximum(l, 1e-30)[..., None]
    else:
        def per_q(qi, qpi):
            acc0 = jnp.zeros((B, G, n_rep, q_chunk, D), jnp.float32)
            m0 = jnp.full((B, G, n_rep, q_chunk), NEG_INF, jnp.float32)
            l0 = jnp.zeros((B, G, n_rep, q_chunk), jnp.float32)

            def inner(carry, kj):
                kcj, vcj, kpj = kj
                return _block(qi, qpi, kcj, vcj, kpj, *carry), None

            (acc, m, l), _ = jax.lax.scan(inner, (acc0, m0, l0),
                                          (kc, vc, kp))
            return acc / jnp.maximum(l, 1e-30)[..., None]

        out = jax.lax.map(lambda args: per_q(*args), (qc, qp))
    # (nq, B, G, n_rep, cq, D) -> (B, nq, cq, G, n_rep, D) -> (B, S, H, D)
    return out.transpose(1, 0, 4, 2, 3, 5).reshape(B, S, H, D).astype(q.dtype)


def attention(params, x, cfg, positions=None, q_chunk: int = 512,
              kv_chunk: int = 1024, return_kv: bool = False,
              linear=None, salt=None):
    """Full-sequence (train / prefill) GQA attention block.

    cfg needs: n_heads, n_kv, head_dim, rope_theta, qk_norm.
    Returns (out, (k, v)) where k/v are the cacheable projections.
    ``linear``/``salt``: optional DS-CIM operator for the projections
    (sites 4..7 of the per-layer salt space; mlp owns 0..2).
    """
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)[None, :]
    q, k, v = _qkv(params, x, cfg.n_heads, cfg.n_kv, cfg.head_dim,
                   positions, cfg.rope_theta, cfg.qk_norm, linear, salt)
    n_rep = q.shape[2] // cfg.n_kv
    pos1 = positions[0]
    kv_chunk = q_chunk  # square blocks enable the causal pair-scan path
    out = _flash(q, k, v, pos1, pos1, q_chunk, kv_chunk, n_rep)
    out = _mm(out.reshape(B, S, -1), params["wo"], linear,
              None if salt is None else salt + 7)
    return (out, (k, v)) if return_kv else (out, None)


def decode_attention(params, x, cache_k, cache_v, pos, cfg,
                     linear=None, salt=None):
    """Single-token decode against a fixed-capacity KV cache.

    x (B,1,D); cache_k/v (B, T, n_kv, head_dim) with valid prefix length
    ``pos``: a scalar (all rows in lockstep — the PR 3 fixed-length path,
    bit-compatible) or a per-slot (B,) vector for ragged completion /
    continuous batching (each row writes and masks at its own position;
    a finished row whose pos stops advancing benignly rewrites its own
    head entry — it is dead until re-admission overwrites the whole row).
    Returns (out (B,1,D), new_k, new_v).
    """
    B, _, _ = x.shape
    T = cache_k.shape[1]
    ragged = getattr(pos, "ndim", 0) == 1
    positions = (pos[:, None].astype(jnp.int32) if ragged
                 else jnp.full((B, 1), pos, jnp.int32))
    q, k, v = _qkv(params, x, cfg.n_heads, cfg.n_kv, cfg.head_dim,
                   positions, cfg.rope_theta, cfg.qk_norm, linear, salt)
    if ragged:
        def upd(c, kk, p):
            return jax.lax.dynamic_update_slice_in_dim(c, kk, p, axis=0)
        new_k = jax.vmap(upd)(cache_k, k.astype(cache_k.dtype), pos)
        new_v = jax.vmap(upd)(cache_v, v.astype(cache_v.dtype), pos)
        mask = jnp.arange(T)[None, None, None, :] <= pos[:, None, None, None]
    else:
        new_k = jax.lax.dynamic_update_slice_in_dim(
            cache_k, k.astype(cache_k.dtype), pos, axis=1)
        new_v = jax.lax.dynamic_update_slice_in_dim(
            cache_v, v.astype(cache_v.dtype), pos, axis=1)
        mask = jnp.arange(T)[None, None, None, :] <= pos
    n_rep = q.shape[2] // cfg.n_kv
    kr = jnp.repeat(new_k, n_rep, axis=2)            # (B,T,H,D)
    vr = jnp.repeat(new_v, n_rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   kr.astype(jnp.float32)) * cfg.head_dim ** -0.5
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vr.astype(jnp.float32))
    out = _mm(out.reshape(B, 1, -1).astype(x.dtype), params["wo"], linear,
              None if salt is None else salt + 7)
    return out, new_k, new_v


def decode_attention_multi(params, x, cache_k, cache_v, pos, cfg,
                           linear=None, salt=None, done=None):
    """Speculative-verify decode: score T consecutive tokens per row in one
    call against a fixed-capacity KV cache.

    The projections (q/k/v/wo) batch over the window — that is the whole
    speedup — while the cache write / mask / softmax run as a per-position
    ``lax.scan`` that replays the exact op sequence of ``decode_attention``
    (write position t, mask ``tj <= pos + t``, (B,1,..)-shaped einsums), so
    position t here is bitwise-identical to t successive single-token
    decodes with the same weights.  (DS-CIM ``statistical``/``paper_inject``
    estimators draw shape-keyed noise and are excluded from that guarantee;
    ``exact``/``lut``/``bitmatmul``/``kernel`` and the plain float path
    batch bitwise-cleanly.)

    x (B, T, D); pos (B,) valid prefix lengths.  ``done`` rows freeze their
    positions (write/mask at ``pos``, like the single-token ragged path) so
    a finished slot only benignly rewrites its own head entry.
    Returns (out (B, T, D), new_k, new_v).
    """
    B, T, _ = x.shape
    Tc = cache_k.shape[1]
    step = jnp.ones((B,), jnp.int32) if done is None \
        else jnp.where(done, 0, 1).astype(jnp.int32)
    offs = jnp.arange(T, dtype=jnp.int32)
    positions = pos[:, None].astype(jnp.int32) + step[:, None] * offs[None, :]
    q, k, v = _qkv(params, x, cfg.n_heads, cfg.n_kv, cfg.head_dim,
                   positions, cfg.rope_theta, cfg.qk_norm, linear, salt)
    n_rep = q.shape[2] // cfg.n_kv

    def upd(c, kk, p):
        return jax.lax.dynamic_update_slice_in_dim(c, kk[None], p, axis=0)

    def pstep(carry, xs):
        ck, cv = carry
        qt, kt, vt, t = xs                                # (B,H,D)/(B,KV,D)
        pt = pos + step * t                               # (B,)
        nk = jax.vmap(upd)(ck, kt.astype(ck.dtype), pt)
        nv = jax.vmap(upd)(cv, vt.astype(cv.dtype), pt)
        mask = jnp.arange(Tc)[None, None, None, :] <= pt[:, None, None, None]
        kr = jnp.repeat(nk, n_rep, axis=2)
        vr = jnp.repeat(nv, n_rep, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", qt[:, None].astype(jnp.float32),
                       kr.astype(jnp.float32)) * cfg.head_dim ** -0.5
        s = jnp.where(mask, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        ot = jnp.einsum("bhqk,bkhd->bqhd", p, vr.astype(jnp.float32))
        return (nk, nv), ot[:, 0]                         # (B,H,D)

    (new_k, new_v), outs = jax.lax.scan(
        pstep, (cache_k, cache_v),
        (jnp.moveaxis(q, 1, 0), jnp.moveaxis(k, 1, 0),
         jnp.moveaxis(v, 1, 0), offs))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, T, -1).astype(x.dtype)
    out = _mm(out, params["wo"], linear,
              None if salt is None else salt + 7)
    return out, new_k, new_v


def _paged_read_jnp(qf, view, k_tail, v_tail):
    """The jnp reference read path: flash-style online softmax over logical
    pages as a ``lax.scan``, gathering each physical int8 page and fusing
    its per-kv-head dequant into the inner loop — the full-precision cache
    is never materialized (though on TPU the gathered page and its f32
    copy still stage through HBM, which is what the Pallas kernel path
    removes).  qf (B, KV, n_rep, HD) f32; returns (B, KV, n_rep, HD) f32.
    """
    B, KV, n_rep, HD = qf.shape
    pos = view["pos"]
    page_table = view["page_table"]
    k_pages, v_pages = view["k_pages"], view["v_pages"]
    k_scale, v_scale = view["k_scale"], view["v_scale"]
    ps = k_pages.shape[1]
    MP = page_table.shape[1]
    scale_qk = HD ** -0.5
    tail_page = pos // ps

    def page_step(carry, j):
        m, l, acc = carry
        phys = page_table[:, j]                           # (B,)
        kj = k_pages[phys].astype(jnp.float32) \
            * k_scale[phys][:, None, :, None]             # (B,ps,KV,HD)
        vj = v_pages[phys].astype(jnp.float32) \
            * v_scale[phys][:, None, :, None]
        is_tail = (j == tail_page)[:, None, None, None]
        kj = jnp.where(is_tail, k_tail.astype(jnp.float32), kj)
        vj = jnp.where(is_tail, v_tail.astype(jnp.float32), vj)
        tj = j * ps + jnp.arange(ps, dtype=jnp.int32)     # token indices
        valid = tj[None, :] <= pos[:, None]               # (B,ps)
        s = jnp.einsum("bgrd,bpgd->bgrp", qf, kj) * scale_qk
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum("bgrp,bpgd->bgrd", p, vj)
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, KV, n_rep), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, n_rep), jnp.float32)
    acc0 = jnp.zeros((B, KV, n_rep, HD), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(page_step, (m0, l0, acc0),
                                  jnp.arange(MP, dtype=jnp.int32))
    return acc / jnp.maximum(l, 1e-30)[..., None]         # (B,KV,R,HD)


def _paged_read_kernel(qf, view, k_tail, v_tail, par):
    """The fused Pallas read path (kernels/paged_attention.py): one launch
    walking the page table per (slot, kv-head-group) grid cell, int8 pages
    streamed into VMEM by scalar-prefetch index maps, dequant + bf16 tail
    overlay fused into the in-VMEM online softmax.  Under a mesh the call
    wraps in shard_map (batch over DP, pool gathered — Pallas cannot be
    GSPMD-partitioned).  Tile knobs come from the autotune cache under
    ``REPRO_DSCIM_TUNE`` (checked-in winners for the serving shapes)."""
    import os

    from repro.kernels.paged_attention import (paged_attention_decode,
                                               paged_attention_decode_sharded)
    tune = os.environ.get("REPRO_DSCIM_TUNE", "") not in ("", "0")
    args = (qf, view["k_pages"], view["v_pages"], view["k_scale"],
            view["v_scale"], k_tail, v_tail, view["page_table"], view["pos"])
    if par is not None:
        return paged_attention_decode_sharded(
            *args, mesh=par.mesh, dp_axes=par.dp_axes, tune=tune)
    return paged_attention_decode(*args, tune=tune)


def decode_attention_paged(params, x, view, cfg, linear=None, salt=None,
                           done=None, par=None, use_kernel=None):
    """Single-token decode against one layer of the int8 paged KV cache
    (core/kvcache.py): flash-style online softmax over logical pages with
    the int8->f32 dequant fused into the inner loop — the full-precision
    cache is never materialized.

    Two read paths compute the page walk: the fused Pallas kernel and the
    jnp gather scan (the reference).  ``use_kernel`` selects explicitly
    (the serve stack threads it from ``paged_attn='kernel'|'jnp'``, which
    keys the jitted-builder caches); ``None`` falls back to
    ``kernels.paged_attention.use_paged_kernel(cfg.dscim)`` — kernel for
    the 'kernel' serving mode, jnp everywhere else, with the
    ``REPRO_PAGED_ATTN`` env knob (read at trace time) forcing either.
    Both walk pages in the same order with f32 statistics, so they agree
    to float-accumulation tolerance (~1e-8 end-to-end logit RMSE in
    interpret mode — tests/test_paged_kernel.py asserts <=1e-5).

    ``view`` (one layer's slice of the paged cache dict):
      k_pages/v_pages (P, ps, KV, HD) int8, k_scale/v_scale (P, KV) f32,
      k_tail/v_tail (B, ps, KV, HD), page_table (B, MP) int32, pos (B,).
    ``done`` (B,) bool: finished slots neither advance nor flush — a dead
    slot must not scatter into pool pages its allocator may already have
    re-granted to a live request.  (The read needs no done mask of its
    own: a finished slot's ``pos`` is frozen, so the in-loop ragged mask
    already covers it.)
    ``par``: ParallelCtx when serving under a mesh — the kernel path must
    run inside shard_map there; the jnp path partitions under GSPMD and
    ignores it.

    Returns (out (B,1,D), (k_pages, v_pages, k_scale, v_scale, k_tail,
    v_tail)) — pos advances at the model level, shared by all layers.
    """
    from repro.core.kvcache import quantize_page
    from repro.kernels.paged_attention import use_paged_kernel

    B = x.shape[0]
    pos = view["pos"]
    page_table = view["page_table"]
    k_pages, v_pages = view["k_pages"], view["v_pages"]
    k_scale, v_scale = view["k_scale"], view["v_scale"]
    n_pages, ps, KV, HD = k_pages.shape
    positions = pos[:, None].astype(jnp.int32)
    q, k, v = _qkv(params, x, cfg.n_heads, cfg.n_kv, cfg.head_dim,
                   positions, cfg.rope_theta, cfg.qk_norm, linear, salt)

    # 1. the new token lands in the slot's tail page at offset pos % ps
    #    (bf16 — recent tokens attend at full precision until the page
    #    fills and is quantized exactly once)
    off = pos % ps

    def _tail_write(tail, val):
        def upd(t, vv, o):
            return jax.lax.dynamic_update_slice_in_dim(t, vv[None], o, 0)
        new = jax.vmap(upd)(tail, val[:, 0].astype(tail.dtype), off)
        if done is None:
            return new
        return jnp.where(done[:, None, None, None], tail, new)

    k_tail = _tail_write(view["k_tail"], k)
    v_tail = _tail_write(view["v_tail"], v)

    # 2. the page walk: online softmax with in-loop dequant + tail overlay
    n_rep = q.shape[2] // KV
    # _qkv lays heads out kv-major: head h = (g, r) with g = h // n_rep,
    # matching jnp.repeat(k, n_rep, axis=2) on the dense path
    qf = q[:, 0].astype(jnp.float32).reshape(B, KV, n_rep, HD)
    if use_kernel is None:
        use_kernel = use_paged_kernel(getattr(cfg, "dscim", "off"))
    if use_kernel:
        out = _paged_read_kernel(qf, view, k_tail, v_tail, par)
    else:
        out = _paged_read_jnp(qf, view, k_tail, v_tail)
    out = out.reshape(B, 1, -1).astype(x.dtype)

    # 3. flush: a tail page that just filled is quantized (fresh per-head
    #    absmax scales) and scattered to its physical page; slots that are
    #    not flushing (or are done) scatter to an out-of-bounds sentinel
    #    which mode="drop" discards — no read-modify-write, no collisions
    full = (pos + 1) % ps == 0
    if done is not None:
        full = full & ~done
    tail_page = pos // ps
    phys_t = jnp.take_along_axis(page_table, tail_page[:, None], 1)[:, 0]
    idx = jnp.where(full, phys_t, n_pages)
    qk_, sk_ = quantize_page(k_tail)
    qv_, sv_ = quantize_page(v_tail)
    k_pages = k_pages.at[idx].set(qk_, mode="drop")
    v_pages = v_pages.at[idx].set(qv_, mode="drop")
    k_scale = k_scale.at[idx].set(sk_, mode="drop")
    v_scale = v_scale.at[idx].set(sv_, mode="drop")

    out = _mm(out, params["wo"], linear,
              None if salt is None else salt + 7)
    return out, (k_pages, v_pages, k_scale, v_scale, k_tail, v_tail)


def _paged_multi_scan(qf, view, win_k, win_v, done, par, use_kernel):
    """Multi-position paged attention as a per-position ``lax.scan`` that
    replays ``decode_attention_paged`` exactly: write the tail at
    ``pt % ps``, read with the ragged mask at ``pt`` (through the kernel
    or the jnp read path), then the quantize-once flush when ``pt`` fills
    a page.  ``done`` rows freeze ``pt`` and suppress writes + flushes.
    qf (B, T, KV, n_rep, HD) f32; returns (out (B, T, KV, n_rep, HD) f32,
    (k_pages, v_pages, k_scale, v_scale, k_tail, v_tail)).
    """
    from repro.core.kvcache import quantize_page

    B, T = qf.shape[:2]
    pos = view["pos"]
    page_table = view["page_table"]
    n_pages, ps = view["k_pages"].shape[:2]
    step = jnp.ones((B,), jnp.int32) if done is None \
        else jnp.where(done, 0, 1).astype(jnp.int32)

    def upd(t, vv, o):
        return jax.lax.dynamic_update_slice_in_dim(t, vv[None], o, 0)

    def pstep(carry, xs):
        k_pages, v_pages, k_scale, v_scale, k_tail, v_tail = carry
        qt, kt, vt, t = xs                                # (B,KV,..,HD)
        pt = pos + step * t
        off = pt % ps
        nkt = jax.vmap(upd)(k_tail, kt, off)
        nvt = jax.vmap(upd)(v_tail, vt, off)
        if done is not None:
            nkt = jnp.where(done[:, None, None, None], k_tail, nkt)
            nvt = jnp.where(done[:, None, None, None], v_tail, nvt)
        viewt = {"k_pages": k_pages, "v_pages": v_pages,
                 "k_scale": k_scale, "v_scale": v_scale,
                 "page_table": page_table, "pos": pt}
        if use_kernel:
            ot = _paged_read_kernel(qt, viewt, nkt, nvt, par)
        else:
            ot = _paged_read_jnp(qt, viewt, nkt, nvt)
        full = (pt + 1) % ps == 0
        if done is not None:
            full = full & ~done
        tail_page = pt // ps
        phys_t = jnp.take_along_axis(page_table, tail_page[:, None], 1)[:, 0]
        idx = jnp.where(full, phys_t, n_pages)
        qk_, sk_ = quantize_page(nkt)
        qv_, sv_ = quantize_page(nvt)
        k_pages = k_pages.at[idx].set(qk_, mode="drop")
        v_pages = v_pages.at[idx].set(qv_, mode="drop")
        k_scale = k_scale.at[idx].set(sk_, mode="drop")
        v_scale = v_scale.at[idx].set(sv_, mode="drop")
        return (k_pages, v_pages, k_scale, v_scale, nkt, nvt), ot

    carry0 = (view["k_pages"], view["v_pages"], view["k_scale"],
              view["v_scale"], view["k_tail"], view["v_tail"])
    planes, outs = jax.lax.scan(
        pstep, carry0,
        (jnp.moveaxis(qf, 1, 0), jnp.moveaxis(win_k, 1, 0),
         jnp.moveaxis(win_v, 1, 0), jnp.arange(T, dtype=jnp.int32)))
    return jnp.moveaxis(outs, 0, 1), planes


def _chunk_pages(win, tail0, first):
    """The bf16 pages a T-position window covers, each as the slot's tail
    holds it while that page is current, (B, nc, ps, KV, HD); ``first``
    (B, nc) is the window index of each page's first token.  A row takes
    the window's K/V of its position, the pre-window tail before the
    window, and past the window's end what the sequential tail holds
    there: the window's last write at that offset, else the pre-window
    tail."""
    B, T = win.shape[:2]
    nc, ps = first.shape[1], tail0.shape[1]
    t = first[..., None] + jnp.arange(ps, dtype=jnp.int32)    # (B, nc, ps)
    t = jnp.where(t >= T, t - ps * ((t - T + ps) // ps), t).reshape(B, -1)
    g = jnp.take_along_axis(win, jnp.clip(t, 0, T - 1)[..., None, None],
                            axis=1)
    g = jnp.where((t >= 0)[..., None, None], g, jnp.tile(tail0, (1, nc, 1, 1)))
    return g.reshape(B, nc, ps, *win.shape[2:])


def _paged_multi_kernel(qf, view, win_k, win_v, done, par):
    """Multi-position paged attention on the kernel read path, in one
    launch: flush every page the window fills, then score all T positions
    against the flushed pool and the window's bf16 pages
    (kernels/paged_attention.py ``paged_attention_decode_chunk``).

    The pool, scales and tails it leaves are bitwise what the per-position
    scan of ``decode_attention_paged_multi`` leaves: each flushed page is
    ``quantize_page`` of the bf16 page the scan's tail holds when that
    page fills, written by the same ``mode="drop"`` scatter, and the tail
    is the window's last page as the scan leaves it.  Each position reads
    pages before its own from the pool (already flushed when the scan
    reads them) and its own page from the bf16 copy, as the scan does.
    Done rows write nothing and their outputs are zeros.
    qf (B, T, KV, n_rep, HD) f32; returns (out (B, T, KV, n_rep, HD) f32,
    (k_pages, v_pages, k_scale, v_scale, k_tail, v_tail)).
    """
    from repro.core.kvcache import quantize_page
    from repro.kernels.paged_attention import (
        paged_attention_decode_chunk, paged_attention_decode_chunk_sharded)

    B, T, KV, HD = win_k.shape
    pos = view["pos"]
    page_table = view["page_table"]
    n_pages, ps = view["k_pages"].shape[:2]
    nc = (T + 2 * ps - 2) // ps              # most pages T positions span
    done = jnp.zeros((B,), bool) if done is None else done
    lpage = (pos // ps)[:, None] + jnp.arange(nc, dtype=jnp.int32)
    k_chunk = _chunk_pages(win_k, view["k_tail"], lpage * ps - pos[:, None])
    v_chunk = _chunk_pages(win_v, view["v_tail"], lpage * ps - pos[:, None])

    # flush: a page the window fills is quantized once and scattered to
    # its physical page; others (and done rows) go to the dropped sentinel
    full = (lpage * ps + ps - 1 <= (pos + T - 1)[:, None]) & ~done[:, None]
    phys = jnp.take_along_axis(page_table, lpage, 1)
    idx = jnp.where(full, phys, n_pages).reshape(-1)
    planes = []
    for name, chunk in (("k", k_chunk), ("v", v_chunk)):
        qv, sv = quantize_page(chunk)
        planes.append(view[f"{name}_pages"].at[idx].set(
            qv.reshape(-1, ps, KV, HD), mode="drop"))
        planes.append(view[f"{name}_scale"].at[idx].set(
            sv.reshape(-1, KV), mode="drop"))
    k_pages, k_scale, v_pages, v_scale = planes

    last = ((pos + T - 1) // ps - pos // ps)[:, None, None, None, None]
    keep = done[:, None, None, None]
    k_tail = jnp.where(keep, view["k_tail"],
                       jnp.take_along_axis(k_chunk, last, 1)[:, 0])
    v_tail = jnp.where(keep, view["v_tail"],
                       jnp.take_along_axis(v_chunk, last, 1)[:, 0])

    args = (qf, k_pages, v_pages, k_scale, v_scale, k_chunk, v_chunk,
            page_table, pos, done)
    if par is not None:
        out = paged_attention_decode_chunk_sharded(
            *args, mesh=par.mesh, dp_axes=par.dp_axes)
    else:
        out = paged_attention_decode_chunk(*args)
    return out, (k_pages, v_pages, k_scale, v_scale, k_tail, v_tail)


def decode_attention_paged_multi(params, x, view, cfg, linear=None, salt=None,
                                 done=None, par=None, use_kernel=None):
    """Speculative-verify decode against one layer of the int8 paged cache:
    score T consecutive tokens per row in one call.

    Projections batch over the window.  Position t is bitwise-identical
    to t successive single-token decodes (``decode_attention_paged``:
    write tail at ``pt % ps``, read with the ragged mask at ``pt``, then
    quantize-once flush when ``pt`` fills a page) in its cache writes, and
    in its output up to the read path's float accumulation order:

    * the jnp read path, and T = 1 on either path, replay that sequence
      per position inside a ``lax.scan``;
    * the kernel read path with T > 1 makes one launch per layer
      (``_paged_multi_kernel``): it flushes every page the window fills
      up front, and the kernel's page rule (pool pages before the
      position's own, the bf16 page of its own, none past it) reads for
      each position exactly what the scan would, by construction.

    ``done`` rows freeze ``pt`` and suppress writes + flushes, exactly
    like the single-token path (the one-launch path returns zeros for
    them; every caller discards done rows' outputs).

    Also returns the window's K/V projections in tail dtype — the
    speculative rollback (core/kvcache.spec_rollback) needs them to rebuild
    the committed tail when a rejected window crossed a page boundary.

    Returns (out (B, T, D),
             (k_pages, v_pages, k_scale, v_scale, k_tail, v_tail),
             (win_k, win_v))  with win_k/win_v (B, T, KV, HD) tail-dtype.
    """
    from repro.kernels.paged_attention import use_paged_kernel

    B, T, _ = x.shape
    pos = view["pos"]
    KV, HD = view["k_pages"].shape[2:]
    step = jnp.ones((B,), jnp.int32) if done is None \
        else jnp.where(done, 0, 1).astype(jnp.int32)
    offs = jnp.arange(T, dtype=jnp.int32)
    positions = pos[:, None].astype(jnp.int32) + step[:, None] * offs[None, :]
    q, k, v = _qkv(params, x, cfg.n_heads, cfg.n_kv, cfg.head_dim,
                   positions, cfg.rope_theta, cfg.qk_norm, linear, salt)
    n_rep = q.shape[2] // KV
    if use_kernel is None:
        use_kernel = use_paged_kernel(getattr(cfg, "dscim", "off"))
    win_k = k.astype(view["k_tail"].dtype)                # (B,T,KV,HD)
    win_v = v.astype(view["v_tail"].dtype)
    qf = q.astype(jnp.float32).reshape(B, T, KV, n_rep, HD)
    if use_kernel and T > 1:
        outs, planes = _paged_multi_kernel(qf, view, win_k, win_v, done,
                                           par)
    else:
        outs, planes = _paged_multi_scan(qf, view, win_k, win_v, done, par,
                                         use_kernel)
    out = outs.reshape(B, T, -1).astype(x.dtype)
    out = _mm(out, params["wo"], linear,
              None if salt is None else salt + 7)
    return out, planes, (win_k, win_v)


AttnParams = dict
