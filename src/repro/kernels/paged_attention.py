"""Fused Pallas paged-attention decode kernel (ISSUE 5 tentpole).

PR 4's int8 block-paged KV cache won the *bytes* (3.53x fewer resident
decode-cache bytes) but read them through a jnp gather + dequant +
online-softmax ``lax.scan`` in ``decode_attention_paged`` — on TPU that
path stages every gathered page and its dequantized f32 copy in HBM
before the QK contraction sees it, so the bandwidth win the quantization
paid for is handed straight back.  The paper's premise (DS-CIM's fused
in-array sign-correction + dequant) and the SC memory-system literature
(Khatamifard et al.; Stoch-IMC's bit-parallel banking) agree on the fix:
keep the dequant *inside* the bandwidth-bound loop.

This kernel is that loop, in one launch:

* grid ``(B, KV // gh, MP)`` — one cell per (batch slot, kv-head group),
  walking the MP logical pages on the innermost (sequential) grid axis;
* the **page table is a scalar-prefetch operand**, so each step's
  BlockSpec index map resolves ``table[b, j]`` before the body runs and
  the pipeline DMA streams the *physical* int8 page straight into VMEM —
  the gather never materializes in HBM;
* per-page per-kv-head dequant scales ride as SMEM scalar blocks and the
  int8 -> f32 dequant happens on the VMEM-resident page inside the flash
  online-softmax update (m/l/acc live in VMEM scratch across the page
  axis, exactly like kernels/flash_attention.py);
* the page block is (ps, gh, HD) — the pool's own layout — flattened to
  (ps*gh, HD) rows; q comes in as (gh*qp, HD) head-major rows, and one
  2-D dot scores every q row against every page row, with rows of other
  kv heads masked like tokens past ``pos`` (gh times the small decode
  score work, in exchange for a body of plain 2-D dots on the chip);
* the slot's bf16 **tail page overlays** its logical slot in-kernel
  (``j == pos[b] // ps``) at full precision;
* **ragged slots mask in-kernel**: tokens past ``pos[b]`` get NEG_INF
  scores, and pages entirely past the valid prefix are skipped with
  ``pl.when`` (no MXU work).  Done slots need no extra masking — the
  model freezes a finished slot's ``pos``, so the same predicate covers
  them (their tail write and flush are gated host-side by ``done`` in
  layers/attention.py, which stays the jnp reference semantics).

**Chunk entry** (``paged_attention_decode_chunk``, kernel name
``paged_attention_decode_chunk``): T consecutive positions per slot —
a chunked-prefill window, a prefix-cache admission, a speculative verify
window — scored in one launch on the same (B, KV // gh, MP) grid, with
the T positions' q rows (position-major, T*gh*qp of them) resident per
cell.  The caller has already flushed every page the window fills into
the pool, and hands in the window's pages at full precision as the
slot's bf16 tail holds each while it is current.  For the query at
position p, logical page j is read

* as int8 from the pool if ``j < p // ps``;
* from the window's bf16 page if ``j == p // ps``, tokens past p masked;
* not at all past that page,

which is the rule the per-position replay applies, on the same int8 and
bf16 values, in the same page order with the same f32 contractions.  The
int8 page and the bf16 page are stacked into one block and one 2-D dot
scores both; rows run in tiles of positions, and a tile whose last
position lies before page j skips it.  A slot's pages past the window's
last one, and every page of a done slot, map to the block already
resident (no DMA) and skip the body; done slots' outputs are zeros.

The attended output (B, KV, n_rep, HD) comes out in f32; the q/k/v
projections, RoPE, tail write and page flush stay in jnp around the call
(they are O(B) scatter work, not the bandwidth term).  Numerics match the
jnp reference scan to float-accumulation tolerance: both walk pages in
the same order with f32 contraction and f32 m/l/acc statistics, but
XLA's einsum layout and the kernel's dot_general round differently, so
end-to-end logit RMSE is ~1e-8 (the CI threshold,
tools/ci_thresholds.json, is 1e-5).

Tile knobs (threaded through kernels/autotune.py ``paged_attn_tiles``,
with winners for the decode serving shapes in the checked-in cache):

* ``gh``  — kv heads per grid cell.  On the chip the page block's
  (gh, HD) minor dims must be legal, so gh is KV or a multiple of 8
  (``chip_legal_ghs``); the default is the smallest legal group, since
  the cell's score, exp and PV work grows with gh (8 for KV = 8, 16 or
  32).  Smaller groups are for interpret-mode A/Bs;
* ``qp``  — q rows per cell, i.e. n_rep padded up (pad rows are zeros,
  sliced off after the call; on TPU this is the sublane-alignment knob).

Parity with the jnp reference is tested in interpret mode
(tests/test_paged_kernel.py) and on the chip (chip_smoke.py); the TPU
compile at serving widths is guarded by tests/test_tpu_compile.py.
Under a mesh
the call must sit inside shard_map (a Pallas call cannot be GSPMD-
partitioned): ``paged_attention_decode_sharded`` (and
``paged_attention_decode_chunk_sharded``) shards the batch-carried
operands (q, tails or window pages, page table, pos) over the DP axes
and gathers the page pool whole per shard — under continuous batching
any slot may reference any physical page, so the pool cannot shard with
the slots.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ops import ON_TPU

__all__ = ["paged_attention_decode", "paged_attention_decode_sharded",
           "paged_attention_decode_chunk",
           "paged_attention_decode_chunk_sharded", "use_paged_kernel",
           "KERNEL_NAME", "CHUNK_KERNEL_NAME"]

NEG_INF = -1e30
# the Pallas kernel's name: it labels the kernel's custom call in compiled
# programs (and its events in profiler traces)
KERNEL_NAME = "paged_attention_decode"
# the chunk entry's name holds KERNEL_NAME, so a match on that name counts
# both entries' device time
CHUNK_KERNEL_NAME = "paged_attention_decode_chunk"
# full-f32 MXU contractions: the kernel matches the f32 jnp reference to
# float-accumulation tolerance on the chip too, not to bf16 rounding
_F32 = (jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST)
_ENV_FLAG = "REPRO_PAGED_ATTN"


def use_paged_kernel(dscim_spec: str) -> bool:
    """Fallback read-path selector for ``decode_attention_paged`` when no
    explicit pin was threaded in (``paged_attn='auto'``): the Pallas
    kernel is the default for the 'kernel' serving mode, the jnp gather
    scan stays the reference everywhere else.  ``REPRO_PAGED_ATTN=
    kernel|jnp`` forces either path; like ``REPRO_DSCIM_TUNE`` it is read
    at trace time, so in-process A/Bs should prefer the cache-keyed
    ``paged_attn`` option on the serve stack."""
    env = os.environ.get(_ENV_FLAG, "").strip().lower()
    if env in ("kernel", "pallas", "1"):
        return True
    if env in ("jnp", "gather", "0"):
        return False
    from repro.core.qweights import split_dscim_mode
    return split_dscim_mode(dscim_spec)[0] == "kernel"


def _head_scales(s_ref, gh: int):
    """The (1, gh, 1) f32 dequant scales of one page block's kv heads,
    read from its (1, 1, 1, gh) SMEM scale block."""
    hid = jax.lax.broadcasted_iota(jnp.int32, (1, gh, 1), 1)
    sc = jnp.zeros((1, gh, 1), jnp.float32)
    for h in range(gh):
        sc = jnp.where(hid == h, s_ref[0, 0, 0, h], sc)
    return sc


def _kernel(table_ref, pos_ref, ks_ref, vs_ref, q_ref, kp_ref, vp_ref,
            kt_ref, vt_ref, o_ref, acc_ref, m_ref, l_ref, *,
            ps: int, gh: int, qp: int, scale: float):
    """One grid step: one logical page of one (slot, kv-head-group) cell.

    Blocks (leading size-1 page/slot dim squeezed on read):
      q (1, gh*qp, HD) f32 — head-major rows; kp/vp (1, ps, gh, HD) int8 —
      the *physical* page picked by the scalar-prefetched table; ks/vs
      (1, 1, 1, gh) f32 in SMEM; kt/vt (1, ps, gh, HD) bf16.  Scratch acc
      (gh*qp, HD), m/l (gh*qp, 1) carry the online-softmax state across
      the page axis.

    The page is flattened to (ps*gh, HD) token-major rows and every q row
    is scored against every row of it in one 2-D dot; rows of another kv
    head are masked out like tokens past ``pos``.  That keeps the body to
    2-D dots on the chip, at gh times the (small) decode score work.
    """
    b = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    posb = pos_ref[b]

    def dequant(p_ref, s_ref, t_ref, is_tail):
        """(ps, gh, HD) int8 page * per-head scale, or the bf16 tail."""
        sc = _head_scales(s_ref, gh)
        x = jnp.where(is_tail, t_ref[0].astype(jnp.float32),
                      p_ref[0].astype(jnp.float32) * sc)
        return x.reshape(ps * gh, x.shape[-1])

    # pages entirely past the slot's valid prefix contribute exactly
    # nothing (the jnp reference's fully-masked page is a no-op update:
    # alpha = 1, p = 0) — skip their dequant + MXU work outright
    @pl.when(j * ps <= posb)
    def _page():
        is_tail = j == posb // ps
        kj = dequant(kp_ref, ks_ref, kt_ref, is_tail)        # (ps*gh, HD)
        vj = dequant(vp_ref, vs_ref, vt_ref, is_tail)
        q = q_ref[0]                                         # (gh*qp, HD)
        s = jax.lax.dot_general(                             # (gh*qp, ps*gh)
            q, kj, (((1,), (1,)), ((), ())), precision=_F32,
            preferred_element_type=jnp.float32) * scale
        shape = s.shape
        col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        valid = (j * ps + col // gh <= posb) & (col % gh == row // qp)
        s = jnp.where(valid, s, NEG_INF)                     # ragged + head
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, vj, (((1,), (0,)), ((), ())), precision=_F32,
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nj - 1)
    def _finalize():
        o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


@functools.partial(jax.jit, static_argnames=("gh", "qp", "interpret"))
def _paged_call(q, k_pages, v_pages, k_scale, v_scale, k_tail, v_tail,
                page_table, pos, *, gh: int, qp: int, interpret: bool):
    B, KV, R, HD = q.shape
    ps = k_pages.shape[1]
    MP = page_table.shape[1]
    if qp > R:
        # zero pad rows: their scores softmax over the same valid tokens,
        # never NaN, and are sliced off below — the TPU sublane-pad knob
        q = jnp.pad(q, ((0, 0), (0, 0), (0, qp - R), (0, 0)))
    q = q.reshape(B, KV * qp, HD)
    rows = gh * qp
    kernel = functools.partial(_kernel, ps=ps, gh=gh, qp=qp,
                               scale=HD ** -0.5)
    page = pl.BlockSpec((1, ps, gh, HD),
                        lambda b, g, j, t, p: (t[b, j], 0, g, 0))
    tail = pl.BlockSpec((1, ps, gh, HD), lambda b, g, j, t, p: (b, 0, g, 0))
    # per-page scales as (P, KV // gh, 1, gh): a block's minor dims are
    # then the whole (1, gh), which the chip accepts in SMEM
    scales = pl.BlockSpec((1, 1, 1, gh),
                          lambda b, g, j, t, p: (t[b, j], g, 0, 0),
                          memory_space=pltpu.SMEM)
    P = k_scale.shape[0]
    k_scale = k_scale.reshape(P, KV // gh, 1, gh)
    v_scale = v_scale.reshape(P, KV // gh, 1, gh)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV // gh, MP),
        in_specs=[
            scales, scales,
            pl.BlockSpec((1, rows, HD), lambda b, g, j, t, p: (b, g, 0)),
            page, page, tail, tail,
        ],
        out_specs=pl.BlockSpec((1, rows, HD),
                               lambda b, g, j, t, p: (b, g, 0)),
        scratch_shapes=[pltpu.VMEM((rows, HD), jnp.float32),
                        pltpu.VMEM((rows, 1), jnp.float32),
                        pltpu.VMEM((rows, 1), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV * qp, HD), jnp.float32),
        interpret=interpret, name=KERNEL_NAME,
    )(page_table, pos, k_scale, v_scale, q, k_pages, v_pages, k_tail,
      v_tail)
    return out.reshape(B, KV, qp, HD)[:, :, :R]


def chip_legal_ghs(kv: int) -> list:
    """The kv-head group sizes whose page block the chip accepts: the
    whole kv axis, or a divisor of it that is a multiple of 8."""
    return [g for g in range(1, kv + 1)
            if kv % g == 0 and (g == kv or g % 8 == 0)]


def paged_attention_decode(q, k_pages, v_pages, k_scale, v_scale,
                           k_tail, v_tail, page_table, pos, *,
                           gh: int | None = None, qp: int | None = None,
                           interpret: bool | None = None,
                           tune: bool = False):
    """Single-launch paged decode attention (see module docstring).

    q (B, KV, n_rep, HD) f32 — post-RoPE query, kv-major head layout (the
    ``decode_attention_paged`` ``qf`` reshape); k/v_pages (P, ps, KV, HD)
    int8; k/v_scale (P, KV) f32; k/v_tail (B, ps, KV, HD) bf16 — the tail
    must already hold this step's token (layers/attention.py writes it,
    done-gated, before calling); page_table (B, MP) int32 (physical page
    ids); pos (B,) int32.  Returns the attended (B, KV, n_rep, HD) f32.

    ``gh``/``qp``: kv heads per grid cell / padded q rows per cell —
    ``tune=True`` consults kernels/autotune.py (checked-in winners for
    the decode serving shapes); the defaults are the pad-free cell of the
    smallest chip-legal kv-head group.
    """
    interpret = (not ON_TPU) if interpret is None else interpret
    B, KV, R, HD = q.shape
    ps = k_pages.shape[1]
    if tune and gh is None and qp is None:
        from . import autotune
        gh, qp = autotune.paged_attn_tiles((B, KV, R, HD), ps,
                                           interpret=interpret)
    gh = gh or chip_legal_ghs(KV)[0]
    qp = qp or R
    if KV % gh:
        raise ValueError(f"gh={gh} must divide the kv head count {KV}")
    if qp < R:
        raise ValueError(f"qp={qp} < n_rep={R}")
    return _paged_call(q, k_pages, v_pages, k_scale, v_scale,
                       k_tail, v_tail, page_table,
                       pos.astype(jnp.int32), gh=gh, qp=qp,
                       interpret=interpret)


def _slot_axes(mesh, dp_axes: tuple, batch: int):
    """The mesh axes the slot dimension shards over: the DP axes when
    they divide the batch, else None (replicated)."""
    import math
    if dp_axes and batch % math.prod(mesh.shape[a] for a in dp_axes) == 0:
        return tuple(dp_axes)
    return None


def paged_attention_decode_sharded(q, k_pages, v_pages, k_scale, v_scale,
                                   k_tail, v_tail, page_table, pos, *,
                                   mesh, dp_axes: tuple = (), **kw):
    """Mesh placement of the paged-attention kernel (a Pallas call must run
    inside shard_map on a multi-device mesh, like the fused MVM).

    Batch-carried operands (q, tails, page table, pos) shard over the DP
    axes when B divides; the page pool + scales replicate into each shard
    (in_specs ``P(None, ...)`` gathers the committed DP-sharded pool) —
    under continuous batching the allocator may grant a slot *any*
    physical page, so pool rows cannot be assumed slot-aligned.  Output
    lands batch-sharded.  Bit-identical to the single-device call: the
    per-slot page walk is placement-invariant.
    """
    from jax.sharding import PartitionSpec as P

    b = _slot_axes(mesh, dp_axes, q.shape[0])
    bspec4 = P(b, None, None, None)
    repl = lambda a: P(*([None] * a.ndim))  # noqa: E731

    def inner(ql, kp, vp, ks, vs, kt, vt, tbl, pl_):
        return paged_attention_decode(ql, kp, vp, ks, vs, kt, vt, tbl, pl_,
                                      **kw)

    return jax.shard_map(
        inner, mesh=mesh,
        in_specs=(bspec4, repl(k_pages), repl(v_pages), repl(k_scale),
                  repl(v_scale), bspec4, bspec4, P(b, None), P(b)),
        out_specs=bspec4, check_vma=False,
    )(q, k_pages, v_pages, k_scale, v_scale, k_tail, v_tail, page_table,
      pos.astype(jnp.int32))


def _chunk_kernel(table_ref, pos_ref, lo_ref, hi_ref, ks_ref, vs_ref, q_ref,
                  kp_ref, vp_ref, kc_ref, vc_ref, o_ref, acc_ref, m_ref,
                  l_ref, *, ps: int, gh: int, qp: int, tq: int,
                  scale: float):
    """One grid step of the chunk entry: one logical page ``j`` of one
    (slot, kv-head-group) cell, scored for all T window positions.

    Blocks: q (1, 1, T*gh*qp, HD) f32 — position-major rows (t, head, r);
    kp/vp (1, ps, gh, HD) int8 — pool page ``table[b, j]``; kc/vc (1, 1,
    ps, gh, HD) — the window's page ``j`` at full precision; ks/vs
    (1, 1, 1, gh) f32 in SMEM.  Scratch acc/m/l carry each row's online
    softmax across the page axis.

    The int8 page and the chunk page are stacked into one (2*ps*gh, HD)
    block and every row scores both in one 2-D dot; the page rule masks
    per row which half counts: the int8 half for a query whose own page
    lies past ``j``, the chunk half (tokens up to the query) for a query
    on page ``j``, neither past it.  Rows run in tiles of ``tq``
    positions, and a tile whose last position lies before page ``j`` is
    skipped.
    """
    b = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    posb = pos_ref[b]
    w = ps * gh                          # rows of one flattened page
    nr = gh * qp                         # q rows per position
    T = q_ref.shape[2] // nr

    def stack(p_ref, s_ref, c_ref):
        pool = p_ref[0].astype(jnp.float32) * _head_scales(s_ref, gh)
        chunk = c_ref[0, 0].astype(jnp.float32)
        return jnp.concatenate([pool.reshape(w, pool.shape[-1]),
                                chunk.reshape(w, chunk.shape[-1])], axis=0)

    # done slots (hi = -1) and pages past the window's last one do nothing
    @pl.when(j <= hi_ref[b])
    def _page():
        kj = stack(kp_ref, ks_ref, kc_ref)                   # (2w, HD)
        vj = stack(vp_ref, vs_ref, vc_ref)
        for t0 in range(0, T, tq):
            @pl.when(j * ps <= posb + t0 + tq - 1)
            def _tile(t0=t0):
                r0, nrow = t0 * nr, tq * nr
                q = q_ref[0, 0, r0:r0 + nrow, :]
                s = jax.lax.dot_general(                     # (nrow, 2w)
                    q, kj, (((1,), (1,)), ((), ())), precision=_F32,
                    preferred_element_type=jnp.float32) * scale
                shape = s.shape
                row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
                col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
                p = posb + t0 + row // nr                    # query position
                in_chunk = col >= w
                cc = jnp.where(in_chunk, col - w, col)
                past = (j + 1) * ps <= p                     # page j < p // ps
                valid = (in_chunk & (j * ps + cc // gh <= p) & ~past) \
                    | (~in_chunk & past)
                valid = valid & (cc % gh == (row // qp) % gh)
                s = jnp.where(valid, s, NEG_INF)
                m_prev = m_ref[r0:r0 + nrow, :]
                m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
                pr = jnp.exp(s - m_new)
                alpha = jnp.exp(m_prev - m_new)
                l_ref[r0:r0 + nrow, :] = (l_ref[r0:r0 + nrow, :] * alpha
                                          + pr.sum(-1, keepdims=True))
                acc_ref[r0:r0 + nrow, :] = (
                    acc_ref[r0:r0 + nrow, :] * alpha + jax.lax.dot_general(
                        pr, vj, (((1,), (0,)), ((), ())), precision=_F32,
                        preferred_element_type=jnp.float32))
                m_ref[r0:r0 + nrow, :] = m_new

    @pl.when(j == nj - 1)
    def _finalize():
        o_ref[0, 0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def _tile_positions(T: int, nr: int, rows: int = 256) -> int:
    """Window positions per row tile of the chunk entry: the largest
    divisor of T whose ``nr``-row positions fit ``rows`` rows in whole
    8-row groups (the chip's sublane tiling), else all T."""
    for tq in range(T, 0, -1):
        if T % tq == 0 and tq * nr <= rows and (tq == T or tq * nr % 8 == 0):
            return tq
    return T


@functools.partial(jax.jit, static_argnames=("gh", "interpret"))
def _chunk_call(q, k_pages, v_pages, k_scale, v_scale, k_chunk, v_chunk,
                page_table, pos, done, *, gh: int, interpret: bool):
    B, T, KV, R, HD = q.shape
    ps = k_pages.shape[1]
    MP = page_table.shape[1]
    NC = k_chunk.shape[1]
    G, nr = KV // gh, gh * R
    q = q.reshape(B, T, G, nr, HD).transpose(0, 2, 1, 3, 4) \
        .reshape(B, G, T * nr, HD)
    lo = pos // ps
    hi = jnp.where(done, -1, (pos + T - 1) // ps)
    kernel = functools.partial(_chunk_kernel, ps=ps, gh=gh, qp=R,
                               tq=_tile_positions(T, nr), scale=HD ** -0.5)

    # a slot's pages past its last one (and every page of a done slot)
    # map to the block already resident, so the pipeline issues no DMA
    def page_of(b, j, h):
        return jnp.maximum(jnp.minimum(j, h[b]), 0)

    page = pl.BlockSpec(
        (1, ps, gh, HD),
        lambda b, g, j, t, p, lo_, h: (t[b, page_of(b, j, h)], 0, g, 0))
    chunk = pl.BlockSpec(
        (1, 1, ps, gh, HD),
        lambda b, g, j, t, p, lo_, h: (
            b, jnp.clip(page_of(b, j, h) - lo_[b], 0, NC - 1), 0, g, 0))
    scales = pl.BlockSpec(
        (1, 1, 1, gh),
        lambda b, g, j, t, p, lo_, h: (t[b, page_of(b, j, h)], g, 0, 0),
        memory_space=pltpu.SMEM)
    rows = pl.BlockSpec((1, 1, T * nr, HD),
                        lambda b, g, j, t, p, lo_, h: (b, g, 0, 0))
    P = k_scale.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, G, MP),
        in_specs=[scales, scales, rows, page, page, chunk, chunk],
        out_specs=rows,
        scratch_shapes=[pltpu.VMEM((T * nr, HD), jnp.float32),
                        pltpu.VMEM((T * nr, 1), jnp.float32),
                        pltpu.VMEM((T * nr, 1), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, G, T * nr, HD), jnp.float32),
        interpret=interpret, name=CHUNK_KERNEL_NAME,
    )(page_table, pos, lo, hi, k_scale.reshape(P, G, 1, gh),
      v_scale.reshape(P, G, 1, gh), q, k_pages, v_pages, k_chunk, v_chunk)
    return out.reshape(B, G, T, nr, HD).transpose(0, 2, 1, 3, 4) \
        .reshape(B, T, KV, R, HD)


def paged_attention_decode_chunk(q, k_pages, v_pages, k_scale, v_scale,
                                 k_chunk, v_chunk, page_table, pos, done, *,
                                 interpret: bool | None = None):
    """Paged attention for a window of T positions per slot in one launch
    (see module docstring).

    q (B, T, KV, n_rep, HD) f32 — post-RoPE queries of positions
    ``pos .. pos+T-1``, kv-major heads; k/v_pages (P, ps, KV, HD) int8 and
    k/v_scale (P, KV) f32 — the pool with every page the window fills
    already flushed into it; k/v_chunk (B, NC, ps, KV, HD) bf16 — the
    window's logical pages ``pos // ps + c`` at full precision, as the
    slot's tail holds each while it is current; page_table (B, MP) int32;
    pos (B,) int32; done (B,) bool.  Returns (B, T, KV, n_rep, HD) f32:
    row t is what ``paged_attention_decode`` gives at position pos+t with
    the tail holding that position's page; done rows are zeros.
    """
    interpret = (not ON_TPU) if interpret is None else interpret
    KV = q.shape[2]
    return _chunk_call(q, k_pages, v_pages, k_scale, v_scale, k_chunk,
                       v_chunk, page_table, pos.astype(jnp.int32),
                       done.astype(bool), gh=chip_legal_ghs(KV)[0],
                       interpret=interpret)


def paged_attention_decode_chunk_sharded(q, k_pages, v_pages, k_scale,
                                         v_scale, k_chunk, v_chunk,
                                         page_table, pos, done, *, mesh,
                                         dp_axes: tuple = ()):
    """Mesh placement of the chunk entry, as
    ``paged_attention_decode_sharded`` places the decode entry: the
    slot-carried operands (q, chunk pages, page table, pos, done) shard
    over the DP axes, the pool and scales replicate into each shard."""
    from jax.sharding import PartitionSpec as P

    b = _slot_axes(mesh, dp_axes, q.shape[0])
    slots = lambda a: P(b, *([None] * (a.ndim - 1)))  # noqa: E731
    repl = lambda a: P(*([None] * a.ndim))  # noqa: E731
    args = (q, k_pages, v_pages, k_scale, v_scale, k_chunk, v_chunk,
            page_table, pos.astype(jnp.int32), done.astype(bool))
    return jax.shard_map(
        paged_attention_decode_chunk, mesh=mesh,
        in_specs=(slots(q), repl(k_pages), repl(v_pages), repl(k_scale),
                  repl(v_scale), slots(k_chunk), slots(v_chunk),
                  slots(page_table), P(b), P(b)),
        out_specs=slots(q), check_vma=False,
    )(*args)
