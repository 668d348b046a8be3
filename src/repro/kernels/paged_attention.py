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
partitioned): ``paged_attention_decode_sharded`` shards the batch-carried
operands (q, tails, page table, pos) over the DP axes and gathers the
page pool whole per shard — under continuous batching any slot may
reference any physical page, so the pool cannot shard with the slots.
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
           "use_paged_kernel", "KERNEL_NAME"]

NEG_INF = -1e30
# the Pallas kernel's name: it labels the kernel's custom call in compiled
# programs (and its events in profiler traces)
KERNEL_NAME = "paged_attention_decode"
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
        hid = jax.lax.broadcasted_iota(jnp.int32, (1, gh, 1), 1)
        sc = jnp.zeros((1, gh, 1), jnp.float32)
        for h in range(gh):
            sc = jnp.where(hid == h, s_ref[0, 0, 0, h], sc)
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
    import math

    from jax.sharding import PartitionSpec as P


    b = None
    if dp_axes:
        dp_size = math.prod(mesh.shape[a] for a in dp_axes)
        if q.shape[0] % dp_size == 0:
            b = tuple(dp_axes)
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
