"""Fused batched DS-CIM MVM: one Pallas launch from float activations to
float output.

The staged path (``DSCIMLinear`` pre-fusion) drove the blocked-points kernel
through a per-window ``jax.vmap`` — one kernel launch per 128-row
quantization window — then applied the four sign-correction terms and the
per-window dequant scales in separate f32 HBM passes, materializing an
``(M, nw, N)`` psum tensor in HBM.  That throws away the macro's headline
property (stochastic bit traffic and partial sums never leave the array).

This kernel folds the window axis into the K grid dimension and finishes the
estimator inside the grid step:

    out[m,n] = Σ_u s_x[m,u] * s_w[u,n] * psum_u[m,n]
    psum_u   = scale*C_u - 128*Σx_u - 128*Σ(w_u+128)  (+ center-trunc terms)

Each K step is one whole quantization window (padded to 128 rows, the
lane width), so it computes that window's counts *and* corrections and
multiplies them by the window's dequant scales — no per-window psum ever
exists in HBM; the only HBM traffic is the int8 operands, the tiny scale
vectors and the final f32 output (same traffic class as a plain int8
matmul).  Every block is legal for the TPU compiler (Mosaic): the scale
operands come in whole along the window axis and the kernel selects the
step's window, and the point tables are tiled per window row outside the
kernel, so the body is plain 2-D compares and dots.

Further wins over the staged path:

* bit-expansion dots run on **bf16** operands with f32 accumulation — {0,1}
  values are exact in bf16, counts ≤ K·pmax << 2^24 stay exact in the f32
  accumulator, VMEM for the bit tiles halves and the MXU runs at its
  bf16-input rate (``bits="float32"`` kept for A/B benchmarking);
* leading batch dims map onto a **batch grid axis** (grid (B, M/bm, N/bn,
  nw)) instead of a reshape(-1, K) round-trip through HBM;
* blocked-points tables (disjointness theorem) shrink the contraction from
  K·L to K·pmax exactly as in ``dscim_mvm_blocked``: one (bm, 128) x
  (128, bn) {0,1} dot per point slot, pmax slots per window.

In-kernel padding uses the never-fire sentinel x = w = -128 (x' = w' = 0):
counts, Σ(w+128), Σa and Σb pad contributions are all zero by construction,
and the only non-zero pad term (-128·Σx picking up 128²·pad_g per window) is
cancelled by a compile-time per-window constant.

Serving entries (prepare-once weights, core/qweights.py):

* ``dscim_fused_mvm_prepared(x, qw, cfg)`` — the quantize-free hot path:
  the int8 window planes + per-window scales are resident (the CIM array's
  static storage); only activations are quantized per call, so the jitted
  decode step contains no weight quantization at all;
* ``dscim_fused_mvm(x, w, cfg)`` — float-weight wrapper, now literally
  ``prepare_linear_weight`` + the prepared entry (bit-identical by
  construction; kept for training/tests and one-shot calls);
* ``dscim_fused_mvm_sharded(x, qw, cfg, mesh)`` — multi-chip serving: the
  prepared weight and its scales shard on N over the 'model' mesh axis
  (shard_map; x broadcasts, output lands N-sharded).  Quantization windows
  live on the K axis, so every shard computes its output columns exactly —
  no collective in the MVM and bit-identical results to single-device.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.macro import DSCIMConfig
from repro.core.quant import quantize_int8
from repro.core.qweights import QuantizedLinearWeight, prepare_linear_weight

from .dscim_mvm_blocked import block_point_tables, dscim_counts_blocked
from .ops import ON_TPU, default_bits, round_up as _round_up

__all__ = ["dscim_fused_mvm", "dscim_fused_mvm_prepared",
           "dscim_fused_mvm_sharded", "quantize_activations_windowed",
           "dscim_windowed_vmap_mvm", "KERNEL_NAME"]

# the Pallas kernel's name: it labels the kernel's custom call in compiled
# programs (and its events in profiler traces)
KERNEL_NAME = "dscim_fused_mvm"


def window_rows(g: int) -> int:
    """Rows of one padded quantization window: a grid step takes one whole
    window, lane-aligned for the chip (g = 128, the serving window, pads
    nothing)."""
    return _round_up(g, 128)


def _kernel(x_ref, w_ref, tu_ref, tv_ref, sx_ref, sw_ref, out_ref, *,
            k: int, pmax: int, scale: float, win_const: float,
            trunc_center: bool, bits: str):
    """One grid step: the counts and corrections of one whole quantization
    window, dequantized by that window's scales, accumulated into out."""
    kk = pl.program_id(3)

    @pl.when(kk == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...].astype(jnp.int32)           # (bm, gp) signed int8 values
    w = w_ref[...].astype(jnp.int32)           # (gp, bn)
    a = (x + 128) >> k                         # shifted unsigned, [0, S)
    b = (w + 128) >> k

    # one 2-D {0,1} dot per point slot: the row -> block wiring restarts at
    # every window and a step is one whole window, so the point tables come
    # in pre-tiled per row (tu (pmax, gp), tv (gp, pmax)); slot p of row r
    # fires where its local coordinate is below the shifted operand
    bdt = jnp.dtype(bits)
    counts = jnp.zeros((x.shape[0], w.shape[1]), jnp.float32)
    for p in range(pmax):
        abit = (tu_ref[p:p + 1, :] < a).astype(bdt)        # (bm, gp)
        wbit = (tv_ref[:, p:p + 1] < b).astype(bdt)        # (gp, bn)
        counts = counts + jax.lax.dot_general(
            abit, wbit, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    psum = scale * counts
    psum = psum - 128.0 * jnp.sum(x, axis=1, keepdims=True).astype(jnp.float32)
    psum = psum - 128.0 * jnp.sum(w + 128, axis=0,
                                  keepdims=True).astype(jnp.float32)
    if trunc_center:
        delta = (2 ** k - 1) / 2.0
        psum = psum + (2 ** k) * delta * (
            jnp.sum(a, axis=1, keepdims=True)
            + jnp.sum(b, axis=0, keepdims=True)).astype(jnp.float32)
    if win_const:
        # center-trunc constant + pad-sentinel cancellation
        psum = psum + jnp.float32(win_const)
    # the scale blocks span every window; pick this window's column / row
    sx = sx_ref[...]                                       # (bm, nw)
    sw = sw_ref[...]                                       # (nw, bn)
    sxk = jnp.sum(jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, sx.shape, 1) == kk, sx, 0.0),
        axis=1, keepdims=True)
    swk = jnp.sum(jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, sw.shape, 0) == kk, sw, 0.0),
        axis=0, keepdims=True)
    out_ref[...] += psum * sxk * swk


def _tiled_tables(cfg: DSCIMConfig, gp: int):
    """Point tables tiled to one padded window's rows: row r of a window
    reads block r % G.  Pad rows (r >= g) never fire whatever they read —
    their operands are the x = w = -128 sentinel."""
    tu, tv, pmax = block_point_tables(cfg)
    blk = np.arange(gp) % cfg.group
    return tu[blk].T.copy(), tv[blk], pmax     # (pmax, gp), (gp, pmax)


@functools.partial(jax.jit, static_argnames=(
    "cfg", "g", "bm", "bn", "bits", "interpret"))
def _fused_call(xq, wq, sx, sw, cfg: DSCIMConfig, *, g: int, bm: int,
                bn: int, bits: str, interpret: bool):
    """xq (B, Mp, nw*gp) int8, wq (nw*gp, Np) int8, sx (B, Mp, nw) f32,
    sw (nw, Np) f32 -> (B, Mp, Np) f32."""
    B, Mp, KL = xq.shape
    Np = wq.shape[1]
    gp = window_rows(g)
    nw = KL // gp
    tu_np, tv_np, pmax = _tiled_tables(cfg, gp)
    tu, tv = jnp.asarray(tu_np), jnp.asarray(tv_np)
    delta = (2 ** cfg.k - 1) / 2.0
    win_const = (g * delta * delta if cfg.trunc == "center" else 0.0) \
        - 128.0 * 128.0 * (gp - g)
    kernel = functools.partial(
        _kernel, k=cfg.k, pmax=pmax, scale=cfg.scale, win_const=win_const,
        trunc_center=(cfg.trunc == "center"), bits=bits)
    return pl.pallas_call(
        kernel,
        grid=(B, Mp // bm, Np // bn, nw),
        in_specs=[
            pl.BlockSpec((None, bm, gp), lambda b, i, j, kk: (b, i, kk)),
            pl.BlockSpec((gp, bn), lambda b, i, j, kk: (kk, j)),
            pl.BlockSpec((pmax, gp), lambda b, i, j, kk: (0, 0)),
            pl.BlockSpec((gp, pmax), lambda b, i, j, kk: (0, 0)),
            pl.BlockSpec((None, bm, nw), lambda b, i, j, kk: (b, i, 0)),
            pl.BlockSpec((nw, bn), lambda b, i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((None, bm, bn), lambda b, i, j, kk: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, Mp, Np), jnp.float32),
        interpret=interpret, name=KERNEL_NAME,
    )(xq, wq, tu, tv, sx, sw)


def quantize_activations_windowed(x, nw: int, g: int):
    """Float x (..., K) -> per-window int8 activations (DSCIMLinear
    semantics: pad K with float zeros to nw*g *before* quantizing, one scale
    per (row, window)).  Returns a QuantizedTensor with q (..., nw, g)."""
    K = x.shape[-1]
    pad = nw * g - K
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return quantize_int8(x.reshape(*x.shape[:-1], nw, g), axis=-1)


def dscim_fused_mvm_prepared(x, qw: QuantizedLinearWeight, cfg: DSCIMConfig,
                             *, bm: int | None = None, bn: int | None = None,
                             bits: str | None = None,
                             interpret: bool | None = None,
                             tune: bool = False):
    """Quantize-free fused DS-CIM linear: x (..., K) float + prepared weight
    -> (..., N) f32.

    The serving hot path: ``qw`` holds the resident int8 window planes and
    per-window scales (prepared once, core/qweights.py), so the only
    quantization traced here is the per-call activation quantization — no
    ``quantize_int8`` over (K, N) appears in the jitted step.  Single Pallas
    launch covering all quantization windows, sign-correction terms and
    dequant scales; leading batch dims ride a batch grid axis.  ``bits``
    defaults to bf16 on TPU (halved VMEM, doubled MXU rate; {0,1} operands
    are exact) and f32 under interpret mode, where CPU bf16 emulation would
    dominate the runtime.  ``tune=True`` consults the tile autotuner
    (kernels/autotune.py).
    """
    interpret = (not ON_TPU) if interpret is None else interpret
    bits = bits or default_bits(interpret)
    lead = x.shape[:-1]
    K = x.shape[-1]
    if K != qw.k_orig:
        raise ValueError(f"x K={K} vs prepared weight K={qw.k_orig}")
    nw, g, N = qw.nw, qw.g, qw.n
    # native batch: keep the last lead dim as the M grid rows, fold any
    # extra leading dims into the batch grid axis (no flatten through M)
    if x.ndim <= 2:
        x3 = x.reshape(1, -1 if x.ndim == 2 else 1, K)
    else:
        B = math.prod(lead[:-1])
        x3 = x.reshape(B, lead[-1], K)
    B, M, _ = x3.shape

    if tune:
        from . import autotune
        bm, bn = autotune.fused_tiles(
            (B, M, K, N), cfg, g, interpret=interpret, bits=bits)
    if bm is None:
        # decode-shape (skinny-M GEMV) default: a pad-free bm=M tile — the
        # M grid dim collapses to 1 and no dead rows are computed.  Only
        # interpret mode may take it: the chip needs sublane-aligned rows.
        bm = M if (M <= 16 and interpret) else min(128, _round_up(M, 8))
    # a lane-aligned column tile, or the whole (8-padded) N when it is small
    bn = bn or min(128, _round_up(N, 8))

    xq = quantize_activations_windowed(x3, nw, g)       # (B,M,nw,1) scales
    gp = window_rows(g)
    # never-fire sentinel padding (x' = w' = 0) along the window axis …
    x4 = jnp.pad(xq.q, ((0, 0), (0, 0), (0, 0), (0, gp - g)),
                 constant_values=-128)
    w4 = jnp.pad(qw.q, ((0, 0), (0, gp - g), (0, 0)), constant_values=-128)
    x2 = x4.reshape(B, M, nw * gp)
    w2 = w4.reshape(nw * gp, N)
    sx = xq.scale.reshape(B, M, nw)
    sw = qw.scale
    # … and along M/N (pad rows/cols never read back; scales padded with 0)
    padm, padn = _round_up(M, bm) - M, _round_up(N, bn) - N
    if padm:
        x2 = jnp.pad(x2, ((0, 0), (0, padm), (0, 0)), constant_values=-128)
        sx = jnp.pad(sx, ((0, 0), (0, padm), (0, 0)))
    if padn:
        w2 = jnp.pad(w2, ((0, 0), (0, padn)), constant_values=-128)
        sw = jnp.pad(sw, ((0, 0), (0, padn)))
    out = _fused_call(x2.astype(jnp.int8), w2.astype(jnp.int8), sx, sw, cfg,
                      g=g, bm=bm, bn=bn, bits=bits, interpret=interpret)
    return out[:, :M, :N].reshape(*lead, N)


def dscim_fused_mvm(x, w, cfg: DSCIMConfig, *, group_k: int | None = 128,
                    bm: int | None = None, bn: int | None = None,
                    bits: str | None = None,
                    interpret: bool | None = None, tune: bool = False):
    """Fused DS-CIM linear from float weights: x (..., K), w (K, N) float
    -> (..., N) f32.  Exactly ``prepare_linear_weight`` + the prepared
    entry, so it is bit-identical to the serve path by construction."""
    qw = prepare_linear_weight(w, group_k)
    return dscim_fused_mvm_prepared(x, qw, cfg, bm=bm, bn=bn, bits=bits,
                                    interpret=interpret, tune=tune)


def dscim_fused_mvm_sharded(x, qw: QuantizedLinearWeight, cfg: DSCIMConfig,
                            mesh, *, axis: str = "model",
                            batch_axes: tuple = (), **kw):
    """Model-axis sharded fused MVM (multi-chip serving, ROADMAP item).

    The prepared weight's output columns tile over the ``axis`` mesh axis —
    ``q`` (nw, g, N) and ``scale`` (nw, N) both shard on N, and the output
    lands N-sharded (no collective: quantization windows live on the local
    K axis, the StoX-Net/Stoch-IMC array-banking decomposition).
    ``batch_axes``: DP mesh axes the leading dim of x/out additionally
    shards over (when x has a batch dim and it divides) — on a
    data x model serving mesh each data group then computes only its batch
    slice instead of redoing the full batch; with no batch axes or a
    non-dividing batch, x broadcasts.  Bit-identical to the single-device
    prepared path either way (per-element math is placement-invariant).

    When N does not divide over the axis, the call degrades to a replicated
    shard_map (every device computes the full output — still correct, still
    a legal Pallas-under-mesh placement) instead of failing, and warns once
    per trace: the redundant compute + resident planes on every device are
    a misconfiguration an operator should see.  Serving meshes should pick
    an axis size dividing every DS-CIM matrix's N.
    """
    import math as _math

    from jax.sharding import PartitionSpec as P


    nshard = mesh.shape[axis]
    t = axis if qw.n % nshard == 0 else None
    if t is None:
        import warnings
        warnings.warn(
            f"dscim_fused_mvm_sharded: N={qw.n} not divisible by mesh axis "
            f"{axis!r}={nshard}; replicating — every device computes the "
            "full output (no speedup, N-fold redundant work/memory)",
            stacklevel=2)
    # leading batch dim over the DP axes (x ndim >= 2 keeps K unsharded)
    b = None
    if batch_axes and x.ndim >= 2:
        bsize = _math.prod(mesh.shape[a] for a in batch_axes)
        if x.shape[0] % bsize == 0:
            b = tuple(batch_axes)
    qspec = P(*([None] * (qw.q.ndim - 1)), t)
    sspec = P(*([None] * (qw.scale.ndim - 1)), t)
    xdims = [b] + [None] * (x.ndim - 1)       # b is None unless it divides
    # out has x's ndim with K replaced by N; 1D x -> 1D out (N,)
    odims = [b] + [None] * (x.ndim - 2) + [t] if x.ndim >= 2 else [t]
    xspec = P(*xdims)
    ospec = P(*odims)

    def inner(xl, ql, sl):
        qwl = QuantizedLinearWeight(ql, sl, qw.k_orig, qw.group_k)
        return dscim_fused_mvm_prepared(xl, qwl, cfg, **kw)

    return jax.shard_map(inner, mesh=mesh,
                         in_specs=(xspec, qspec, sspec), out_specs=ospec,
                         check_vma=False)(x, qw.q, qw.scale)


def dscim_windowed_vmap_mvm(x, w, cfg: DSCIMConfig, *,
                            group_k: int | None = 128,
                            interpret: bool | None = None):
    """The pre-fusion staged path, kept as the perf A/B baseline: one
    blocked-kernel launch per window via vmap, psum (M, nw, N) staged in
    HBM, corrections and dequant applied in separate f32 passes."""
    interpret = (not ON_TPU) if interpret is None else interpret
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = w.shape[-1]
    x2 = x.reshape(-1, K)
    wq = prepare_linear_weight(w, group_k)
    nw, g = wq.nw, wq.g
    xq = quantize_activations_windowed(x2, nw, g)
    xw = xq.q.astype(jnp.int32)                    # (M, nw, g)
    ww = wq.q.astype(jnp.int32)                    # (nw, g, N)
    M = xw.shape[0]
    bm = min(128, _round_up(M, 8))
    bn = min(128, _round_up(N, 8))
    bk = min(16, g)
    gp = _round_up(g, bk)

    def one_window(xg, wg):                        # (M, g), (g, N)
        xp = jnp.pad(xg, ((0, _round_up(M, bm) - M), (0, gp - g)),
                     constant_values=-128)
        wp = jnp.pad(wg, ((0, gp - g), (0, _round_up(N, bn) - N)),
                     constant_values=-128)
        counts = dscim_counts_blocked(
            xp.astype(jnp.int8), wp.astype(jnp.int8), cfg, bm=bm, bn=bn,
            bk=bk, interpret=interpret)[:M, :N]
        psum = cfg.scale * counts \
            - 128.0 * jnp.sum(xg, axis=-1, keepdims=True) \
            - 128.0 * jnp.sum(wg + 128, axis=0, keepdims=True)
        if cfg.trunc == "center":
            delta = (2 ** cfg.k - 1) / 2.0
            a = (xg + 128) >> cfg.k
            b = (wg + 128) >> cfg.k
            psum = psum + (2 ** cfg.k) * delta * (
                jnp.sum(a, axis=-1, keepdims=True)
                + jnp.sum(b, axis=0, keepdims=True)) + g * delta * delta
        return psum

    psum = jax.vmap(one_window, in_axes=(1, 0), out_axes=1)(xw, ww)
    out = jnp.einsum("mun,mu,un->mn", psum, xq.scale.reshape(-1, nw),
                     wq.scale.reshape(nw, N))
    return out.reshape(*lead, N).astype(jnp.float32)
