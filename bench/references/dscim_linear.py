"""Plain reference of the DS-CIM approximate matrix product.

Written from the paper's definition (DS-CIM, Sec. IV) and the estimator
constants a configuration file states; it shares no code or tables with
the program under test.

* Operands are int8: activations get one symmetric scale per row and
  128-row window of the contraction, weights one per window and column
  (``scale = absmax * (1/127)``, round to nearest, clip to +-127), each
  computed in the operand's own precision.
* A signed value ``x`` is shifted to ``(x + 128) >> k``; rows of a window
  form OR groups of ``4^k`` rows, row ``h`` owning block
  ``(h mod 2^k, (h mod 4^k) div 2^k)`` of the ``2^k x 2^k`` partition of
  the sampling square.
* ``L`` sampling points ``(u_t, v_t)`` come from two 8-bit LFSRs.  Each
  coordinate is folded ``k`` times (the upper half of the interval is
  mirrored onto the lower half, and that choice is one bit of the block
  code); a point fires for row ``h`` when its block is the row's and both
  folded coordinates lie below the shifted operands.
* The window's count ``C`` of fired points estimates the product:
  ``psum = 4^k * 65536 / L * C - 128 * sum(x) - 128 * sum(w + 128)``,
  and the windows are dequantized by their two scales and summed.

Counts are sums of {0,1} products, exact in bfloat16 operands with float32
accumulation, so this reference is exact integer arithmetic up to the
final dequantization.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

WINDOW = 128


def lfsr_fibonacci(length: int, seed: int, taps: int) -> np.ndarray:
    """8-bit Fibonacci LFSR: emit the state, shift left, feed back the
    parity of ``state & taps``."""
    s = seed % 256 or 1
    out = []
    for _ in range(length):
        out.append(s)
        s = ((s << 1) | (bin(s & taps).count("1") & 1)) & 0xFF
    return np.array(out, np.int64)


def lfsr_galois(length: int, seed: int, taps: int) -> np.ndarray:
    """8-bit Galois LFSR: emit the state, shift left, xor ``taps`` when the
    bit shifted out was set."""
    s = seed % 256 or 1
    out = []
    for _ in range(length):
        out.append(s)
        s = ((s << 1) & 0xFF) ^ (taps if s >> 7 else 0)
    return np.array(out, np.int64)


def fold(c: np.ndarray, k: int):
    """Reflected fold of 8-bit coordinates: (block code, local coordinate)."""
    code = np.zeros_like(c)
    size = 256
    for _ in range(k):
        half = size // 2
        hi = c >= half
        c = np.where(hi, size - 1 - c, c)
        code = code * 2 + hi
        size = half
    return code, c


@functools.lru_cache(maxsize=8)
def point_tables(kind: str, length: int, seed_u: int, seed_v: int,
                 taps_u: int, taps_v: int, k: int):
    """Per OR-group row, the folded coordinates of the points that fall in
    its block: ``tu, tv`` of shape (4^k, P), padded with the never-firing
    coordinate 256 (no shifted operand exceeds 255)."""
    gen = {"lfsr": lfsr_fibonacci, "galois": lfsr_galois}[kind]
    u = gen(length, seed_u, taps_u)
    v = gen(length, seed_v, taps_v)
    cu, lu = fold(u, k)
    cv, lv = fold(v, k)
    n = 1 << k
    rows = []
    for g in range(n * n):
        hit = (cu == g % n) & (cv == g // n)
        rows.append((lu[hit], lv[hit]))
    P = max(1, max(len(a) for a, _ in rows))
    tu = np.full((n * n, P), 256, np.int32)
    tv = np.full((n * n, P), 256, np.int32)
    for g, (a, b) in enumerate(rows):
        tu[g, :len(a)] = a
        tv[g, :len(b)] = b
    return tu, tv


def quantize(x, axis):
    """Symmetric int8 along ``axis``: ``scale = max(absmax, 1e-8) * (1/127)``
    and ``q = round(x / scale)`` clipped to +-127, the expression evaluated
    in ``x``'s own precision as it stands (the compiler decides which
    intermediate results it rounds, as it does for the program).  Returns
    q (int32) and the dequantization scale as float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) * (1.0 / 127.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int32)
    return q, scale.astype(jnp.float32)


def make_linear(spec: dict, block_m: int = 512, block_n: int = 4096):
    """``linear(x (M, K), w (K, N) f32) -> (M, N) f32`` for a
    configuration's ``dscim`` block.  Rows and columns are taken in blocks
    so that the bit planes fit; quantization is per row and per column, so
    the blocks change nothing."""
    k = int(spec["k"])
    pts = spec["points"]
    tu_np, tv_np = point_tables(pts["kind"], int(spec["length"]),
                                int(pts["seed_u"]), int(pts["seed_v"]),
                                int(pts["taps_u"]), int(pts["taps_v"]), k)
    G = tu_np.shape[0]
    est_scale = (4 ** k) * 65536.0 / int(spec["length"])
    if spec.get("trunc", "floor") != "floor":
        raise ValueError("the reference implements floor truncation only")

    def wbits_of(b):
        """(nw, 128, bn) shifted weights -> (nw, 128, P, bn) firing bits."""
        tv = jnp.asarray(tv_np)[jnp.arange(WINDOW) % G]      # (128, P)
        return (tv[None, :, :, None] < b[:, :, None, :]).astype(jnp.bfloat16)

    def block(xa, wbits, wsum, sw):
        """One block of rows against one block of columns -> (bm, bn)."""
        a, xsum, sx = xa                       # (bm,nw,128), (bm,nw), (bm,nw)
        tu = jnp.asarray(tu_np)[jnp.arange(WINDOW) % G]      # (128, P)
        abits = (tu[None, None] < a[..., None]).astype(jnp.bfloat16)
        c = jnp.einsum("muhp,uhpn->mun", abits, wbits,
                       preferred_element_type=jnp.float32)
        psum = est_scale * c - 128.0 * xsum[:, :, None] \
            - 128.0 * wsum[None]
        out = jnp.zeros(psum[:, 0].shape, jnp.float32)
        for u in range(psum.shape[1]):        # windows in order
            out = out + psum[:, u] * sx[:, u:u + 1] * sw[u][None]
        return out

    def linear(x, w):
        M, K = x.shape
        N = w.shape[1]
        nw = K // WINDOW
        xq, sx = quantize(x.reshape(M, nw, WINDOW), axis=2)   # (M,nw,128)
        wq, sw = quantize(w.reshape(nw, WINDOW, N), axis=1)   # (nw,128,N)
        bm = min(block_m, M)
        bn = min(block_n, N)
        pm, pn = (-M) % bm, (-N) % bn
        xa = ((xq + 128) >> k,
              jnp.sum(xq, axis=2).astype(jnp.float32), sx[:, :, 0])
        xa = tuple(jnp.pad(t, [(0, pm)] + [(0, 0)] * (t.ndim - 1))
                   .reshape((-1, bm) + t.shape[1:]) for t in xa)
        wb = ((wq + 128) >> k,
              jnp.sum(wq + 128, axis=1).astype(jnp.float32), sw[:, 0, :])
        wb = tuple(jnp.moveaxis(
            jnp.pad(t, [(0, 0)] * (t.ndim - 1) + [(0, pn)])
            .reshape(t.shape[:-1] + (-1, bn)), -2, 0) for t in wb)

        def col_block(wj):
            b, wsum, swj = wj
            bits = wbits_of(b)
            return jax.lax.map(lambda xi: block(xi, bits, wsum, swj), xa)

        out = jax.lax.map(col_block, wb)            # (nb, mb, bm, bn)
        out = jnp.transpose(out, (1, 2, 0, 3)).reshape(M + pm, N + pn)
        return out[:M, :N]

    return linear
