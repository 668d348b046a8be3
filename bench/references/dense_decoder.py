"""Plain reference of a dense decoder LM served with DS-CIM matmuls and an
int8 paged KV cache: Qwen3 (RMSNorm, q/k norm, grouped-query attention,
tied embedding).

Written from the published architecture and the configuration file; it
imports nothing of the program.  No kernels, no cache, no batching.  It
computes in the precision the configuration states (``precision.compute``,
bfloat16):

* the embedding, the residual stream, every normalized activation and the
  attention projections (weights and outputs) are held in the compute
  precision; normalization, rotary embedding and attention scores are
  worked out in float32 and rounded to it;
* the MLP matmuls and the LM head are DS-CIM products
  (``dscim_linear.py``) of int8 operands: MLP inputs quantized in the
  compute precision, the head's input in float32, weights from their
  float32 values;
* keys and values are cached in bfloat16 and held as int8 pages of
  ``page_size`` positions with one scale per (page, kv head), the page
  still being filled in bfloat16: query position ``t`` reads key ``j``
  dequantized when ``j``'s page ends before ``t``'s page begins.

Several sequences are packed into one row of ``n_tokens`` positions; a
position attends only within its own sequence, and rotary positions and
pages restart with each sequence.

``control=True`` rounds every value held in the compute precision
(bfloat16) one step lower first: to 8-bit floats (e4m3, one scale per
row, or per column of a weight).  That is the comparison's control.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import dscim_linear

HI = jax.lax.Precision.HIGHEST
NEG = -1e30


def _fp8(x, axis=-1):
    """Round to e4m3 with one scale per slice along ``axis``."""
    x = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) \
        / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _norm(model, x, scale):
    """RMSNorm with scale, in float32, returned in ``x``'s precision."""
    xf = x.astype(jnp.float32)
    y = xf * (jnp.mean(xf * xf, -1, keepdims=True)
              + model["norm_eps"]) ** -0.5
    return (y * scale).astype(x.dtype)


def _rope(x, pos, theta):
    """Half-rotation rotary embedding of x (T, H, D) at positions pos (T,),
    in float32, returned in ``x``'s precision."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           -1).astype(x.dtype)


def _page_quant(x, page_id):
    """x (T, KV, D): each (page, kv head) quantized to int8 with its own
    absmax scale, then dequantized to float32.  ``page_id`` (T,) labels
    positions with a page unique across the packed row."""
    T = x.shape[0]
    xf = x.astype(jnp.float32)
    amax = jax.ops.segment_max(jnp.max(jnp.abs(xf), -1), page_id,
                               num_segments=T)            # (T, KV)
    scale = jnp.maximum(amax[page_id], 1e-8) / 127.0      # (T, KV)
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127)
    return q * scale[..., None]


def build(cfg: dict, *, control: bool = False, q_block: int = 512):
    """Returns ``run(params, tokens, seg, pos, rows, targets, alt)``:

    ``tokens``/``seg``/``pos`` (T,) — packed ids, sequence index (-1 pad)
    and position within the sequence; ``rows`` (R,) — positions whose next
    token is checked, ``targets``/``alt`` (R,) — token ids to read there.
    Returns per check row ``(best, at_target, at_alt, argmax)``: the
    largest logit, the logits of ``targets`` and ``alt``, and the argmax.
    """
    model = cfg["model"]
    ps = int(cfg["serve"]["page_size"])
    if cfg["precision"]["compute"] != "bfloat16" or model["norm"] != \
            "rmsnorm" or not model["tie_embeddings"]:
        raise ValueError("the reference covers bfloat16 compute, RMSNorm "
                         "and a tied head")
    cdt = jnp.bfloat16
    lin = dscim_linear.make_linear(cfg["dscim"])
    low = _fp8 if control else None
    H, KV, D = model["n_heads"], model["n_kv"], model["head_dim"]
    rep = H // KV

    def c(x, axis=-1):
        """A value held in the compute precision."""
        if low is not None:
            x = low(x, axis)
        return x.astype(cdt)

    def mm(x, w):
        return c(jnp.matmul(c(x), c(w, axis=0), precision=HI,
                            preferred_element_type=jnp.float32))

    def attention(lp, h, seg, pos):
        T = h.shape[0]
        q = mm(h, lp["wq"]).reshape(T, H, D)
        k = mm(h, lp["wk"]).reshape(T, KV, D)
        v = mm(h, lp["wv"]).reshape(T, KV, D)
        if model["qk_norm"]:
            q = c(_norm(model, q, lp["q_norm"]["scale"]))
            k = c(_norm(model, k, lp["k_norm"]["scale"]))
        q = c(_rope(q, pos, model["rope_theta"])).astype(jnp.float32)
        k = _bf16(c(_rope(k, pos, model["rope_theta"])))   # cache: bf16
        v = _bf16(v)
        page = pos // ps
        page_id = jnp.cumsum(jnp.concatenate(
            [jnp.zeros(1, jnp.int32),
             ((seg[1:] != seg[:-1]) | (page[1:] != page[:-1]))
             .astype(jnp.int32)]))
        kq = _page_quant(k, page_id)
        vq = _page_quant(v, page_id)
        qg = q.reshape(T, KV, rep, D)
        nb = T // q_block

        def block(i):
            sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * q_block,
                                                        q_block)
            qb, sb, pb, gb = sl(qg), sl(seg), sl(pos), sl(page)
            same = (sb[:, None] == seg[None]) & (pb[:, None] >= pos[None])
            old = gb[:, None] > page[None]            # key page is closed
            s_f = jnp.einsum("tgrd,jgd->tgrj", qb, k, precision=HI)
            s_q = jnp.einsum("tgrd,jgd->tgrj", qb, kq, precision=HI)
            s = jnp.where(old[:, None, None], s_q, s_f) * D ** -0.5
            s = jnp.where(same[:, None, None], s, NEG)
            p = jax.nn.softmax(s, axis=-1)
            po = jnp.where(old[:, None, None], p, 0.0)
            o = jnp.einsum("tgrj,jgd->tgrd", p - po, v, precision=HI) \
                + jnp.einsum("tgrj,jgd->tgrd", po, vq, precision=HI)
            return o.reshape(q_block, H * D)

        o = jax.lax.map(block, jnp.arange(nb)).reshape(T, H * D)
        return mm(c(o), lp["wo"])

    def layer(x, lp, seg, pos):
        ln1, ln2 = lp["ln1"]["scale"], lp["ln2"]["scale"]
        x = c(x + attention(lp["attn"], c(_norm(model, x, ln1)), seg, pos))
        h = c(_norm(model, x, ln2))
        gate = c(lin(h, lp["mlp"]["w_gate"]))
        up = c(lin(h, lp["mlp"]["w_up"]))
        a = c(jax.nn.silu(gate) * up)
        return c(x + c(lin(a, lp["mlp"]["w_down"])))

    @jax.jit
    def run(params, tokens, seg, pos, rows, targets, alt):
        x = c(params["embed"][tokens])

        def body(x, lp):
            return layer(x, lp, seg, pos), None

        x, _ = jax.lax.scan(body, x, params["layers"])
        fs = params["final_norm"]["scale"]
        xr = c(_norm(model, x[rows], fs)).astype(jnp.float32)
        lg = lin(xr, params["embed"].T)              # (R, Vp)
        pick = lambda ids: jnp.take_along_axis(lg, ids[:, None], 1)[:, 0]
        return (jnp.max(lg, -1), pick(targets), pick(alt),
                jnp.argmax(lg, -1).astype(jnp.int32))

    return run
