"""A dense decoder with a tied head (Qwen3): every layer holds the
attention projections ``wq``, ``wk``, ``wv``, ``wo`` with q/k norms and a
SwiGLU MLP; the embedding is also the head.  DS-CIM runs the MLP and the
head (the configuration's ``dscim.scope``).

Weights: random from the seed, float32, made on the device in one jitted
call, in the parameter layout the program serves (``weights.py``: signs
balanced per 128-row window of the contraction).  The embedding holds
+-``init.embed``; the q/k norm scales (``init.qk_norm_scale``) sharpen
attention so that it reads particular positions rather than the mean of
the context, and the final norm's scale (``init.final_norm_scale``) sets
the logits' spread.  The embedding ignores the estimator's column offset.

Work: what one position computes (``position_products``, ``mfu.step``),
the products the window's calls ran (``calls``,
``dscim_fused_mvm_roofline``) and attention's operations
(``attention_ops``), from the configuration's ``model`` block and the
reader's ``ctx.work``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from weights import balanced_signs, key_of


def make(model: dict, init: dict, seed: int):
    """``model``: a configuration file's ``model`` block (a tied head);
    ``init``: its ``init`` block (``embed``, ``qk_norm_scale``,
    ``final_norm_scale``)."""
    L, d, H, KV = (model[k] for k in ("n_layers", "d_model", "n_heads",
                                      "n_kv"))
    D, F, V = model["head_dim"], model["d_ff"], model["vocab_padded"]

    def build(key):
        ks = jax.random.split(key, 8)

        def mat(k, shape, fan_in):
            """(L, K, N): signs balanced along the contraction K."""
            return balanced_signs(k, shape, 1) * fan_in ** -0.5

        def ones(shape, v=1.0):
            return {"scale": jnp.full(shape, v, jnp.float32)}

        attn = {"wq": mat(ks[0], (L, d, H * D), d),
                "wk": mat(ks[1], (L, d, KV * D), d),
                "wv": mat(ks[2], (L, d, KV * D), d),
                "wo": mat(ks[3], (L, H * D, d), H * D),
                "q_norm": ones((L, D), init["qk_norm_scale"]),
                "k_norm": ones((L, D), init["qk_norm_scale"])}
        layers = {"attn": attn, "ln1": ones((L, d)), "ln2": ones((L, d)),
                  "mlp": {"w_gate": mat(ks[4], (L, d, F), d),
                          "w_up": mat(ks[5], (L, d, F), d),
                          "w_down": mat(ks[6], (L, F, d), F)}}
        # the head contracts the embedding along d
        embed = balanced_signs(ks[7], (V, d), 1) * init["embed"]
        return {"layers": layers, "embed": embed,
                "final_norm": ones((d,), init["final_norm_scale"])}

    return jax.jit(build)(key_of(seed))


def _layer(m: dict) -> list:
    """One layer's products (K, N, through DS-CIM): the attention
    projections, then the MLP."""
    d, H, KV, D, F = (m[k] for k in ("d_model", "n_heads", "n_kv",
                                     "head_dim", "d_ff"))
    return [(d, H * D, False), (d, KV * D, False), (d, KV * D, False),
            (H * D, d, False), (d, F, True), (d, F, True), (F, d, True)]


def position_products(ctx) -> list:
    """What one position computes through the model: ``(times, [(K, N),
    ...])`` blocks — every layer and the head."""
    m = ctx.model
    return [(m["n_layers"], [(K, N) for K, N, _ in _layer(m)]),
            (1, [(m["d_model"], m["vocab_padded"])])]


def attention_ops(ctx, context: int) -> int:
    """Attention's operations for one position at ``context`` over all
    layers: the scores and the weighted sum."""
    m = ctx.model
    return 4 * m["n_heads"] * m["head_dim"] * context * m["n_layers"]


def calls(ctx) -> list:
    """The window's calls: ``(count, [(times, [(M, K, N, dscim), ...]),
    ...])`` per kind of call.  Every decode step runs every layer and the
    head with M = live rows (the mean over the steps); a chunked-prefill
    call runs the layers with M = the chunk's real prompt rows, and the
    head only for the row that emits the first token."""
    m, w = ctx.model, ctx.work
    L, d, v = m["n_layers"], m["d_model"], m["vocab_padded"]

    def layer(M):
        return (L, [(M, K, N, ds) for K, N, ds in _layer(m)])

    out = []
    if w["decode_steps"] > 0 and w["decoded"] > 0:
        M = w["decoded"] / w["decode_steps"]
        out.append((w["decode_steps"], [layer(M), (1, [(M, d, v, True)])]))
    if w["extend_calls"] > 0 and w["prefill_rows"] > 0:
        out.append((w["extend_calls"],
                    [layer(w["prefill_rows"] / w["extend_calls"])]))
        out.append((w["emits"], [(1, [(1, d, v, True)])]))
    return out
