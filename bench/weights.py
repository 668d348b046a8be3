"""Random weights for a configuration, made on the device from the seed in
one jitted call, float32, in the parameter layout the program serves.

Every matrix (the embedding, which is also the tied head, and every
projection) holds +-``1/sqrt(fan_in)`` (the embedding +-``embed``) with
the signs balanced in each 128-row window of its contraction: half of
each window's entries are positive.  Gaussian weights leave the DS-CIM
estimator with an offset per output column that is larger than the
product it estimates (it grows with each window's absmax and operand
sums); summed over the layers it swamps what the tokens say, and greedy
decoding then serves a handful of tokens whatever the prompt.  With equal
magnitudes and balanced signs that offset is the same in every column and
the embedding ignores it.  The q/k norm scales (``qk_norm_scale``) sharpen
attention so that it reads particular positions rather than the mean of
the context, and the final norm's scale (``final_norm_scale``) sets the
logits' spread.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

WINDOW = 128


def key_of(seed: int):
    """A PRNG key from any non-negative seed (also past 32 bits)."""
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def balanced_signs(key, shape, axis: int):
    """+-1 of ``shape`` with exactly half of each ``WINDOW``-long window
    along ``axis`` positive."""
    lead = tuple(n for i, n in enumerate(shape) if i != axis)
    k = shape[axis]
    half = jnp.concatenate([jnp.ones(WINDOW // 2), -jnp.ones(WINDOW // 2)])
    s = jax.random.permutation(
        key, jnp.broadcast_to(half, lead + (k // WINDOW, WINDOW)), axis=-1,
        independent=True)
    return jnp.moveaxis(s.reshape(lead + (k,)), -1, axis)


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
