"""The whole model step's share of the chip's bf16 peak over the traced
window: positions computed (chunked-prefill prompt rows and decoded
tokens) times the model's operations per position — 2 x every matmul
parameter (attention projections, MLP, head) plus 4 x heads x head_dim x
context per layer for attention — over window seconds x peak."""


def read(ctx):
    m, w = ctx.model, ctx.work
    n = w["prefill_rows"] + w["decoded"]
    if n <= 0 or ctx.window_s <= 0:
        return None
    d, H, KV, D = m["d_model"], m["n_heads"], m["n_kv"], m["head_dim"]
    per_layer = d * H * D + 2 * d * KV * D + H * D * d + 3 * d * m["d_ff"]
    params = m["n_layers"] * per_layer + d * m["vocab_padded"]
    attn = sum(c * k for c, k in w["contexts"]) * 4 * H * D * m["n_layers"]
    ops = 2 * params * n + attn
    return 100.0 * ops / (ctx.window_s * ctx.peaks["bf16_flops"])
