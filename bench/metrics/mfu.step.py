"""The whole model step's share of the chip's bf16 peak over the traced
window: positions computed (chunked-prefill prompt rows and decoded
tokens) times the model's operations per position — 2 x every product one
position runs (the architecture's ``position_products``) — plus
attention's operations at each position's context (its
``attention_ops``), over window seconds x peak."""


def read(ctx):
    m, w = ctx.model, ctx.work
    n = w["prefill_rows"] + w["decoded"]
    if n <= 0 or ctx.window_s <= 0:
        return None
    arch = ctx.architecture
    params = sum(times * sum(K * N for K, N in prods)
                 for times, prods in arch.position_products(ctx))
    attn = sum(k * arch.attention_ops(ctx, c) for c, k in w["contexts"])
    ops = 2 * params * n + attn
    return 100.0 * ops / (ctx.window_s * ctx.peaks["bf16_flops"])
