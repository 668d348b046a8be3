"""Share of decode-slot steps in the traced window that produced a token
for a live request: decoded tokens over segments * seg_len * slots, from
window deltas of the Router's counters."""


def read(ctx):
    w = ctx.work
    if w["decode_steps"] <= 0:
        return None
    return 100.0 * w["decoded"] / (w["decode_steps"] * ctx.serve["slots"])
