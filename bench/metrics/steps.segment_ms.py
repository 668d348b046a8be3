"""Device time of the jitted decode segment (``jit_segment``) per decode
step, over the segments the trace holds."""


def read(ctx):
    n, t = ctx.summary.module_calls("jit_segment")
    if n == 0:
        return None
    return 1e3 * t / (n * ctx.serve["seg_len"])
