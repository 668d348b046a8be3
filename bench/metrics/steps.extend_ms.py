"""Device time of one chunked-prefill call (``jit_extend``, one chunk of
one slot), over the calls the trace holds."""


def read(ctx):
    n, t = ctx.summary.module_calls("jit_extend")
    if n == 0:
        return None
    return 1e3 * t / n
