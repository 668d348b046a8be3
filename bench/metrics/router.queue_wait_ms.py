"""Mean wait from ``Router.submit`` to admission into a slot over the
admissions in the traced span, in ms: the window deltas of
``Router.stats()`` ``queue_wait_s`` over ``admitted``.  None where the
span admitted nothing or the program does not count admissions."""


def read(ctx):
    a, b = ctx.stats0, ctx.stats1
    if "admitted" not in b:
        return None
    n = b["admitted"] - a["admitted"]
    if n <= 0:
        return None
    return 1e3 * (b["queue_wait_s"] - a["queue_wait_s"]) / n
