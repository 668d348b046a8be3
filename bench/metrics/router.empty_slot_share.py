"""Share of the decode-slot steps in the traced span that held no
request: the window delta of ``Router.stats()`` ``empty_slot_steps`` over
that of ``slot_steps``.  None where the span ran no segment or the
program does not count empty slot steps."""


def read(ctx):
    a, b = ctx.stats0, ctx.stats1
    if "empty_slot_steps" not in b:
        return None
    steps = b["slot_steps"] - a["slot_steps"]
    if steps <= 0:
        return None
    return 100.0 * (b["empty_slot_steps"] - a["empty_slot_steps"]) / steps
