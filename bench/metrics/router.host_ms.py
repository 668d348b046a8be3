"""Host time on the critical path per Router round: for each pair of
successive ``router.round`` span starts inside the traced span, the
interval less the time inside ``router.wait_*`` spans (blocking
device-to-host fetches) in it; the mean over the intervals, in ms.  It
holds the router's bookkeeping and the event loop between rounds.  None
where the trace holds fewer than two rounds (a program without the
spans)."""


def read(ctx):
    s = ctx.summary
    rounds = [(t, thread) for t, _, thread, name in s.host
              if name == "router.round" and s.t0 <= t <= s.t1]
    if not rounds:
        return None
    thread = rounds[0][1]
    starts = sorted(t for t, th in rounds if th == thread)
    if len(starts) < 2:
        return None
    waits = [(t, t + d) for t, d, th, name in s.host
             if th == thread and name.startswith("router.wait_")]
    host = 0
    for a, b in zip(starts, starts[1:]):
        inside = sum(max(0, min(e, b) - max(w, a)) for w, e in waits)
        host += b - a - inside
    return host / (len(starts) - 1) * 1e-6
