"""Open loop with bursts: independent users whose requests come due on a
schedule whatever the server does.  Bursts of geometric size (mean
``1 / burst_p``) arrive at exponential gaps, scaled so
that the mean request rate is ``rate`` per second — the arrival process of
``benchmarks/loadtest.make_trace`` at full-size lengths.

Mix keys: ``rate`` (requests/s), ``burst_p``, ``horizon_s`` (seconds of
schedule to draw, more than a run can use), ``prompt`` and ``output``
(length specs, traffic.py).
"""
from __future__ import annotations

import numpy as np

import traffic


def make(mix: dict, *, seed: int, vocab: int, slots: int) -> dict:
    rng = np.random.default_rng(mix["size_seed"])
    p = float(mix["burst_p"])
    mean_gap = 1.0 / (p * float(mix["rate"]))
    due = []
    t = 0.0
    while t < float(mix["horizon_s"]):
        burst = int(rng.geometric(p))
        t += float(rng.exponential(mean_gap))
        due += [t] * burst
    n = len(due)
    p_len = traffic.lengths(rng, mix["prompt"], n)
    o_len = traffic.lengths(rng, mix["output"], n)
    toks = traffic.token_ids(seed, vocab, p_len)
    return {"kind": "open",
            "requests": [{"due": due[i], "prompt": toks[i],
                          "max_new": int(o_len[i])} for i in range(n)]}
