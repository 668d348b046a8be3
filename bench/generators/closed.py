"""Closed loop: ``clients`` callers, each sending its next request when the
previous one has ended.  Client ``i`` starts once ``i * stagger_tokens``
tokens have been streamed to the clients together, so the first prefills
do not all land in one round, and land in the same rounds in every run.

Mix keys: ``clients`` (a number, or "slots" for one per decode slot),
``per_client`` (requests drawn per client, more than a run can use),
``stagger_tokens``, ``prompt`` and ``output`` (length specs, traffic.py).
"""
from __future__ import annotations

import numpy as np

import traffic


def make(mix: dict, *, seed: int, vocab: int, slots: int) -> dict:
    n_cl = slots if mix["clients"] == "slots" else int(mix["clients"])
    per = int(mix["per_client"])
    rng = np.random.default_rng(mix["size_seed"])
    p_len = traffic.lengths(rng, mix["prompt"], n_cl * per)
    o_len = traffic.lengths(rng, mix["output"], n_cl * per)
    toks = traffic.token_ids(seed, vocab, p_len)
    clients = []
    for c in range(n_cl):
        idx = range(c * per, (c + 1) * per)
        clients.append([{"prompt": toks[i], "max_new": int(o_len[i])}
                        for i in idx])
    return {"kind": "closed", "clients": clients,
            "start_tokens": [c * int(mix["stagger_tokens"])
                             for c in range(n_cl)]}
