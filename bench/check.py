"""The comparison that decides ``correct``.

After the window has closed and the program's state is freed, a sample of
the requests the window finished — drawn from the seed, the one with the
most served tokens always in it — is run through the configuration's
plain reference (``references/``), prompt and served tokens packed into
one row.  At every position that produced a served token the reference
reads the largest logit and the logit of the served token; the number
compared is the mean gap between the two over the sample.  Greedy
decoding serves the largest logit, so a sound program reads a gap near 0
and departs from it only where rounding moves a near-tie.  (The widest
gap is not compared: the DS-CIM estimator turns last-bit differences into
whole int8 steps, so over some hundreds of positions a sound run's widest
gap comes within 1.5x of the control's, while the mean separates them
3.4x; PERF.md.)
"""
from __future__ import annotations

import importlib

import numpy as np


def reference_module(cfg: dict):
    """The configuration's plain reference, ``references/<name>.py``."""
    return importlib.import_module(f"references.{cfg['reference']}")


def select(records, w1: float, seed: int, n_tokens: int, n_rows: int,
           want_rows: int) -> list:
    """Finished requests, the longest (most served tokens) first and the
    rest in an order drawn from the seed, kept while they fit ``n_tokens``
    packed positions and ``n_rows`` checked positions, until ``want_rows``
    are checked."""
    done = [r for r in records
            if r.status == "ok" and r.tokens and r.times[-1] < w1]
    if not done:
        return []
    longest = max(range(len(done)),
                  key=lambda i: (len(done[i].tokens), len(done[i].prompt)))
    rng = np.random.default_rng([seed, 0xC4EC])
    order = [longest] + [i for i in rng.permutation(len(done))
                         if i != longest]
    out, tok, rows = [], 0, 0
    for i in order:
        r = done[i]
        need = len(r.prompt) + len(r.tokens) - 1
        if tok + need > n_tokens or rows + len(r.tokens) > n_rows:
            continue
        out.append(r)
        tok += need
        rows += len(r.tokens)
        if rows >= want_rows:
            break
    return out


def pack(sample, n_tokens: int, n_rows: int) -> dict:
    """One packed row: ids, sequence index (-1 for padding), position in
    the sequence; the checked positions and the served token at each."""
    tokens = np.zeros(n_tokens, np.int32)
    seg = np.full(n_tokens, -1, np.int32)
    pos = np.arange(n_tokens, dtype=np.int32)
    rows = np.zeros(n_rows, np.int32)
    targets = np.zeros(n_rows, np.int32)
    valid = np.zeros(n_rows, bool)
    at = nr = 0
    for i, r in enumerate(sample):
        seq = np.concatenate([np.asarray(r.prompt, np.int32),
                              np.asarray(r.tokens[:-1], np.int32)])
        n = len(seq)
        tokens[at:at + n] = seq
        seg[at:at + n] = i
        pos[at:at + n] = np.arange(n)
        k = len(r.tokens)
        rows[nr:nr + k] = at + len(r.prompt) - 1 + np.arange(k)
        targets[nr:nr + k] = r.tokens
        valid[nr:nr + k] = True
        at += n
        nr += k
    if at < n_tokens:
        pos[at:] = np.arange(n_tokens - at)
    return {"tokens": tokens, "seg": seg, "pos": pos, "rows": rows,
            "targets": targets, "valid": valid}


def gaps(run, params, packed: dict, alt=None):
    """Run the reference; returns (gap of the served token, gap of ``alt``)
    per valid checked position."""
    import jax.numpy as jnp
    alt = packed["targets"] if alt is None else alt
    best, at_t, at_a, _ = run(params, *(jnp.asarray(packed[k]) for k in
                                        ("tokens", "seg", "pos", "rows",
                                         "targets")), jnp.asarray(alt))
    v = packed["valid"]
    return (np.asarray(best - at_t)[v].astype(np.float64),
            np.asarray(best - at_a)[v].astype(np.float64))


def control_tokens(run_ctrl, params, packed: dict) -> np.ndarray:
    """The token the control (the reference one precision step lower) puts
    first at each checked position."""
    import jax.numpy as jnp
    out = run_ctrl(params, *(jnp.asarray(packed[k]) for k in
                             ("tokens", "seg", "pos", "rows", "targets",
                              "targets")))
    return np.asarray(out[3])
