"""The comparison catches what it is there to catch, on the tiny cell
(bfloat16 compute, mean-gap limit 0.04 — conftest.py):

* the control — the reference one precision step lower (8-bit floats)
  put in the program's place — reads above the limit;
* a run with the timed path broken underneath comes out not correct: a
  token altered where the decode segment produces it; a segment that
  returns its state unchanged; half of the slots left out of a segment,
  their tokens taken from the other half.  (One chip: no exchange between
  chips to leave out.)
"""
from __future__ import annotations

import json
import types

import jax
import jax.numpy as jnp
import pytest

import calibrate
import run


def test_control_fails_limit(tiny_cell):
    dev, _, _ = run.require_chip(tiny_cell, platform=None)
    worst = []
    for seed in (41, 42, 43):
        out = run.serve_window(tiny_cell, types.SimpleNamespace(
            seed=seed, seconds=3, trace=0), dev)
        r = calibrate.readings(tiny_cell, seed, out["records"], out["w1"],
                               control=True)
        assert r["mean_gap"] <= tiny_cell.check["mean_logit_gap"]
        worst.append(r["ctrl_mean_gap"])
    assert min(worst) > tiny_cell.check["mean_logit_gap"]


def _altered(seg):
    def f(params, state):
        state, toks, lives, aux = seg(params, state)
        return state, (toks + 1) % 512, lives, aux
    return f


def _stuck(seg):
    def f(params, state):
        keep = jax.tree.map(jnp.copy, state)
        _, toks, lives, aux = seg(params, state)
        return keep, toks, lives, aux
    return f


def _half(seg):
    def f(params, state):
        state, toks, lives, aux = seg(params, state)
        h = toks.shape[1] // 2
        return state, toks.at[:, h:].set(toks[:, :h]), lives, aux
    return f


@pytest.mark.parametrize("fault", [_altered, _stuck, _half],
                         ids=["token_altered", "state_unchanged",
                              "half_batch"])
def test_broken_path_not_correct(tiny_cell, monkeypatch, capsys, fault):
    import repro.runtime.router as router_mod
    real = router_mod.make_segment_fn

    def broken(*a, **k):
        return fault(real(*a, **k))

    monkeypatch.setattr(router_mod, "make_segment_fn", broken)
    # a segment that never advances never finishes the warm-up request:
    # the run then fails, which is not correct either
    monkeypatch.setattr(run, "WARMUP_TIMEOUT_S", 60)
    dev, devs, _ = run.require_chip(tiny_cell, platform=None)
    args = types.SimpleNamespace(seed=51, seconds=3, trace=0)
    try:
        rc = run.measure_cell(tiny_cell, args, dev, devs, None)
    except TimeoutError:
        assert fault is _stuck
        return
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is False
