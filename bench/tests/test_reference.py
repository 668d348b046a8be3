"""The plain reference against the program, on the CPU: the DS-CIM product
agrees with the served kernel and the LUT oracle, and the served tokens of
a whole window of the tiny cell read a gap under its limit."""
from __future__ import annotations

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np

import calibrate
import cells
import run
from references import dscim_linear


def test_dscim_linear_matches_program():
    """The counts are exact; the float32 dequantization sums the windows,
    so the outputs agree with the served kernel and the LUT oracle to
    float32 rounding."""
    from repro.core.dscim_layer import DSCIMLinear
    from repro.core.seed_search import calibrated_config
    with open(os.path.join(cells.HERE, "configs",
                           "qwen3-0.6b.dscim1.json")) as f:
        spec = json.load(f)["dscim"]
    cfg = calibrated_config("dscim1", 256, "paper")
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 1, (37, 384)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 1, (384, 300)), jnp.float32)
    got = np.asarray(jax.jit(dscim_linear.make_linear(
        spec, block_m=16, block_n=128))(x, w))
    kern = np.asarray(DSCIMLinear(cfg, mode="kernel", group_k=128)(x, w))
    with jax.default_matmul_precision("float32"):
        lut = np.asarray(DSCIMLinear(cfg, mode="lut", group_k=128)(x, w))
    tol = 4e-7 * np.abs(lut).max()
    np.testing.assert_allclose(got, kern, rtol=0, atol=tol)
    np.testing.assert_allclose(got, lut, rtol=0, atol=tol)


def test_served_tokens_gap_zero(tiny_cell):
    dev, _, _ = run.require_chip(tiny_cell, platform=None)
    seed = 31
    out = run.serve_window(tiny_cell, types.SimpleNamespace(
        seed=seed, seconds=3, trace=0), dev)
    r = calibrate.readings(tiny_cell, seed, out["records"], out["w1"],
                           control=False)
    assert r["positions"] > 20
    assert r["mean_gap"] <= tiny_cell.check["mean_logit_gap"]
