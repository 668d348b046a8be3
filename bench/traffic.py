"""Length and token draws shared by the traffic generators (generators/).

Sizes (prompt and output lengths, arrival times) come from the mix file's
own ``size_seed``, so every run of a mix offers the same work; token ids
come from the run's ``--seed``, so runs differ in content only.
"""
from __future__ import annotations

import numpy as np


def lengths(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    """``n`` lengths from a mix file's length spec: ``{"fixed": v}`` or a
    lognormal ``{"median", "sigma", "min", "max"}`` rounded and clipped."""
    if "fixed" in spec:
        return np.full(n, int(spec["fixed"]), np.int64)
    x = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n))
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)


def token_ids(seed: int, vocab: int, sizes) -> list:
    """One int32 prompt per size, ids uniform below ``vocab``."""
    rng = np.random.default_rng([seed, 0x7E57])
    return [rng.integers(0, vocab, int(s), dtype=np.int32) for s in sizes]
