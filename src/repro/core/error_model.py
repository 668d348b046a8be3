"""Calibrated statistical DS-CIM error injection (fast big-model path).

The exact backends (lut/bitmatmul) emulate the macro bit-exactly but cost a
K-scan of gathers or an L-times-expanded matmul.  For model-level accuracy
sweeps over millions of MVMs, we inject a Gaussian error with moments
*measured from the exact LUT process* (the paper itself evaluates networks by
"adding the DS-CIM error pattern to the MVM results", Sec. V).

Per-row error moments (mu1, sig1) are estimated once per macro config by
Monte-Carlo over the data distribution; a K-length accumulation then has
mean K*mu1 and std sqrt(K)*sig1 (rows are sampled from disjoint regions —
cross-row covariance is zero by the remapping property; cross-*output*
correlation through shared activations is ignored, documented approximation).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from .macro import DSCIMMacro

__all__ = ["ErrorModel"]


@dataclasses.dataclass(frozen=True)
class ErrorModel:
    mu1: float      # mean per-row psum error (int units)
    sig1: float     # std per-row psum error
    name: str = "dscim-errmodel"

    @staticmethod
    def from_macro(macro: DSCIMMacro, n_samples: int = 200_000,
                   seed: int = 0, dist: str = "uniform") -> "ErrorModel":
        """Measure per-row error moments of scale*count(a,w) - x*w + corr."""
        cfg = macro.cfg
        rng = np.random.default_rng(seed)
        if dist == "uniform":
            x = rng.integers(-128, 128, n_samples).astype(np.int64)
            w = rng.integers(-128, 128, n_samples).astype(np.int64)
        elif dist == "gaussian":
            x = np.clip(np.round(rng.normal(0, 42, n_samples)), -128, 127).astype(np.int64)
            w = np.clip(np.round(rng.normal(0, 42, n_samples)), -128, 127).astype(np.int64)
        else:
            raise ValueError(dist)
        k = cfg.k
        a = (x + 128) >> k
        b = (w + 128) >> k
        g = rng.integers(0, cfg.group, n_samples)
        counts = macro.lut_np[g, a, b].astype(np.float64)
        est = cfg.scale * counts - 128.0 * x - 128.0 * (w + 128)
        if cfg.trunc == "center":
            delta = (2 ** k - 1) / 2.0
            est = est + (2 ** k) * delta * (a + b) + delta * delta
        err = est - (x * w).astype(np.float64)
        return ErrorModel(float(err.mean()), float(err.std()),
                          name=f"errmodel[{cfg.name}]")

    def inject(self, exact_psum, key, k_dim: int):
        """Physical model: k_dim-row accumulation, err mean/var scale with K.

        exact_psum: (..., N) float accumulations over k_dim rows."""
        noise = self.mu1 * k_dim + jnp.sqrt(jnp.asarray(self.sig1 ** 2 * k_dim)) \
            * jax.random.normal(key, exact_psum.shape, exact_psum.dtype)
        return exact_psum + noise

    def inject_paper(self, exact_psum, key, window: int = 128):
        """Paper-style injection (Sec. V: 'the DS-CIM error pattern was added
        to the MVM results'): one window-magnitude error per *output*,
        independent of how many 128-row windows the K dim spans.  This is the
        convention under which Table I/II model accuracies are consistent;
        the physical per-window accumulation is sqrt(K/128) larger (see
        EXPERIMENTS.md §Calibration-notes)."""
        noise = self.mu1 * window + self.sig1 * np.sqrt(window) \
            * jax.random.normal(key, exact_psum.shape, exact_psum.dtype)
        return exact_psum + noise
