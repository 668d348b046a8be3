"""Step watchdog: straggler detection + hang escalation — plus the
serving accuracy watchdog (ISSUE 6).

At 1000+-node scale the common failure modes are (a) a host silently
slowing down (ECC retries, thermal throttle) and (b) a hung collective.
The watchdog tracks a robust step-time baseline (EMA + MAD) and

* flags *stragglers*: step time > straggler_factor x baseline  -> callback
  (production: report host to the scheduler for drain/requeue);
* raises on *hang*: no step completion within hang_timeout seconds, which
  the failover loop (runtime/failover.py) turns into checkpoint-restart.

``AccuracyWatchdog`` is the estimator-health counterpart for DS-CIM
serving: every ``probe_every`` segments the fault-tolerant scheduler
(runtime/serving.py) compares the serving path's logits against an
exact-mode decode of the same (token, cache) inputs and trips a slot
whose relative RMSE exceeds a threshold calibrated on the served model
(runtime/serving.py ``watchdog_for_spec``) — or whose logits go
NaN/Inf.  A tripped slot is quarantined and its request escalated down
the degradation ladder (dscim2 -> dscim1 -> exact) instead of poisoning
the rest of the batch.
"""
from __future__ import annotations

import threading
import time

import numpy as np

__all__ = ["Watchdog", "StepHang", "AccuracyWatchdog"]


class StepHang(RuntimeError):
    pass


class Watchdog:
    def __init__(self, straggler_factor: float = 3.0,
                 hang_timeout: float = 300.0, on_straggler=None):
        self.factor = straggler_factor
        self.hang_timeout = hang_timeout
        self.on_straggler = on_straggler or (lambda info: None)
        self.ema = None
        self.n_stragglers = 0
        self._last_done = time.monotonic()
        self._armed = threading.Event()
        self._hang = threading.Event()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._monitor, daemon=True)
        self._thread.start()

    # -- hang monitoring (background thread) -----------------------------------
    def _monitor(self):
        while not self._stop.is_set():
            time.sleep(0.1)
            if self._armed.is_set() and \
                    time.monotonic() - self._last_done > self.hang_timeout:
                self._hang.set()
                self._armed.clear()

    # -- per-step API -----------------------------------------------------------
    def step(self):
        """Context manager wrapping one training step."""
        wd = self

        class _Ctx:
            def __enter__(self):
                if wd._hang.is_set():
                    raise StepHang("previous step exceeded hang_timeout")
                wd._armed.set()
                self.t0 = time.monotonic()
                return self

            def __exit__(self, et, ev, tb):
                wd._armed.clear()
                wd._last_done = time.monotonic()
                if et is not None:
                    return False
                dt = time.monotonic() - self.t0
                if wd.ema is None:
                    wd.ema = dt
                elif dt > wd.factor * wd.ema:
                    wd.n_stragglers += 1
                    wd.on_straggler({"step_time": dt, "baseline": wd.ema})
                else:
                    wd.ema = 0.9 * wd.ema + 0.1 * dt
                return False
        return _Ctx()

    def check_hang(self):
        if self._hang.is_set():
            raise StepHang("no step completed within hang_timeout")

    def reset(self):
        """Clear a latched hang so a failover replay can re-arm cleanly
        (without this, the StepHang that triggered the restart would
        re-raise on the replay's first step)."""
        self._hang.clear()
        self._armed.clear()
        self._last_done = time.monotonic()

    def close(self):
        self._stop.set()
        self._thread.join(timeout=1)


class AccuracyWatchdog:
    """Sampled exact-vs-stochastic logit drift monitor for DS-CIM serving.

    ``rel_threshold``: maximum healthy per-slot relative logit RMSE
    (``rmse(serving - exact) / rms(exact)``), normally a margin over the
    served model's measured healthy drift (runtime/serving.py
    ``watchdog_for_spec``); ``None``
    disables drift probes (NaN/Inf detection stays on — the scheduler
    checks per-step logit finiteness every segment regardless).
    ``probe_every``: probe cadence in segments — the monitoring cost is
    one extra exact-mode decode step per ``probe_every`` segments, which
    ``tools/bench_regression.py`` bounds on the fault-free path."""

    def __init__(self, rel_threshold: float | None, probe_every: int = 8):
        if probe_every < 1:
            raise ValueError(f"probe_every must be >= 1, got {probe_every}")
        self.rel_threshold = rel_threshold
        self.probe_every = probe_every
        self.n_probes = 0
        self.n_trips = 0
        self.history: list = []    # (segment, per-slot rel rmse) tuples

    def should_probe(self, segment: int) -> bool:
        return self.rel_threshold is not None \
            and segment % self.probe_every == 0

    def check(self, serving_logits, exact_logits, live):
        """Per-slot drift verdicts for one probe.

        serving_logits/exact_logits: (B, V) arrays of the *same* (token,
        cache) decode inputs; live: (B,) bool mask of slots with an active
        request.  Returns (trip (B,) bool, rel (B,) float64) — a slot
        trips when its relative RMSE exceeds the threshold or is not
        finite (NaN/Inf logits)."""
        s = np.asarray(serving_logits, np.float64)
        e = np.asarray(exact_logits, np.float64)
        live = np.asarray(live, bool)
        rms = np.sqrt(np.mean(e * e, axis=-1))
        rel = np.sqrt(np.mean((s - e) ** 2, axis=-1)) / np.maximum(rms, 1e-9)
        trip = live & (~np.isfinite(rel) | (rel > self.rel_threshold))
        self.n_probes += 1
        self.n_trips += int(trip.sum())
        self.history.append(rel)
        return trip, rel
