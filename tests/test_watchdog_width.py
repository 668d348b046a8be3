"""The accuracy watchdog's drift threshold at Qwen3-0.6B's published
widths (d_model 1024, d_ff 3072, vocab 151,936; one layer).

The threshold is calibrated on the served model (runtime/serving.py
``watchdog_for_spec``).  At these widths healthy DS-CIM1/L256 drift sits
near 4.5, above the reduced-width scale (~2.3) any fixed bound was fitted
to, so this pins that a healthy serve never trips while a planted stuck-at
macro fault still does.  The estimator runs in 'lut' mode, which the fused
kernel matches to f32 rounding (tests/test_kernels_fused.py); the kernel
in interpret mode would take minutes per step at this vocabulary."""
import dataclasses

import jax
import numpy as np

from repro.configs import get_arch
from repro.launch.serve import serve_continuous
from repro.models import get_model
from repro.runtime.failover import FailureInjector
from repro.runtime.serving import STATUS_OK, watchdog_for_spec
from repro.runtime.watchdog import AccuracyWatchdog


def test_watchdog_full_width_healthy_passes_stuck_fault_trips():
    spec = "lut:dscim1:256"
    cfg = dataclasses.replace(get_arch("qwen3-0.6b"), n_layers=1,
                              dscim=spec)
    params = get_model(cfg).init_params(cfg, jax.random.PRNGKey(0))
    prompts = np.random.default_rng(1).integers(0, cfg.vocab, (4, 16),
                                                dtype=np.int32)
    budgets = np.full(4, 6, np.int32)
    knobs = dict(slots=4, seg_len=2, max_new=budgets, eos_id=-1, kv="int8",
                 page_size=8, paged_attn="jnp", log=lambda *a: None)

    healthy = watchdog_for_spec(spec, cfg, params, probe_every=1)
    _, st = serve_continuous(cfg, params, prompts, 6, **knobs,
                             monitor=healthy)
    rels = np.concatenate(healthy.history)
    assert st["probes"] >= 2 and rels.size >= 8
    assert st["probe_trips"] == 0 and not st["quarantined"]
    assert rels.max() < healthy.rel_threshold
    # the drift is real: well above the old fixed bound of 3.5
    assert np.median(rels) > 3.5

    faulted = AccuracyWatchdog(healthy.rel_threshold, probe_every=1)
    inj = FailureInjector(macro_fault_at=0, macro_fault="stuck:3:40.0")
    outs, st = serve_continuous(cfg, params, prompts, 6, **knobs,
                                monitor=faulted, injector=inj)
    assert st["probe_trips"] >= 1 and st["quarantined"]
    assert st["status"] == [STATUS_OK] * 4
    assert [len(o) for o in outs] == budgets.tolist()
