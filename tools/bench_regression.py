"""CI bench-regression smoke: paged-attention kernel vs jnp gather
(ISSUE 5 satellite), plus the fault-free monitoring-overhead bound of
the fault-tolerant serving runtime (ISSUE 6).

Runs the serve-bench paged-KV smoke serving configuration twice — once
with the fused Pallas paged-attention read path
(kernels/paged_attention.py), once with the jnp gather reference — and
asserts the matched-prefix logit RMSE between the two paths stays below
the checked-in threshold (tools/ci_thresholds.json), plus full token
agreement.  Kernel drift (a masking bug, a softmax-order change, a tile
regression) is caught here, in CI, instead of surfacing later as a
mysteriously-degraded BENCH row.

The comparison metric is launch/serve.py ``logit_drift_rmse`` — the same
teacher-matched-prefix RMSE serve_bench and the acceptance tests use, so
the threshold means the same thing everywhere.  Both paths run the same
f32 page walk in the same order, so the healthy RMSE is float-epsilon
noise (~1e-8 — XLA's einsum layout vs the kernel's dot_general round
differently); the 1e-5 threshold is the acceptance-criterion bound, two
decades above it.

The two paths are selected via ``serve_batch(paged_attn=...)`` — the
read-path pin is part of the jitted builder's cache key, so each run
traces its own executable.

The ISSUE 6 leg serves the same continuous queue plain and with the
accuracy watchdog + boundary snapshots armed (no faults injected) and
bounds the wall-time ratio at ``chaos_monitor_overhead_ratio``.  The CI
bound is deliberately looser than the <=5% the full-size BENCH row
demonstrates (BENCH_kernels.json ``serve/chaos_monitored``): the CI
shape is tiny (one probe, a couple of snapshots, ~100 ms of serving),
so runner timing noise dominates the true monitoring cost — the gate
exists to catch a monitoring path that suddenly costs a *multiple* of
serving (an accidental per-segment device sync, a probe that stopped
respecting its cadence), not to re-measure the 5%.

The ISSUE 9 leg serves the same queue with ``integrity='off'`` and
``integrity='scrub:2'`` (no faults injected) and bounds the wall-time
ratio at ``integrity_scrub_overhead_ratio``.  Like the monitoring bound,
the CI bound (1.15x) is looser than the checksum layer's true cost at
real shapes — digest upkeep rides the jitted write paths and the sweeps
are one compiled reduction per period — so the gate catches a scrubbing
path that suddenly costs a multiple of serving (a per-segment host
round-trip, a digest recompute that stopped being incremental), not the
percent-level truth the full-size ``serve/integrity_scrub`` BENCH row
records.

The ISSUE 7 leg serves the self-speculative greedy configuration
(dscim2:64 drafts, dscim1:256 verify, int8 paged KV) and gates two
things: the spec output must be *bitwise* the plain-driver output (the
tentpole acceptance criterion — any drift is an immediate fail, no
threshold), and the greedy acceptance rate (accepted draft tokens per
drafted token) must stay above ``spec_greedy_acceptance_rate_min``.
Both drivers are deterministic on the fixed seed, so the measured rate
(0.48 at the full CI shape, 0.58 at the smoke shape) is reproducible;
the 0.40 bound is measured-minus-slack — a drafter regression (wrong
draft cache, a desynced operating point, an estimator change that
silently decorrelates dscim2 from dscim1) shows up as a rate collapse
long before it shows up in tok/s.

The ISSUE 8 leg replays the mini router load test
(benchmarks/loadtest.py ``run_loadtest(smoke=True)`` — plain + sampled-
fault legs, every-request-terminates and zero-live-pages asserted inside)
and bounds two service-shaped regressions: the worst-leg p99/p50 latency
ratio at ``router_p99_p50_ratio_max`` (a head-of-line collapse — one
chunked admission or a failover replay stalling the whole decode plane —
shows up as p99 exploding while p50 stays flat; the bound is generous
because a single injected device-loss replay legitimately stretches the
chaos leg's tail at CI shapes) and the refusal rate at
``router_refusal_rate_max`` (admission control that starts refusing the
majority of a modest trace is broken backpressure, not load shedding).

The ISSUE 10 leg serves the 90%-shared-prefix request queue warm
(``prefix_cache='on'``) and cold (``prefix_cache='cold'`` — the
identical page-aligned chunked admission path with lookup/registration
disabled), asserts the warm outputs bitwise against the cold ones (the
tentpole acceptance criterion: a prefix hit maps page-table entries to
already-quantized physical pages, it never re-derives bytes), and
bounds two metrics: the fraction of prefill positions removed by page
sharing must stay above ``prefix_flops_removed_min`` (the >= 0.4
acceptance bar at the 90% trace; measured 0.50 at the CI shape — 5 of
6 requests share 3 of 4 prompt pages and only the first admission pays
for them), and the mean wall admission latency of a prefix *hit* over
the cold leg's miss admissions must stay below
``prefix_hit_admission_latency_ratio_max`` (hits feed strictly fewer
chunks through the same compiled extend program, so the ratio sits
well under 1 — 0.37 measured; a ratio drifting toward 1 means hit
admissions started re-feeding their shared pages, i.e. the dedup
stopped removing work without breaking bitwise parity).

Usage:  PYTHONPATH=src python -m tools.bench_regression [--smoke]
(--smoke shortens the trace; CI passes it.)  Exit 0 on pass, 1 on drift.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
THRESHOLDS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "ci_thresholds.json")


def _serve_both_paths(smoke: bool):
    """(tokens, trace) for the kernel and gather read paths on the
    serve-bench paged-KV smoke shape (float model — the read path is the
    only thing under test)."""
    import jax
    import numpy as np

    from repro.configs import get_arch
    from repro.launch.serve import serve_batch
    from repro.models import get_model

    cfg = get_arch("qwen3-0.6b").reduced()
    model = get_model(cfg)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    B, prompt_len = 4, 16
    n_tokens = 16 if smoke else 48
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab, (B, prompt_len), dtype=np.int32)

    return {path: serve_batch(cfg, params, prompts, n_tokens,
                              trace_logits=True, prepare=False,
                              kv="int8", page_size=4, paged_attn=path)
            for path in ("kernel", "jnp")}


def _chaos_monitor_overhead(smoke: bool) -> float:
    """Fault-free wall-time ratio monitored/plain for serve_continuous on
    a small continuous queue (ISSUE 6).  Median of 3 warmed runs per path
    even in smoke — single-shot timings on a CI runner are too noisy to
    gate on — and the queue does NOT shrink under --smoke: below ~8
    decode segments the one probe + one snapshot are a fixed cost with
    nothing to amortize over and the ratio measures shape, not the
    monitoring path (measured: 1.35x at 3 segments vs ~1.0x at 8)."""
    import dataclasses

    import jax
    import numpy as np

    from benchmarks.common import timed
    from repro.configs import get_arch
    from repro.launch.serve import serve_continuous
    from repro.models import get_model
    from repro.runtime.serving import watchdog_for_spec

    spec = "kernel:dscim1:256"
    cfg = dataclasses.replace(get_arch("qwen3-0.6b").reduced(), dscim=spec)
    model = get_model(cfg)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    R, prompt_len = 4, 8
    n_tokens = 8
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab, (R, prompt_len), dtype=np.int32)
    budgets = np.linspace(2, n_tokens, R).round().astype(np.int32)
    knobs = dict(slots=2, seg_len=4, max_new=budgets, eos_id=-1,
                 kv="int8", page_size=4)
    monitor = watchdog_for_spec(spec, cfg, params, probe_every=8)

    def plain():
        return serve_continuous(cfg, params, prompts, n_tokens, **knobs)[0]

    def monitored():
        return serve_continuous(cfg, params, prompts, n_tokens, **knobs,
                                monitor=monitor, snapshot_every=8)[0]

    us_plain = timed(plain, n=3)
    us_mon = timed(monitored, n=3)
    return us_mon / us_plain


def _integrity_overhead(smoke: bool) -> float:
    """Fault-free wall-time ratio scrub:2/off for serve_continuous on a
    small continuous queue (ISSUE 9).  Same shape discipline as
    ``_chaos_monitor_overhead`` (the queue does not shrink under --smoke
    — below ~8 decode segments the boundary sweeps are fixed cost with
    nothing to amortize over), but the estimator is min-of-5 over
    *interleaved* off/scrub reps rather than a median of 3: at ~200 ms a
    run, CI-runner noise spans tens of percent and an unpaired median
    ratio flaps; interleaved minima track the noise floor both legs
    share, which is the quantity the bound is about."""
    import dataclasses
    import time

    import jax
    import numpy as np

    from repro.configs import get_arch
    from repro.launch.serve import serve_continuous
    from repro.models import get_model

    spec = "kernel:dscim1:256"
    cfg = dataclasses.replace(get_arch("qwen3-0.6b").reduced(), dscim=spec)
    model = get_model(cfg)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    R, prompt_len = 4, 8
    n_tokens = 8
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab, (R, prompt_len), dtype=np.int32)
    budgets = np.linspace(2, n_tokens, R).round().astype(np.int32)
    knobs = dict(slots=2, seg_len=4, max_new=budgets, eos_id=-1,
                 kv="int8", page_size=4)

    def off():
        return serve_continuous(cfg, params, prompts, n_tokens, **knobs)[0]

    def scrubbed():
        return serve_continuous(cfg, params, prompts, n_tokens, **knobs,
                                integrity="scrub:2")[0]

    off(), scrubbed()  # warm both executables (trace + compile)
    best = {"off": float("inf"), "scrub": float("inf")}
    for _ in range(5):
        for name, fn in (("off", off), ("scrub", scrubbed)):
            t0 = time.perf_counter()
            fn()
            best[name] = min(best[name], time.perf_counter() - t0)
    return best["scrub"] / best["off"]


def _spec_acceptance(smoke: bool):
    """(bitwise_match, acceptance_rate) for greedy self-speculative
    decoding on the serve-bench spec shape (ISSUE 7)."""
    import dataclasses

    import jax
    import numpy as np

    from repro.configs import get_arch
    from repro.launch.serve import serve_batch
    from repro.models import get_model

    cfg = dataclasses.replace(get_arch("qwen3-0.6b").reduced(),
                              dscim="kernel:dscim1:256")
    model = get_model(cfg)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    B, prompt_len, k = 4, 8, 4
    n_tokens = 8 if smoke else 16
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab, (B, prompt_len), dtype=np.int32)
    kw = dict(kv="int8", page_size=4)
    t_ref, _ = serve_batch(cfg, params, prompts, n_tokens, **kw)
    t_spec, _, ss = serve_batch(cfg, params, prompts, n_tokens,
                                spec=f"dscim2:{k}", spec_stats=True, **kw)
    match = bool(np.array_equal(np.asarray(t_spec), np.asarray(t_ref)))
    accepted = int((ss["emitted"] - 1).sum())
    rate = accepted / max(int(ss["windows"].sum()), 1) / k
    return match, rate


def _prefix_leg(smoke: bool):
    """(bitwise_match, prefill_removed_frac, admit_latency_ratio) for the
    90%-shared-prefix continuous-serving queue (ISSUE 10).  Both legs run
    the same compiled page-aligned chunked extend program; the cold leg
    just has prefix lookup/registration disabled, so the warm outputs
    must be token-identical and every difference is pure dedup.  Each leg
    runs twice and the second run's stats are used — the first pair warms
    the shared executables so the admission wall-clock samples measure
    the steady-state path, not tracing."""
    import dataclasses

    import jax
    import numpy as np

    from repro.configs import get_arch
    from repro.launch.serve import serve_continuous
    from repro.models import get_model

    cfg = dataclasses.replace(get_arch("qwen3-0.6b").reduced(),
                              dscim="kernel:dscim1:256")
    model = get_model(cfg)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    ps, S, R = 4, 16, 6
    n_tokens = 4 if smoke else 8
    rng = np.random.default_rng(0)
    budgets = np.clip(np.linspace(2, n_tokens, R).round(), 2,
                      n_tokens).astype(np.int32)
    prompts = rng.integers(0, cfg.vocab, (R, S), dtype=np.int32)
    prompts[:round(0.9 * R), :12] = rng.integers(0, cfg.vocab, 12,
                                                 dtype=np.int32)
    knobs = dict(slots=2, seg_len=2, max_new=budgets, eos_id=-1,
                 kv="int8", page_size=ps, prepare=False,
                 log=lambda *a: None)

    def leg(mode):
        return serve_continuous(cfg, params, prompts, n_tokens,
                                prefix_cache=mode, **knobs)

    leg("cold"), leg("on")          # warm the shared executables
    out_c, st_c = leg("cold")
    out_w, st_w = leg("on")
    match = len(out_c) == len(out_w) and all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(out_c, out_w))
    pw = st_w["prefix"]
    removed = 1.0 - pw["prefill_positions_computed"] \
        / max(pw["prefill_positions_total"], 1)
    lat_cold = float(np.mean(st_c["prefix"]["admit_lat_miss"]))
    lat_hit = float(np.mean(pw["admit_lat_hit"])) if pw["admit_lat_hit"] \
        else float("inf")
    return match, removed, lat_hit / max(lat_cold, 1e-12)


def _router_loadtest(smoke: bool):
    """(worst-leg p99/p50 ratio, worst-leg refusal rate) from the mini
    router load test (ISSUE 8).  run_loadtest itself hard-asserts the
    liveness contract (every request terminal, zero live pages at drain,
    ok-vs-ok bitwise agreement between legs); this leg adds the bounded
    service metrics on top."""
    sys.path.insert(0, REPO)        # benchmarks/ package, as CI runs it
    from benchmarks.loadtest import run_loadtest
    _, m_plain, m_chaos = run_loadtest(True, log=lambda *a: None)
    ratio = max(m["p99_ms"] / max(m["p50_ms"], 1e-9)
                for m in (m_plain, m_chaos))
    refusal = max(m["refusal_rate"] for m in (m_plain, m_chaos))
    return ratio, refusal


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="short trace (the CI leg)")
    args = ap.parse_args(argv)

    import numpy as np

    from repro.launch.serve import _agreement, logit_drift_rmse

    with open(THRESHOLDS) as f:
        th = json.load(f)
    out = _serve_both_paths(args.smoke)
    tk, lk = out["kernel"]
    tj, lj = out["jnp"]
    rmse = logit_drift_rmse(tj, tk, lj, lk)
    agree = _agreement(np.asarray(tk), np.asarray(tj), None)
    bound = th["paged_kernel_vs_gather_logit_rmse"]
    min_agree = th["paged_kernel_vs_gather_token_agreement"]
    print(f"paged kernel vs jnp gather: matched-prefix logit RMSE "
          f"{rmse:.3e} (threshold {bound:.0e}), token agreement "
          f"{agree:.4f} (threshold {min_agree})")
    ok = rmse <= bound and agree >= min_agree
    if not ok:
        print("BENCH REGRESSION: paged-attention kernel drifted from the "
              "jnp gather reference", file=sys.stderr)

    ratio = _chaos_monitor_overhead(args.smoke)
    ratio_bound = th["chaos_monitor_overhead_ratio"]
    print(f"fault-tolerant serving monitoring overhead: "
          f"{ratio:.3f}x plain (threshold {ratio_bound}x)")
    if ratio > ratio_bound:
        print("BENCH REGRESSION: fault-free monitoring overhead of the "
              "serving runtime exceeded its bound", file=sys.stderr)
        ok = False

    iratio = _integrity_overhead(args.smoke)
    iratio_bound = th["integrity_scrub_overhead_ratio"]
    print(f"integrity scrubbing overhead: {iratio:.3f}x off "
          f"(threshold {iratio_bound}x)")
    if iratio > iratio_bound:
        print("BENCH REGRESSION: fault-free integrity scrubbing overhead "
              "exceeded its bound", file=sys.stderr)
        ok = False

    match, rate = _spec_acceptance(args.smoke)
    rate_min = th["spec_greedy_acceptance_rate_min"]
    print(f"self-speculative greedy serving: bitwise match {match}, "
          f"acceptance rate {rate:.3f} (threshold {rate_min})")
    if not match:
        print("BENCH REGRESSION: greedy self-speculative output drifted "
              "from the plain driver (bitwise-parity contract)",
              file=sys.stderr)
        ok = False
    if rate < rate_min:
        print("BENCH REGRESSION: greedy self-spec acceptance rate "
              "collapsed below its bound", file=sys.stderr)
        ok = False

    pmatch, removed, admit_ratio = _prefix_leg(args.smoke)
    removed_min = th["prefix_flops_removed_min"]
    admit_max = th["prefix_hit_admission_latency_ratio_max"]
    print(f"prefix cache (90% shared trace): bitwise match {pmatch}, "
          f"prefill removed {removed:.3f} (threshold >= {removed_min}), "
          f"hit/cold admission latency ratio {admit_ratio:.3f} "
          f"(threshold <= {admit_max})")
    if not pmatch:
        print("BENCH REGRESSION: prefix-cached serving drifted from the "
              "cold chunked reference (bitwise-parity contract)",
              file=sys.stderr)
        ok = False
    if removed < removed_min:
        print("BENCH REGRESSION: prefix caching stopped removing prefill "
              "work — shared pages are being re-fed", file=sys.stderr)
        ok = False
    if admit_ratio > admit_max:
        print("BENCH REGRESSION: prefix-hit admission latency no longer "
              "beats a cold admission — dedup is not skipping chunks",
              file=sys.stderr)
        ok = False

    tail, refusal = _router_loadtest(args.smoke)
    tail_max = th["router_p99_p50_ratio_max"]
    refusal_max = th["router_refusal_rate_max"]
    print(f"router load test: p99/p50 ratio {tail:.2f} (threshold "
          f"{tail_max}), refusal rate {refusal:.3f} "
          f"(threshold {refusal_max})")
    if tail > tail_max:
        print("BENCH REGRESSION: router tail latency collapsed — p99/p50 "
              "exceeded its bound (head-of-line blocking?)",
              file=sys.stderr)
        ok = False
    if refusal > refusal_max:
        print("BENCH REGRESSION: router refusal rate exceeded its bound — "
              "admission control is shedding most of a modest trace",
              file=sys.stderr)
        ok = False
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
