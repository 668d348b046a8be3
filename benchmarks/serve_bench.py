"""Serving-loop microbench: host-loop vs scanned generation (ISSUE 3),
plus the only-live-work rows (ISSUE 4) — EOS early-exit + continuous
batching vs the fixed-length scan on a skewed-completion-length queue,
and the int8 block-paged KV cache vs the dense float cache.

Accounting (the ISSUE 4 fix): every serve row now carries
``live_slot_steps`` and ``occupancy`` — the fixed-length drivers burn a
slot-step per (slot, step) whether or not the slot still has useful work,
so their occupancy on a skewed workload is sum(budgets)/(B*n_tokens) and
the old all-slots tok/s over-credited padded/finished slots.  ``tok_s``
on queue rows counts *useful* tokens only (each request's budget-long
prefix), which is exactly the live-slot-step-credited rate: a live
slot-step emits one useful token, a dead one earns nothing.

The paged-KV rows record resident decode-cache bytes (dense fixed-
capacity float cache vs int8 pages + per-page scales + bf16 tails +
page table, core/kvcache.py) and the logit drift measured on the
teacher-matched prefix — per row, decode steps up to the first token
divergence — so feedback of a flipped argmax doesn't masquerade as
quantization error.  Compile time is excluded everywhere (warmed runs).

ISSUE 5 adds the paged *read-path* A/B on the same int8 cache: the fused
Pallas paged-attention kernel (kernels/paged_attention.py) vs the jnp
gather reference, with the per-step HBM bytes the kernel stops staging
(gathered int8 pages + their f32 dequant copies) in the derived fields.

ISSUE 7 adds the self-speculative decoding rows (``serve/spec_*``):
dscim2-draft -> dscim1-verify vs the plain driver at asserted-bitwise
greedy outputs, with accepted-tokens-per-verify / acceptance-rate in the
derived fields, and page-pool occupancy read from ``PageAllocator.stats()``
on the continuous rows.

ISSUE 10 adds the prefix-cache rows (``serve/prefix_hit0|hit50|hit90``):
the same queue with a shared system prompt on 0/50/90% of requests,
served warm vs cold, with prefill-positions-removed and hit-vs-cold
admission latency in the derived fields — both CI-bounded by
tools/bench_regression.py.
"""
from __future__ import annotations

import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timed

DSCIM = "kernel:dscim1:256"


def _host_loop(prefill, decode, params, batch, n_tokens):
    logits, cache = prefill(params, batch)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    out = [tok]
    for _ in range(n_tokens - 1):
        tok, cache = decode(params, {"token": tok}, cache)
        out.append(tok)
    return jnp.stack(out, axis=1)


def _dispatch_rows(cfg, params, smoke):
    """PR 3 rows: host loop vs scanned generate, dispatch accounting."""
    from repro.launch.steps import (make_decode_step, make_generate_fn,
                                    make_prefill_step)
    n_tokens = 4 if smoke else 16
    prompt_len = 8
    reps = 1 if smoke else 3
    rows = []
    rng = np.random.default_rng(0)
    for B in ([1] if smoke else [1, 8, 16]):
        prompts = rng.integers(0, cfg.vocab, (B, prompt_len), dtype=np.int32)
        batch = {"tokens": jnp.asarray(prompts)}
        prefill = jax.jit(make_prefill_step(cfg, None,
                                            capacity=prompt_len + n_tokens))
        # cache donated between steps exactly like serve_batch's host loop
        decode = jax.jit(make_decode_step(cfg, None), donate_argnums=(2,))
        generate = make_generate_fn(cfg, None, n_tokens)
        us_host = timed(lambda: _host_loop(prefill, decode, params, batch,
                                           n_tokens), n=reps)
        us_scan = timed(lambda: generate(params, batch)[0], n=reps)
        tok = jnp.zeros((B,), jnp.int32)
        noop = jax.jit(lambda t: t + 0)
        us_dispatch = timed(lambda: noop(tok), n=max(reps, 3))
        # fixed-length drivers: every slot-step is counted live (no EOS),
        # which is exactly the over-credit the queue rows below expose
        shared = (f"n_tokens={n_tokens};dispatches_host={n_tokens};"
                  f"dispatches_scanned=1;"
                  f"dispatch_us={us_dispatch:.1f};"
                  f"dispatch_overhead_removed_us="
                  f"{(n_tokens - 1) * us_dispatch:.1f};"
                  f"live_slot_steps={B * n_tokens};occupancy=1.00")
        rows.append({
            "name": f"serve/host_loop/{DSCIM}/B{B}x{prompt_len}+{n_tokens}",
            "us": us_host,
            "derived": (f"tok_s={B * n_tokens / us_host * 1e6:.1f};"
                        f"{shared}")})
        rows.append({
            "name": f"serve/scanned/{DSCIM}/B{B}x{prompt_len}+{n_tokens}",
            "us": us_scan,
            "derived": (f"tok_s={B * n_tokens / us_scan * 1e6:.1f};"
                        f"speedup_vs_host_loop={us_host / us_scan:.2f}x;"
                        f"{shared}")})
    return rows


def _queue_rows(cfg, params, smoke):
    """ISSUE 4 A/B at skewed completion lengths: a queue of R requests with
    budgets 2..n_tokens served by (a) the PR 3 fixed-length scan in
    R/slots-sized batches, (b) the EOS early-exit while_loop on the same
    batches (exits at each batch's max budget), (c) continuous batching
    (early-exit segments + admission into freed slots)."""
    from repro.launch.serve import serve_batch, serve_continuous
    n_tokens = 4 if smoke else 16
    slots = 2 if smoke else 4
    R = 4 if smoke else 8
    prompt_len = 8
    reps = 1 if smoke else 3    # odd, so timed()'s median is a real median
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab, (R, prompt_len), dtype=np.int32)
    budgets = np.linspace(2, n_tokens, R).round().astype(np.int32)
    rng.shuffle(budgets)                     # admission order is skewed too
    useful = int(budgets.sum())
    tag = f"{DSCIM}/R{R}s{slots}x{prompt_len}+{n_tokens}"

    def fixed_queue():
        for i in range(0, R, slots):
            serve_batch(cfg, params, prompts[i:i + slots], n_tokens,
                        prepare=False)

    def early_exit_queue():
        for i in range(0, R, slots):
            serve_batch(cfg, params, prompts[i:i + slots], n_tokens,
                        prepare=False, eos_id=-1,
                        max_new=budgets[i:i + slots])

    stats = {}     # filled by the timed runs (no extra serve just for it)

    def continuous_queue():
        outs, st = serve_continuous(cfg, params, prompts, n_tokens,
                                    slots=slots, seg_len=4, max_new=budgets,
                                    eos_id=-1, prepare=False)
        stats.update(st)
        return outs

    us_fixed = timed(fixed_queue, n=reps)
    us_ee = timed(early_exit_queue, n=reps)
    us_cont = timed(continuous_queue, n=reps)
    # early exit runs each batch to its max budget (tokens incl. prefill,
    # so max-1 decode steps after the batch prefill step)
    ee_slot_steps = sum(slots * int(budgets[i:i + slots].max())
                        for i in range(0, R, slots))
    rows = [{
        "name": f"serve/fixed_scan_queue/{tag}",
        "us": us_fixed,
        "derived": (f"tok_s={useful / us_fixed * 1e6:.1f};"
                    f"useful_tokens={useful};"
                    f"live_slot_steps={useful};"
                    f"slot_steps={R * n_tokens};"
                    f"occupancy={useful / (R * n_tokens):.2f}"),
    }, {
        "name": f"serve/early_exit_queue/{tag}",
        "us": us_ee,
        "derived": (f"tok_s={useful / us_ee * 1e6:.1f};"
                    f"useful_tokens={useful};"
                    f"live_slot_steps={useful};"
                    f"slot_steps={ee_slot_steps};"
                    f"occupancy={useful / ee_slot_steps:.2f};"
                    f"speedup_vs_fixed={us_fixed / us_ee:.2f}x"),
    }, {
        # stated on the same token-slot basis as the other two rows
        # (admission tokens count as live slot-steps, one slot-step per
        # token emitted), so the three occupancy numbers are comparable —
        # serve_continuous's own stats count decode steps only
        "name": f"serve/continuous_queue/{tag}",
        "us": us_cont,
        "derived": (f"tok_s={useful / us_cont * 1e6:.1f};"
                    f"useful_tokens={useful};"
                    f"live_slot_steps={useful};"
                    f"slot_steps={stats['slot_steps'] + R};"
                    f"occupancy={useful / (stats['slot_steps'] + R):.2f};"
                    f"speedup_vs_fixed={us_fixed / us_cont:.2f}x"),
    }]
    return rows


def _paged_kernel_rows(cfg_float, params, smoke):
    """ISSUE 5 rows: the fused Pallas paged-attention read path vs the jnp
    gather reference on the same int8 paged cache.  The derived fields
    carry the HBM traffic the kernel removes *per decode step*: the jnp
    path stages the gathered int8 k+v pages and their dequantized f32
    copies in HBM before the QK contraction (gather -> dequant -> einsum
    are separate XLA ops), while the kernel streams the int8 pages
    HBM->VMEM once and dequantizes in VMEM — on TPU that staged traffic is
    the bandwidth term the int8 cache was supposed to save.  Logit drift
    between the two paths is the tools/bench_regression.py CI metric
    (matched-prefix RMSE, threshold tools/ci_thresholds.json)."""
    from repro.core.kvcache import n_pages_for
    from repro.launch.serve import logit_drift_rmse, serve_batch
    from repro.launch.steps import make_generate_fn
    B, prompt_len = 4, 16
    n_tokens = 16 if smoke else 112
    page_size = 4
    reps = 1 if smoke else 3
    capacity = prompt_len + n_tokens
    MP = n_pages_for(capacity, page_size)
    L, KV, HD = cfg_float.n_layers, cfg_float.n_kv, cfg_float.head_dim
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg_float.vocab, (B, prompt_len),
                           dtype=np.int32)
    batch = {"tokens": jnp.asarray(prompts)}

    def timed_path(path):
        # the read-path pin keys the builder cache — no env state, no
        # stale-executable hazard between the two timed paths
        gen = make_generate_fn(cfg_float, None, n_tokens, kv="int8",
                               page_size=page_size, paged_attn=path)
        us = timed(lambda: gen(params, batch)[0], n=reps)
        toks, trace = serve_batch(cfg_float, params, prompts, n_tokens,
                                  trace_logits=True, prepare=False,
                                  kv="int8", page_size=page_size,
                                  paged_attn=path)
        return us, toks, trace

    us_k, tk, lk = timed_path("kernel")
    us_j, tj, lj = timed_path("jnp")
    drift = logit_drift_rmse(tj, tk, lj, lk)
    # per decode step, per layer: gathered int8 k+v pages (2x) + their f32
    # dequantized copies (8x) staged in HBM by the jnp path — all removed
    # by the kernel (pages go HBM->VMEM once, dequant stays in VMEM)
    page_elems = B * MP * page_size * KV * HD
    staged = L * page_elems * (2 * 1 + 2 * 4)
    shared = (f"page_size={page_size};capacity={capacity};"
              f"hbm_staged_bytes_per_step_gather={staged};"
              f"hbm_staged_bytes_per_step_kernel=0;"
              f"hbm_bytes_removed_per_step={staged};"
              f"logit_drift_rmse={drift:.3e};"
              f"token_agreement={float((tk == tj).mean()):.3f}")
    tag = f"float/B{B}x{prompt_len}+{n_tokens}"
    return [{
        "name": f"serve/paged_read_gather/{tag}",
        "us": us_j,
        "derived": f"tok_s={B * n_tokens / us_j * 1e6:.1f};{shared}",
    }, {
        "name": f"serve/paged_read_kernel/{tag}",
        "us": us_k,
        "derived": (f"tok_s={B * n_tokens / us_k * 1e6:.1f};"
                    f"speedup_vs_gather={us_j / us_k:.2f}x;{shared}"),
    }]


def _paged_kv_rows(cfg_float, params, smoke):
    """Int8 block-paged KV cache vs the dense float cache: tok/s, resident
    decode-cache bytes, and teacher-matched-prefix logit drift."""
    from repro.core.kvcache import (dense_cache_bytes, kv_cache_bytes,
                                    paged_cache_specs)
    from repro.launch.serve import logit_drift_rmse, serve_batch
    from repro.launch.steps import make_generate_fn
    B, prompt_len = 4, 16
    n_tokens = 16 if smoke else 112        # capacity 32 / 128
    page_size = 4
    reps = 1 if smoke else 3
    capacity = prompt_len + n_tokens
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg_float.vocab, (B, prompt_len),
                           dtype=np.int32)
    batch = {"tokens": jnp.asarray(prompts)}
    gen_f = make_generate_fn(cfg_float, None, n_tokens)
    gen_q = make_generate_fn(cfg_float, None, n_tokens, kv="int8",
                             page_size=page_size)
    us_f = timed(lambda: gen_f(params, batch)[0], n=reps)
    us_q = timed(lambda: gen_q(params, batch)[0], n=reps)
    bytes_f = dense_cache_bytes(cfg_float, B, capacity)
    bytes_q = kv_cache_bytes(paged_cache_specs(cfg_float, B, capacity,
                                               page_size))
    # drift on the teacher-matched prefix (same fed-back tokens)
    tf, lf = serve_batch(cfg_float, params, prompts, n_tokens,
                         trace_logits=True, prepare=False)
    tq, lq = serve_batch(cfg_float, params, prompts, n_tokens,
                         trace_logits=True, prepare=False, kv="int8",
                         page_size=page_size)
    drift = logit_drift_rmse(tf, tq, lf, lq)
    # fraction of the trace before the first per-row divergence — a raw
    # all-positions agreement would be dominated by the feedback cascade
    # after one argmax flip, not by the quantization under test
    prefix = np.mean([(np.nonzero(tf[b] != tq[b])[0][0] + 1) / n_tokens
                      if (tf[b] != tq[b]).any() else 1.0
                      for b in range(B)])
    shared = (f"kv_bytes_float={bytes_f};kv_bytes_int8={bytes_q};"
              f"kv_bytes_ratio={bytes_f / bytes_q:.2f};"
              f"logit_drift_rmse={drift:.5f};"
              f"matched_prefix_frac={prefix:.3f};"
              f"page_size={page_size};capacity={capacity}")
    tag = f"float/B{B}x{prompt_len}+{n_tokens}"
    return [{
        "name": f"serve/kv_float/{tag}",
        "us": us_f,
        "derived": f"tok_s={B * n_tokens / us_f * 1e6:.1f};{shared}",
    }, {
        "name": f"serve/kv_int8_paged/{tag}",
        "us": us_q,
        "derived": (f"tok_s={B * n_tokens / us_q * 1e6:.1f};"
                    f"speedup_vs_float_kv={us_f / us_q:.2f}x;{shared}"),
    }]


def _spec_rows(cfg, params, smoke):
    """ISSUE 7 rows: self-speculative decoding A/B — the dscim2 drafter in
    front of the dscim1 verifier vs the plain (target-only) driver, greedy,
    on the int8 paged cache.  Greedy spec is *bitwise* the plain output
    (asserted here — a spec row whose tokens drifted would be a lie), so
    ``tok_s`` differences are pure draft-amortization: the useful-tok/s
    win is ``accepted_tok_per_verify`` cheap-draft tokens per full-model
    verify forward.  ``acceptance_rate`` = accepted draft tokens / k
    drafted is the CI-bounded metric (tools/bench_regression.py).

    The continuous leg reports the scheduler's occupancy on the same
    verifier-position basis the deadline ledger uses, plus the
    PageAllocator's own ``stats()`` counters (live/high-water/refusals) —
    the occupancy fields read the allocator, not a recomputation."""
    from repro.launch.serve import serve_batch, serve_continuous
    B, prompt_len = 4, 8
    n_tokens = 8 if smoke else 32
    k = 4
    reps = 1 if smoke else 3
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab, (B, prompt_len), dtype=np.int32)
    kw = dict(prepare=False, kv="int8", page_size=4)
    tag = f"{DSCIM}/B{B}x{prompt_len}+{n_tokens}"

    us_plain = timed(lambda: serve_batch(cfg, params, prompts, n_tokens,
                                         **kw)[0], n=reps)
    us_spec = timed(lambda: serve_batch(cfg, params, prompts, n_tokens,
                                        spec=f"dscim2:{k}", **kw)[0],
                    n=reps)
    t_ref, _ = serve_batch(cfg, params, prompts, n_tokens, **kw)
    t_spec, _, ss = serve_batch(cfg, params, prompts, n_tokens,
                                spec=f"dscim2:{k}", spec_stats=True, **kw)
    np.testing.assert_array_equal(
        np.asarray(t_spec), np.asarray(t_ref),
        err_msg="greedy self-spec output drifted from the plain driver")
    windows = int(ss["windows"].sum())
    accepted = int((ss["emitted"] - 1).sum())  # tok0 isn't a drafted token
    tpv = accepted / max(windows, 1)
    useful = B * n_tokens
    shared = (f"k={k};windows={windows};"
              f"accepted_tok_per_verify={tpv:.3f};"
              f"acceptance_rate={tpv / k:.3f};tokens_match=1")
    rows = [{
        "name": f"serve/spec_off/{tag}",
        "us": us_plain,
        "derived": f"tok_s={useful / us_plain * 1e6:.1f};{shared}",
    }, {
        "name": f"serve/spec_dscim2_k{k}/{tag}",
        "us": us_spec,
        "derived": (f"tok_s={useful / us_spec * 1e6:.1f};"
                    f"speedup_vs_plain={us_plain / us_spec:.2f}x;{shared}"),
    }]

    R, slots, seg_len = (4, 2, 2) if smoke else (8, 4, 2)
    cprompts = rng.integers(0, cfg.vocab, (R, prompt_len), dtype=np.int32)
    st = {}

    def continuous():
        outs, s = serve_continuous(cfg, params, cprompts, n_tokens,
                                   slots=slots, seg_len=seg_len, eos_id=-1,
                                   spec=f"dscim2:{k}", prepare=False,
                                   kv="int8", page_size=4)
        st.update(s)
        return outs

    us_cont = timed(continuous, n=reps)
    pg = st["pages"]
    rows.append({
        "name": f"serve/spec_continuous/{DSCIM}/R{R}s{slots}"
                f"x{prompt_len}+{n_tokens}",
        "us": us_cont,
        "derived": (f"tok_s={st['useful_tokens'] / us_cont * 1e6:.1f};"
                    f"useful_tokens={st['useful_tokens']};"
                    f"occupancy={st['occupancy']:.2f};"
                    f"segments={st['segments']};k={k};"
                    f"pages_live={pg['live_pages']};"
                    f"pages_high_water={pg['high_water']};"
                    f"pages_refusals={pg['refusals']};"
                    f"pages_total={pg['n_pages']}")})
    return rows


def _prefix_rows(cfg, params, smoke):
    """ISSUE 10 rows: prefix caching with refcounted copy-on-write pages.
    The same request queue — a 3-page shared system prompt on 0% / 50% /
    90% of the requests — is served warm (``prefix_cache='on'``) and
    cold (``prefix_cache='cold'``: the identical page-aligned chunked
    admission path with lookup/registration disabled, so the warm leg's
    outputs are asserted bitwise against it by tests/test_prefix_cache.py
    and the prefix CI smoke, and timing differences are pure dedup).

    The derived fields carry the two CI-bounded metrics
    (tools/bench_regression.py): ``prefill_removed_frac`` — the fraction
    of prefill positions never computed because their pages were shared
    (>= 0.4 at the 90% trace is the ISSUE 10 acceptance bar) — and
    ``admit_latency_ratio`` — mean wall admission latency of a prefix
    *hit* over the cold leg's miss admissions (hits feed fewer chunks,
    so the ratio must stay well under 1)."""
    from repro.launch.serve import serve_continuous
    ps, S = 4, 16
    R = 6 if smoke else 10
    n_tokens = 4 if smoke else 8
    slots = 2 if smoke else 4
    reps = 1 if smoke else 3
    rng = np.random.default_rng(0)
    budgets = np.clip(np.linspace(2, n_tokens, R).round(), 2,
                      n_tokens).astype(np.int32)
    base = rng.integers(0, cfg.vocab, (R, S), dtype=np.int32)
    sysp = rng.integers(0, cfg.vocab, 12, dtype=np.int32)  # 3 shared pages
    knobs = dict(slots=slots, seg_len=2, max_new=budgets, eos_id=-1,
                 kv="int8", page_size=ps, prepare=False,
                 log=lambda *a: None)
    rows = []
    for frac, kind in ((0.0, "hit0"), (0.5, "hit50"), (0.9, "hit90")):
        prompts = base.copy()
        n_shared = int(round(frac * R))
        if n_shared:
            prompts[:n_shared, :12] = sysp
        st_w, st_c = {}, {}

        def cold():
            outs, s = serve_continuous(cfg, params, prompts, n_tokens,
                                       prefix_cache="cold", **knobs)
            st_c.clear()
            st_c.update(s)
            return outs

        def warm():
            outs, s = serve_continuous(cfg, params, prompts, n_tokens,
                                       prefix_cache="on", **knobs)
            st_w.clear()
            st_w.update(s)
            return outs

        us_cold = timed(cold, n=reps)
        us_warm = timed(warm, n=reps)
        pw = st_w["prefix"]
        removed = 1.0 - pw["prefill_positions_computed"] \
            / max(pw["prefill_positions_total"], 1)
        lat_cold = float(np.mean(st_c["prefix"]["admit_lat_miss"])) * 1e6
        lat_hit = float(np.mean(pw["admit_lat_hit"])) * 1e6 \
            if pw["admit_lat_hit"] else lat_cold
        useful = int(budgets.sum())
        pg = st_w["pages"]
        tag = f"{DSCIM}/R{R}s{slots}x{S}+{n_tokens}"
        rows.append({
            "name": f"serve/prefix_{kind}/{tag}",
            "us": us_warm,
            "derived": (f"tok_s={useful / us_warm * 1e6:.1f};"
                        f"hit_rate_target={frac:.2f};"
                        f"hits={pw['hits']};lookups={pw['lookups']};"
                        f"hit_tokens={pw['hit_tokens']};"
                        f"pages_deduped={pw['pages_deduped']};"
                        f"prefill_removed_frac={removed:.3f};"
                        f"admit_us_hit={lat_hit:.1f};"
                        f"admit_us_cold={lat_cold:.1f};"
                        f"admit_latency_ratio={lat_hit / max(lat_cold, 1e-9):.3f};"
                        f"speedup_vs_cold={us_cold / us_warm:.2f}x;"
                        f"pages_live={pg['live_pages']};"
                        f"pages_retained={pg['retained_pages']};"
                        f"pages_shares={pg['shares']}")})
    return rows


def _chaos_rows(cfg, params, smoke):
    """ISSUE 6 rows: fault-free monitoring cost of the fault-tolerant
    serving runtime.  The same continuous queue is served plain and with
    the full monitoring stack armed (accuracy-watchdog probes every
    ``probe_every`` segments, a restorable host snapshot every
    ``snapshot_every`` segments) — no faults injected, so
    ``overhead_vs_plain`` is pure monitoring cost, the ratio
    tools/bench_regression.py bounds in CI.  Full mode adds a chaos-drill
    counters row (runtime/serving.chaos_drill: injected segment failure +
    page-pool bit flips + deadline expiry + stuck-at macro fault)."""
    from repro.launch.serve import serve_continuous
    from repro.runtime.serving import chaos_drill, watchdog_for_spec
    n_tokens = 4 if smoke else 16
    slots = 2 if smoke else 4
    R = 4 if smoke else 8
    seg_len = 4
    probe_every, snapshot_every = 8, 8
    prompt_len = 8
    reps = 1 if smoke else 3
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab, (R, prompt_len), dtype=np.int32)
    budgets = np.linspace(2, n_tokens, R).round().astype(np.int32)
    rng.shuffle(budgets)
    useful = int(budgets.sum())
    tag = f"{DSCIM}/R{R}s{slots}x{prompt_len}+{n_tokens}"
    knobs = dict(slots=slots, seg_len=seg_len, max_new=budgets, eos_id=-1,
                 kv="int8", page_size=4, prepare=False)

    def plain():
        return serve_continuous(cfg, params, prompts, n_tokens, **knobs)[0]

    mon_stats = {}
    # threshold calibration (one forward of each estimator) is cold-start
    # cost, not per-request serving overhead — build the watchdog once
    monitor = watchdog_for_spec(DSCIM, cfg, params, probe_every=probe_every)

    def monitored():
        # per-run counters (the watchdog object is reused across reps)
        monitor.n_probes = monitor.n_trips = 0
        monitor.history = []
        outs, st = serve_continuous(
            cfg, params, prompts, n_tokens, **knobs, monitor=monitor,
            snapshot_every=snapshot_every)
        mon_stats.update(st)
        return outs

    us_plain = timed(plain, n=reps)
    us_mon = timed(monitored, n=reps)
    shared = (f"useful_tokens={useful};probe_every={probe_every};"
              f"snapshot_every={snapshot_every}")
    rows = [{
        "name": f"serve/chaos_plain/{tag}",
        "us": us_plain,
        "derived": f"tok_s={useful / us_plain * 1e6:.1f};{shared}",
    }, {
        "name": f"serve/chaos_monitored/{tag}",
        "us": us_mon,
        "derived": (f"tok_s={useful / us_mon * 1e6:.1f};"
                    f"overhead_vs_plain={us_mon / us_plain:.3f};"
                    f"probes={mon_stats['probes']};"
                    f"probe_trips={mon_stats['probe_trips']};"
                    f"replays={mon_stats['replays']};"
                    # page-pool occupancy straight from PageAllocator.stats()
                    f"pages_live={mon_stats['pages']['live_pages']};"
                    f"pages_high_water={mon_stats['pages']['high_water']};"
                    f"pages_refusals={mon_stats['pages']['refusals']};"
                    f"{shared}"),
    }]
    if not smoke:
        import time
        t0 = time.perf_counter()
        rep = chaos_drill(log=lambda *a, **k: None)
        us_drill = (time.perf_counter() - t0) * 1e6
        rows.append({
            "name": "serve/chaos_drill/kernel:dscim2:64/R6s3x8+8",
            "us": us_drill,
            "derived": (f"requests={rep['requests']};"
                        f"clean={len(rep['clean'])};"
                        f"replays={rep['replays']};"
                        f"probe_trips={rep['probe_trips']};"
                        f"escalations={rep['escalations']};"
                        f"deadline_cancelled={rep['deadline_cancelled']};"
                        f"corrupted={len(rep['corrupted_requests'])}")})
    return rows


def _integrity_rows(cfg, params, smoke):
    """ISSUE 9 rows: cost and coverage of the checksummed-state integrity
    layer.  The same continuous queue is served with ``integrity='off'``
    and ``integrity='scrub:2'`` — no faults injected, so
    ``overhead_vs_off`` is pure scrubbing cost (digest plane upkeep in
    the jitted write paths + the boundary sweeps), the ratio
    tools/bench_regression.py bounds in CI.  Full mode adds a counters
    row from the self-verifying integrity drill (runtime/serving.py
    ``integrity_drill``: scripted page + weight-plane flips, exact-
    coordinate detection, bitwise-identical repaired outputs)."""
    from repro.launch.serve import serve_continuous
    from repro.runtime.serving import integrity_drill
    n_tokens = 4 if smoke else 16
    slots = 2 if smoke else 4
    R = 4 if smoke else 8
    seg_len = 4
    prompt_len = 8
    reps = 1 if smoke else 3
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab, (R, prompt_len), dtype=np.int32)
    budgets = np.linspace(2, n_tokens, R).round().astype(np.int32)
    rng.shuffle(budgets)
    useful = int(budgets.sum())
    tag = f"{DSCIM}/R{R}s{slots}x{prompt_len}+{n_tokens}"
    knobs = dict(slots=slots, seg_len=seg_len, max_new=budgets, eos_id=-1,
                 kv="int8", page_size=4, prepare=False)

    def off():
        return serve_continuous(cfg, params, prompts, n_tokens, **knobs)[0]

    ig_stats = {}

    def scrubbed():
        outs, st = serve_continuous(cfg, params, prompts, n_tokens,
                                    **knobs, integrity="scrub:2")
        ig_stats.update(st["integrity"])
        return outs

    us_off = timed(off, n=reps)
    us_scrub = timed(scrubbed, n=reps)
    shared = f"useful_tokens={useful};period=2"
    rows = [{
        "name": f"serve/integrity_off/{tag}",
        "us": us_off,
        "derived": f"tok_s={useful / us_off * 1e6:.1f};{shared}",
    }, {
        "name": f"serve/integrity_scrub/{tag}",
        "us": us_scrub,
        "derived": (f"tok_s={useful / us_scrub * 1e6:.1f};"
                    f"overhead_vs_off={us_scrub / us_off:.3f};"
                    f"checks={ig_stats['checks']};"
                    f"pages_verified={ig_stats['pages_verified']};"
                    f"weight_planes_verified="
                    f"{ig_stats['weight_planes_verified']};"
                    f"mismatches={ig_stats['page_mismatches'] + ig_stats['weight_mismatches']};"
                    f"repairs={ig_stats['page_repairs'] + ig_stats['weight_repairs']};"
                    f"scrub_time_us={ig_stats['scrub_time_s'] * 1e6:.0f};"
                    f"{shared}"),
    }]
    if not smoke:
        import time
        t0 = time.perf_counter()
        rep = integrity_drill(log=lambda *a, **k: None)
        us_drill = (time.perf_counter() - t0) * 1e6
        rows.append({
            "name": "serve/integrity_drill/kernel:dscim2:64/R6s3x8+8",
            "us": us_drill,
            "derived": (f"requests={rep['requests']};"
                        f"page_repairs={rep['leg1']['page_repairs']};"
                        f"weight_repairs={rep['leg1']['weight_repairs'] + rep['leg2']['weight_repairs']};"
                        f"replays={rep['leg1']['replays'] + rep['leg2']['replays']};"
                        f"checks={rep['leg1']['checks'] + rep['leg2']['checks']};"
                        f"scrub_period={rep['scrub_period']}")})
    return rows


def run(smoke: bool = False):
    from repro.configs import get_arch
    from repro.launch.steps import prepare_serving_params
    from repro.models import get_model

    cfg = dataclasses.replace(get_arch("qwen3-0.6b").reduced(), dscim=DSCIM)
    model = get_model(cfg)
    params = prepare_serving_params(
        cfg, model.init_params(cfg, jax.random.PRNGKey(0)))
    rows = _dispatch_rows(cfg, params, smoke)
    rows += _queue_rows(cfg, params, smoke)
    rows += _spec_rows(cfg, params, smoke)
    rows += _prefix_rows(cfg, params, smoke)
    rows += _chaos_rows(cfg, params, smoke)
    rows += _integrity_rows(cfg, params, smoke)
    cfg_float = dataclasses.replace(cfg, dscim="off")
    params_float = model.init_params(cfg_float, jax.random.PRNGKey(0))
    rows += _paged_kv_rows(cfg_float, params_float, smoke)
    rows += _paged_kernel_rows(cfg_float, params_float, smoke)
    return rows


def main():
    """Prints CSV rows and returns them (benchmarks.run appends them to the
    BENCH_kernels.json trajectory)."""
    smoke = "--smoke" in sys.argv[1:]
    rows = run(smoke=smoke)
    for r in rows:
        emit(r["name"], r["us"], r["derived"])
    return rows


if __name__ == "__main__":
    main()
