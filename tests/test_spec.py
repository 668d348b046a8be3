"""Self-speculative decoding (ISSUE 7): DS-CIM2 drafts, DS-CIM1 verifies,
one device-resident loop (launch/steps.py ``_make_spec_window``).

The load-bearing contract is *bitwise parity*: greedy spec serving must
reproduce target-only greedy serving exactly — same tokens, same KV cache
evolution — for every kv / paged_attn combination, because the verify
pass replays the single-token op sequence per position and the rollback
(core/kvcache.py ``spec_rollback``) reconstructs the committed paged tail
from the window projections.  The noise backends (statistical /
paper_inject) fold output *shape* into their fallback keys, so a batched
(B, T) verify draws different noise than T single-token calls — they are
excluded from the k>0 bitwise guarantee (and documented as such) but
covered by the k=0 fall-through tests.

Sampled decoding is replay-deterministic (the PRNG key rides the loop
carry); a single-row batch stays bitwise-aligned with the non-spec driver
because the key commits once per emitted position — the rejected-draft
RNG-stream test below pins that.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core.kvcache import PageAllocator, spec_rollback
from repro.launch.serve import serve_batch, serve_continuous
from repro.launch.steps import (_draft_cfg, _parse_spec, make_generate_fn,
                                make_segment_fn)
from repro.models import get_model

MODES = ["off", "exact:dscim2:64", "lut:dscim2:64", "bitmatmul:dscim2:64",
         "kernel:dscim2:64", "kernel+attn:dscim2:64",
         "statistical:dscim2:64", "paper_inject:dscim2:64"]
# deterministic estimators: batched verify MVMs are bitwise the
# single-token MVMs, so greedy spec == greedy non-spec exactly
DET_MODES = ["off", "exact:dscim1:256", "lut:dscim1:256",
             "bitmatmul:dscim1:256", "kernel+attn:dscim1:256"]


def _setup(dscim="off", arch="qwen3-0.6b", rows=2):
    cfg = get_arch(arch).reduced()
    if dscim != "off":
        cfg = dataclasses.replace(cfg, dscim=dscim)
    model = get_model(cfg)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    prompts = np.random.default_rng(0).integers(0, cfg.vocab, (rows, 8),
                                                dtype=np.int32)
    return cfg, params, prompts


# ---------------------------------------------------------------------------
# spec parsing / draft-config algebra
# ---------------------------------------------------------------------------

def test_parse_spec():
    assert _parse_spec(None) is None
    assert _parse_spec("") is None
    assert _parse_spec("dscim2:0") is None          # k=0: plain driver
    assert _parse_spec("dscim2:4") == ("dscim2", 4)
    assert _parse_spec("dscim1:2") == ("dscim1", 2)
    for bad in ["dscim2", "dscim3:4", "dscim2:-1", "dscim2:x", "4"]:
        with pytest.raises(ValueError):
            _parse_spec(bad)


def test_draft_cfg_rewrites_operating_point_only():
    cfg = dataclasses.replace(get_arch("qwen3-0.6b").reduced(),
                              dscim="kernel+attn:dscim1:256:minmax")
    d = _draft_cfg(cfg, "dscim2")
    assert d.dscim == "kernel+attn:dscim2:64:minmax"
    assert cfg.dscim == "kernel+attn:dscim1:256:minmax"  # original intact
    # off/float have no estimator to cheapen: degenerate self-draft
    for spec in ["off", "float"]:
        c = dataclasses.replace(cfg, dscim=spec)
        assert _draft_cfg(c, "dscim2").dscim == spec


# ---------------------------------------------------------------------------
# greedy bitwise parity — the tentpole acceptance criterion
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv,paged_attn", [("float", "auto"),
                                           ("int8", "jnp"),
                                           ("int8", "kernel")])
def test_spec_greedy_bitwise_kv_combos(kv, paged_attn):
    """kernel:dscim1 verify with dscim2 drafts rejects often enough to
    leave windows page-misaligned — page_size=4 forces draft writes across
    page boundaries, exercising the tail restore + spec_rollback paths."""
    cfg, params, prompts = _setup("kernel:dscim1:256")
    t_ref, _ = serve_batch(cfg, params, prompts, 8, kv=kv, page_size=4,
                           paged_attn=paged_attn)
    t_spec, _, ss = serve_batch(cfg, params, prompts, 8, kv=kv, page_size=4,
                                paged_attn=paged_attn, spec="dscim2:3",
                                spec_stats=True)
    np.testing.assert_array_equal(np.asarray(t_spec), np.asarray(t_ref))
    # every live row emits >= 1 token per verify window
    assert (ss["emitted"] >= ss["windows"]).all()
    assert (ss["emitted"] == 8).all()


@pytest.mark.parametrize("dscim", DET_MODES)
def test_spec_greedy_bitwise_modes(dscim):
    cfg, params, prompts = _setup(dscim)
    t_ref, _ = serve_batch(cfg, params, prompts, 6)
    t_spec, _ = serve_batch(cfg, params, prompts, 6, spec="dscim2:2")
    np.testing.assert_array_equal(np.asarray(t_spec), np.asarray(t_ref))


def test_spec_composes_with_eos_and_budget():
    """EOS early-exit + per-slot budgets under spec: emitted rows stop at
    EOS/budget exactly where the non-spec while_loop stops them."""
    cfg, params, prompts = _setup("kernel:dscim1:256")
    kw = dict(kv="int8", page_size=4, eos_id=14, max_new=[6, 4])
    t_ref, _ = serve_batch(cfg, params, prompts, 8, **kw)
    t_spec, _ = serve_batch(cfg, params, prompts, 8, spec="dscim2:3", **kw)
    np.testing.assert_array_equal(np.asarray(t_spec), np.asarray(t_ref))


# ---------------------------------------------------------------------------
# ISSUE 7 satellite: k=0 falls through to the plain driver, all modes and
# samplers — and rejected-draft RNG streams stay aligned with non-spec
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dscim", MODES)
def test_spec_k0_matches_plain_driver(dscim):
    cfg, params, prompts = _setup(dscim)
    for sample in ["greedy", "temp:0.8", "topk:8:0.9", "topp:0.9"]:
        kw = dict(eos_id=7, sample=sample, rng_seed=3)
        t_ref, _ = serve_batch(cfg, params, prompts, 4, **kw)
        t0, _ = serve_batch(cfg, params, prompts, 4, spec="dscim2:0", **kw)
        np.testing.assert_array_equal(np.asarray(t0), np.asarray(t_ref),
                                      err_msg=f"{dscim} {sample}")


def test_spec_rejected_drafts_leave_rng_stream_aligned():
    """Single-row sampled serving: the carried key commits exactly once
    per *emitted* position (rejected draft positions consume nothing), so
    the i-th draw uses the same chain state as the non-spec driver's i-th
    step — greedy dscim2 drafts against temperature sampling reject
    constantly, making this the rejected-draft stream test."""
    cfg, params, prompts = _setup("kernel:dscim1:256", rows=1)
    for kv in ["float", "int8"]:
        kw = dict(kv=kv, page_size=4, sample="temp:0.8", rng_seed=5)
        t_ref, _ = serve_batch(cfg, params, prompts, 8, **kw)
        t_spec, _, ss = serve_batch(cfg, params, prompts, 8, spec="dscim2:3",
                                    spec_stats=True, **kw)
        np.testing.assert_array_equal(np.asarray(t_spec), np.asarray(t_ref),
                                      err_msg=f"kv={kv}")
        # sampling vs greedy drafts must actually have rejected something,
        # or this test pinned nothing
        assert int(ss["windows"][0]) > (8 - 1 + 3) // 4, ss


def test_spec_sampled_replay_deterministic():
    cfg, params, prompts = _setup("kernel:dscim1:256")
    kw = dict(kv="int8", page_size=4, sample="temp:0.8", rng_seed=3,
              spec="dscim2:3")
    a, _ = serve_batch(cfg, params, prompts, 8, **kw)
    b, _ = serve_batch(cfg, params, prompts, 8, **kw)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# the loop stays device-resident: jaxpr shape of the spec drivers
# ---------------------------------------------------------------------------

def _count_scans(jaxpr, length) -> int:
    def subs(v):
        if hasattr(v, "jaxpr"):
            return [v.jaxpr]
        if hasattr(v, "eqns"):
            return [v]
        if isinstance(v, (list, tuple)):
            return [j for x in v for j in subs(x)]
        return []

    n = sum(1 for e in jaxpr.eqns
            if e.primitive.name == "scan" and e.params.get("length") == length)
    for e in jaxpr.eqns:
        for v in e.params.values():
            n += sum(_count_scans(j, length) for j in subs(v))
    return n


def _count_whiles(jaxpr) -> int:
    def subs(v):
        if hasattr(v, "jaxpr"):
            return [v.jaxpr]
        if hasattr(v, "eqns"):
            return [v]
        if isinstance(v, (list, tuple)):
            return [j for x in v for j in subs(x)]
        return []

    n = sum(1 for e in jaxpr.eqns if e.primitive.name == "while")
    for e in jaxpr.eqns:
        for v in e.params.values():
            n += sum(_count_whiles(j) for j in subs(v))
    return n


def test_spec_segment_is_one_scan_accept_in_carry():
    """A spec segment is still ONE top-level lax.scan of seg_len windows
    (one host dispatch per segment); the k-step draft scan and the
    (k+1)-step accept fold live *inside* it — accept/reject never leaves
    the device carry."""
    from repro.launch.steps import init_serve_state, prepare_serving_params
    cfg, params, _ = _setup("kernel:dscim1:256")
    seg_len, k = 3, 4
    assert len({seg_len, k, k + 1, cfg.n_layers}) == 4  # no length clashes
    pp = prepare_serving_params(cfg, params)
    state = init_serve_state(cfg, 2, 16, kv="int8", page_size=4)
    seg = make_segment_fn(cfg, None, seg_len, jit=False,
                          spec=f"dscim2:{k}")
    jaxpr = jax.make_jaxpr(seg)(pp, state)
    assert _count_scans(jaxpr.jaxpr, seg_len) == 1
    assert _count_scans(jaxpr.jaxpr, k) >= 1       # draft loop, inside
    assert _count_scans(jaxpr.jaxpr, k + 1) >= 1   # accept fold, inside


def test_spec_generate_is_one_while_loop():
    from repro.launch.steps import prepare_serving_params
    cfg, params, prompts = _setup("kernel:dscim1:256")
    pp = prepare_serving_params(cfg, params)
    gen = make_generate_fn(cfg, None, 8, jit=False, spec="dscim2:3")
    jaxpr = jax.make_jaxpr(gen)(pp, {"tokens": jnp.asarray(prompts)})
    assert _count_whiles(jaxpr.jaxpr) == 1


def test_spec_rejects_trace_logits_and_host_loop():
    cfg, params, prompts = _setup("kernel:dscim1:256")
    with pytest.raises(ValueError):
        make_generate_fn(cfg, None, 8, trace_logits=True, spec="dscim2:3")
    with pytest.raises(ValueError):
        serve_batch(cfg, params, prompts, 4, scan=False, spec="dscim2:3")


# ---------------------------------------------------------------------------
# ISSUE 7 satellite: builder-cache keying — every serving knob must key a
# fresh executable (stale-cache aliasing here silently serves wrong math)
# ---------------------------------------------------------------------------

def test_builder_cache_keys_every_knob():
    cfg, _, _ = _setup()
    base = dict(n_tokens=7)
    g = make_generate_fn(cfg, None, 7)
    assert g is make_generate_fn(cfg, None, 7)
    for flip in [dict(spec="dscim2:2"), dict(sample="temp:0.5"),
                 dict(paged_attn="jnp"), dict(kv="int8"), dict(eos_id=3)]:
        assert g is not make_generate_fn(cfg, None, 7, **flip), flip
    s = make_segment_fn(cfg, None, 4)
    assert s is make_segment_fn(cfg, None, 4)
    for flip in [dict(spec="dscim2:2"), dict(sample="temp:0.5"),
                 dict(paged_attn="jnp"), dict(eos_id=3)]:
        assert s is not make_segment_fn(cfg, None, 4, **flip), flip
    # distinct spec strings are distinct executables (k is static)
    assert make_generate_fn(cfg, None, 7, spec="dscim2:2") is not \
        make_generate_fn(cfg, None, 7, spec="dscim2:3")


# ---------------------------------------------------------------------------
# paged rollback + allocator accounting
# ---------------------------------------------------------------------------

def test_spec_rollback_rebuilds_committed_tail():
    """After a rejected window crossed a page boundary the committed tail
    page must be rebuilt: offsets >= pos0 from the window projections,
    offsets below it (same page, committed before the window) from the
    pre-window tail.  Pool planes and the page table are untouched —
    rollback never talks to the PageAllocator."""
    rng = np.random.default_rng(0)
    L, B, ps, KV, HD, T = 2, 3, 4, 2, 5, 3        # layer-stacked planes
    pos0 = jnp.asarray([6, 5, 4], jnp.int32)
    new_pos = jnp.asarray([9, 5, 6], jnp.int32)   # cross / reject-all / mid
    tails0 = tuple(jnp.asarray(rng.normal(size=(L, B, ps, KV, HD)),
                               jnp.float32) for _ in range(2))
    win = tuple(jnp.asarray(rng.normal(size=(L, B, T, KV, HD)), jnp.float32)
                for _ in range(2))
    cache = {"k_pages": jnp.zeros((L, 8, ps, KV, HD), jnp.int8),
             "k_tail": jnp.asarray(rng.normal(size=(L, B, ps, KV, HD)),
                                   jnp.float32),
             "v_tail": jnp.asarray(rng.normal(size=(L, B, ps, KV, HD)),
                                   jnp.float32),
             "pos": pos0}
    out = spec_rollback(cache, pos0, new_pos, tails0, win)
    np.testing.assert_array_equal(np.asarray(out["pos"]),
                                  np.asarray(new_pos))
    for plane, t0, w in [("k_tail", tails0[0], win[0]),
                         ("v_tail", tails0[1], win[1])]:
        got = np.asarray(out[plane])
        for b in range(B):
            base = int(new_pos[b]) // ps * ps
            for o in range(ps):
                i = base + o                       # stream index of offset o
                if i >= int(new_pos[b]):
                    continue                       # uncommitted: don't-care
                exp = np.asarray(w)[:, b, i - int(pos0[b])] \
                    if i >= int(pos0[b]) else np.asarray(t0)[:, b, o]
                np.testing.assert_array_equal(got[:, b, o], exp,
                                              err_msg=f"{plane} b={b} o={o}")
    # dense cache: pos truncation only
    d = spec_rollback({"pos": pos0, "k": 1}, pos0, new_pos)
    assert np.asarray(d["pos"]).tolist() == np.asarray(new_pos).tolist()
    assert d["k"] == 1


def test_page_allocator_stats():
    a = PageAllocator(4)
    st0 = a.stats()
    assert {k: st0[k] for k in ("n_pages", "live_pages", "high_water",
                                "refusals")} \
        == {"n_pages": 4, "live_pages": 0, "high_water": 0, "refusals": 0}
    # the ISSUE 10 sharing counters start at zero and stay there on the
    # non-prefix path exercised here
    assert {k: st0[k] for k in ("shared_pages", "retained_pages", "shares",
                                "reclaimed")} \
        == {"shared_pages": 0, "retained_pages": 0, "shares": 0,
            "reclaimed": 0}
    p1 = a.alloc(3)
    assert a.stats()["live_pages"] == 3 and a.stats()["high_water"] == 3
    assert a.alloc(2) is None                     # refused, pool exhausted
    assert a.stats()["refusals"] == 1
    a.free(p1)
    p2 = a.alloc(4)
    st = a.stats()
    assert {k: st[k] for k in ("n_pages", "live_pages", "high_water",
                               "refusals")} \
        == {"n_pages": 4, "live_pages": 4, "high_water": 4, "refusals": 1}
    # counters survive the snapshot/restore failover path
    b = PageAllocator.from_snapshot(a.snapshot())
    assert b.stats() == st
    b.free(p2)
    assert b.stats()["live_pages"] == 0 and b.stats()["high_water"] == 4


# ---------------------------------------------------------------------------
# continuous serving composition: scheduler, deadlines, watchdog
# ---------------------------------------------------------------------------

def test_spec_continuous_bitwise_and_no_page_leak():
    cfg, params, _ = _setup("kernel:dscim1:256")
    prompts = np.random.default_rng(1).integers(0, cfg.vocab, (5, 8),
                                                dtype=np.int32)
    kw = dict(slots=2, seg_len=2, kv="int8", page_size=4, eos_id=14)
    o_ref, _ = serve_continuous(cfg, params, prompts, 8, **kw)
    o_spec, st = serve_continuous(cfg, params, prompts, 8, spec="dscim2:3",
                                  **kw)
    for r, (a, b) in enumerate(zip(o_spec, o_ref)):
        np.testing.assert_array_equal(a, b, err_msg=f"request {r}")
    # every page returned to the allocator; occupancy rows read these
    assert st["pages"]["live_pages"] == 0
    assert st["pages"]["high_water"] >= 1
    assert st["pages"]["n_pages"] >= st["pages"]["high_water"]


def test_spec_deadline_counts_rejected_draft_positions():
    """deadline_steps is a verifier-position ledger: one spec segment
    attempts seg_len * (k+1) positions whether or not drafts are
    accepted, so a budget of exactly that expires the request at the
    first boundary — where the non-spec run takes k+1 x more segments."""
    cfg, params, _ = _setup("kernel:dscim1:256")
    prompts = np.random.default_rng(0).integers(0, cfg.vocab, (1, 8),
                                                dtype=np.int32)
    seg_len, k = 2, 3
    kw = dict(slots=1, seg_len=seg_len, kv="int8", page_size=4, eos_id=-1,
              deadline_steps=[seg_len * (k + 1)])
    o_s, st_s = serve_continuous(cfg, params, prompts, 16,
                                 spec=f"dscim2:{k}", **kw)
    o_n, st_n = serve_continuous(cfg, params, prompts, 16, **kw)
    assert st_s["status"] == ["deadline"] and st_n["status"] == ["deadline"]
    assert st_s["segments"] == 1                  # expired after one segment
    assert st_n["segments"] == k + 1              # same ledger, k+1 segments
    # partial tokens kept, and the greedy streams agree on their overlap
    n = min(len(o_s[0]), len(o_n[0]))
    assert n >= 1
    np.testing.assert_array_equal(o_s[0][:n], o_n[0][:n])


def test_spec_watchdog_probes_the_verifier():
    """aux['logits0'] under spec is the first window's verify logits at
    position 0 — verifier estimator, same (token, cache) inputs as the
    exact probe.  A healthy dscim1 run must sit under the threshold
    calibrated for dscim1, with a drift nearer dscim1's than the draft
    (dscim2) estimator's, which leaking draft logits would show."""
    from repro.runtime.serving import healthy_logit_drift, watchdog_for_spec
    spec = "kernel:dscim1:256"
    cfg, params, _ = _setup(spec)
    prompts = np.random.default_rng(2).integers(0, cfg.vocab, (2, 8),
                                                dtype=np.int32)
    mon = watchdog_for_spec(spec, cfg, params, probe_every=1)
    _, st = serve_continuous(cfg, params, prompts, 6, slots=2, seg_len=2,
                             kv="int8", page_size=4, eos_id=-1,
                             monitor=mon, spec="dscim2:2")
    assert st["probes"] >= 1
    assert st["probe_trips"] == 0 and st["quarantined"] == []
    d1 = mon.rel_threshold / 3.0
    d2 = healthy_logit_drift("kernel:dscim2:64", cfg, params)
    assert np.mean(np.concatenate(mon.history)) < (d1 + d2) / 2
