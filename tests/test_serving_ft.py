"""Fault-tolerant serving runtime (ISSUE 6, runtime/serving.py): serve-
state snapshot/restore + failover replay (bitwise across KV layouts and
paged-attention read paths), preemptive priority eviction with mid-stream
re-admission parity, deadline cancellation with partial outputs, the
accuracy watchdog + degradation ladder (drift *and* NaN trips), the page-
allocator hardening, the sampler degenerate-row guard, and the end-to-end
chaos drill that pins the whole acceptance contract at once."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core.kvcache import (PageAllocator, extract_slot_pages,
                                insert_slot_pages, n_pages_for)
from repro.launch.serve import serve_continuous
from repro.models import get_model
from repro.runtime.failover import FailureInjector, flip_bits
from repro.runtime.serving import (STATUS_DEADLINE, STATUS_OK,
                                   chaos_drill, exact_probe_spec,
                                   healthy_logit_drift, next_ladder_spec,
                                   watchdog_for_spec)
from repro.runtime.watchdog import AccuracyWatchdog


def _setup(dscim="off", arch="qwen3-0.6b"):
    cfg = get_arch(arch).reduced()
    if dscim != "off":
        cfg = dataclasses.replace(cfg, dscim=dscim)
    model = get_model(cfg)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, model, params


BUDGETS = np.array([2, 5, 3, 4, 6, 1], np.int32)


# --------------------------------------------------------------------------
# page-allocator hardening (satellite a) + blob round trip
# --------------------------------------------------------------------------

def test_page_allocator_free_validation():
    """free() rejects double frees, never-allocated ids and out-of-range
    ids instead of silently corrupting the free list — the classic way a
    scheduler bug turns into cross-request page aliasing."""
    a = PageAllocator(8)
    g1 = a.alloc(3)
    a.free(g1)
    with pytest.raises(ValueError, match="double free"):
        a.free(g1)                         # already back in the pool
    g2 = a.alloc(2)
    with pytest.raises(ValueError, match="double free"):
        a.free(g2 + [g2[0]])               # duplicate inside one call
    with pytest.raises(ValueError, match="never allocated|double free"):
        a.free([7])                        # never handed out
    with pytest.raises(ValueError, match="out of range"):
        a.free([8])
    with pytest.raises(ValueError, match="out of range"):
        a.free([-1])
    # a rejected call must not have committed anything: g2 still live
    assert a.free_pages == 6
    a.free(g2)
    assert a.free_pages == 8


def test_page_allocator_snapshot_roundtrip():
    a = PageAllocator(6)
    g1 = a.alloc(2)
    a.alloc(3)
    a.free(g1)
    snap = a.snapshot()
    b = PageAllocator.from_snapshot(snap)
    assert b.free_pages == a.free_pages == 3
    # identical allocation behaviour from the restored free list
    assert a.alloc(3) == b.alloc(3)
    assert a.alloc(1) is None and b.alloc(1) is None
    # the snapshot is a value, not a view
    snap2 = a.snapshot()
    a.free(g1)
    assert PageAllocator.from_snapshot(snap2).free_pages == 0


def test_page_allocator_random_schedule_properties():
    """Property-style hammer (ISSUE 8 satellite): random grant / free /
    snapshot-restore schedules checked against a reference model after
    every op.  Invariants: a grant never overlaps live pages (the
    double-grant corruption), free-page accounting is exact, high-water
    is the monotone peak of concurrent live pages, refusals are counted
    (not silently retried), a snapshot round-trip is behaviour-preserving
    mid-schedule, and a full drain leaks nothing — the whole pool
    re-allocates."""
    for seed in range(5):
        rng = np.random.default_rng(seed)
        n = int(rng.integers(4, 33))
        a = PageAllocator(n)
        grants, live = [], set()      # the reference model
        peak = refusals = 0
        for _ in range(300):
            op = rng.random()
            if op < 0.5:
                k = int(rng.integers(1, max(2, n // 2)))
                ids = a.alloc(k)
                if k > n - len(live):
                    assert ids is None
                    refusals += 1
                else:
                    assert ids is not None and len(set(ids)) == k
                    assert all(0 <= i < n for i in ids)
                    assert not set(ids) & live, "double grant"
                    live.update(ids)
                    grants.append(ids)
                    peak = max(peak, len(live))
            elif op < 0.85 and grants:
                g = grants.pop(int(rng.integers(len(grants))))
                a.free(g)
                live.difference_update(g)
            else:
                a = PageAllocator.from_snapshot(a.snapshot())
            assert a.free_pages == n - len(live)
            st = a.stats()
            assert st["live_pages"] == len(live)
            assert st["high_water"] == peak
            assert st["refusals"] == refusals
        for g in grants:
            a.free(g)
        assert a.free_pages == n and a.stats()["live_pages"] == 0
        assert sorted(a.alloc(n)) == list(range(n))


def test_slot_page_blob_roundtrip():
    """extract_slot_pages -> insert_slot_pages restores a slot's share of
    the pool (pages, scales, tail, page-table row, position) bit-exactly
    into different physical pages — the eviction/re-admission primitive."""
    from repro.core.kvcache import init_paged_cache
    L, B, P, ps, KV, HD, mp = 2, 2, 8, 4, 2, 8, 3
    rng = np.random.default_rng(0)
    cache = init_paged_cache(L, B, P, ps, mp, KV, HD)
    cache = {
        **cache,
        "k_pages": jnp.asarray(rng.integers(-127, 128, (L, P, ps, KV, HD)),
                               jnp.int8),
        "v_pages": jnp.asarray(rng.integers(-127, 128, (L, P, ps, KV, HD)),
                               jnp.int8),
        "k_scale": jnp.asarray(rng.normal(1, .1, (L, P, KV)), jnp.float32),
        "v_scale": jnp.asarray(rng.normal(1, .1, (L, P, KV)), jnp.float32),
        "k_tail": jnp.asarray(rng.normal(0, 1, (L, B, ps, KV, HD)),
                              jnp.bfloat16),
        "v_tail": jnp.asarray(rng.normal(0, 1, (L, B, ps, KV, HD)),
                              jnp.bfloat16),
        "page_table": jnp.asarray([[0, 1, 1], [2, 3, 3]], jnp.int32),
        "pos": jnp.asarray([7, 6], jnp.int32),
    }
    blob = extract_slot_pages(cache, 0, [0, 1])
    assert blob["page_count"] == 2 and blob["pos"] == 7
    restored = insert_slot_pages(cache, 0, [5, 6], blob)  # new physical ids
    np.testing.assert_array_equal(np.asarray(restored["k_pages"][:, 5]),
                                  np.asarray(cache["k_pages"][:, 0]))
    np.testing.assert_array_equal(np.asarray(restored["v_pages"][:, 6]),
                                  np.asarray(cache["v_pages"][:, 1]))
    np.testing.assert_array_equal(np.asarray(restored["k_scale"][:, 5]),
                                  np.asarray(cache["k_scale"][:, 0]))
    np.testing.assert_array_equal(
        np.asarray(restored["k_tail"][:, 0], np.float32),
        np.asarray(cache["k_tail"][:, 0], np.float32))
    assert np.asarray(restored["page_table"][0]).tolist() == [5, 6, 6]
    assert int(restored["pos"][0]) == 7
    # the other slot's state is untouched
    np.testing.assert_array_equal(np.asarray(restored["page_table"][1]),
                                  np.asarray(cache["page_table"][1]))
    with pytest.raises(ValueError, match="parked but"):
        insert_slot_pages(cache, 0, [5], blob)


# --------------------------------------------------------------------------
# sampler degenerate-row guard (satellite b)
# --------------------------------------------------------------------------

def test_sampler_degenerate_row_guard():
    """top-k/top-p rows that mask everything (or go NaN upstream) fall
    back to per-row greedy instead of sampling garbage from a uniform-
    over-everything distribution; healthy rows keep drawing."""
    from repro.launch.steps import _make_sampler
    draw = _make_sampler("topk:2:1.0")
    key = jax.random.PRNGKey(0)
    logits = jnp.asarray([
        [0.0, 3.0, 1.0, 2.0],                        # healthy
        [-jnp.inf, -jnp.inf, -jnp.inf, -jnp.inf],    # fully masked
        [jnp.nan, 0.5, jnp.nan, 0.2],                # NaN poisoned
        [1.0, jnp.inf, 0.0, 0.0],                    # +inf spike
    ])
    toks = np.asarray(draw(key, logits))
    assert toks[0] in (1, 3)          # top-2 of the healthy row
    assert toks[1] == 0               # all -inf: greedy argmax fallback
    assert toks[2] == 1               # NaN masked out of the argmax
    assert toks[3] == 1               # inf row: the spike is the argmax
    # the guard must not perturb healthy-row draws: all-healthy batch
    # draws the same token for row 0 under the same key
    healthy = jnp.tile(logits[0:1], (4, 1))
    assert np.asarray(draw(key, healthy))[0] == toks[0]


def test_sampler_degenerate_topp():
    from repro.launch.steps import _make_sampler
    draw = _make_sampler("topp:0.5:1.0")
    key = jax.random.PRNGKey(1)
    logits = jnp.asarray([[0.1, 0.9, 0.2, 0.3],
                          [jnp.nan, jnp.nan, jnp.nan, jnp.nan]])
    toks = np.asarray(draw(key, logits))
    assert toks[1] == 0               # all-NaN row: deterministic fallback


# --------------------------------------------------------------------------
# accuracy watchdog + ladder algebra
# --------------------------------------------------------------------------

def test_accuracy_watchdog_check():
    wd = AccuracyWatchdog(rel_threshold=0.5, probe_every=2)
    assert wd.should_probe(0) and not wd.should_probe(1) \
        and wd.should_probe(2)
    exact = np.ones((3, 8))
    near = exact + 0.01
    far = exact + 10.0
    nan = exact.copy()
    nan[2, 0] = np.nan
    live = np.asarray([True, True, False])
    trip, rel = wd.check(np.stack([near[0], far[1], nan[2]]), exact, live)
    assert not trip[0] and rel[0] < 0.1
    assert trip[1] and rel[1] > 1.0
    assert not trip[2]                # dead slots never trip (NaN or not)
    trip2, _ = wd.check(nan, exact, np.asarray([True, True, True]))
    assert trip2[2]                   # live NaN row trips regardless
    assert wd.n_probes == 2 and wd.n_trips == 2
    with pytest.raises(ValueError, match="probe_every"):
        AccuracyWatchdog(0.5, probe_every=0)


def test_ladder_spec_algebra():
    assert next_ladder_spec("kernel:dscim2:64") == "kernel:dscim1:256"
    assert next_ladder_spec("kernel:dscim1:256") == "exact:dscim1:256"
    assert next_ladder_spec("lut+attn:dscim2:64:opt") \
        == "lut+attn:dscim1:256:opt"
    assert next_ladder_spec("exact:dscim1:256") is None
    assert next_ladder_spec("off") is None
    assert exact_probe_spec("kernel+attn:dscim2:64") \
        == "exact+attn:dscim2:64"
    assert exact_probe_spec("off") == "off"


def test_relative_moment_bound_scales():
    """The drift threshold is a margin over the served model's measured
    healthy drift: the noisier dscim2:64 point gets the looser threshold,
    and an armed macro fault does not leak into the calibration."""
    cfg, model, params = _setup("kernel:dscim1:256")
    d1 = healthy_logit_drift("kernel:dscim1:256", cfg, params)
    d2 = healthy_logit_drift("kernel:dscim2:64", cfg, params)
    assert 0 < d1 < d2                # dscim2 is the noisier point
    wd = watchdog_for_spec("kernel:dscim2:64", cfg, params, probe_every=4)
    assert wd.rel_threshold == pytest.approx(3.0 * d2)
    assert wd.probe_every == 4
    faulted = dataclasses.replace(cfg, dscim_fault="stuck:3:40.0")
    assert healthy_logit_drift("kernel:dscim2:64", faulted, params) \
        == pytest.approx(d2)


# --------------------------------------------------------------------------
# snapshot/restore + failover replay: bitwise parity (satellite d)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("kv,paged_attn", [("float", "auto"),
                                           ("int8", "jnp"),
                                           ("int8", "kernel")])
def test_failover_replay_bitwise(kv, paged_attn):
    """A mid-stream device loss + snapshot restore replays the serve
    bit-identically to the uninterrupted run — across the dense and
    paged KV layouts and both paged-attention read paths."""
    cfg, model, params = _setup()
    prompts = np.random.default_rng(0).integers(0, cfg.vocab, (6, 8),
                                                dtype=np.int32)
    knobs = dict(slots=3, seg_len=2, max_new=BUDGETS, eos_id=-1, kv=kv,
                 page_size=4, paged_attn=paged_attn)
    ref, _ = serve_continuous(cfg, params, prompts, 6, **knobs)
    outs, stats = serve_continuous(cfg, params, prompts, 6, **knobs,
                                   injector=FailureInjector(fail_at=(2,)),
                                   snapshot_every=1, log=lambda *a: None)
    assert stats["replays"] == 1
    assert stats["status"] == [STATUS_OK] * 6
    for r, (a, b) in enumerate(zip(outs, ref)):
        np.testing.assert_array_equal(a, b, err_msg=f"request {r}")


def test_failover_exhausts_replays():
    """An unrecoverable fault pattern (fresh failure every segment beyond
    the budget) surfaces instead of looping forever."""
    from repro.runtime.failover import SimulatedHardwareFailure
    cfg, model, params = _setup()
    prompts = np.random.default_rng(0).integers(0, cfg.vocab, (2, 8),
                                                dtype=np.int32)
    with pytest.raises(SimulatedHardwareFailure):
        serve_continuous(cfg, params, prompts, 4, slots=2, seg_len=2,
                         eos_id=-1, max_new=np.asarray([4, 4], np.int32),
                         injector=FailureInjector(fail_at=(0, 1, 2, 3)),
                         snapshot_every=1, max_replays=2,
                         log=lambda *a: None)


# --------------------------------------------------------------------------
# preemptive eviction + re-admission (tentpole) and deadlines
# --------------------------------------------------------------------------

def test_eviction_readmission_bitwise_parity():
    """A high-priority admission preempts the youngest lower-priority
    slot; the evictee's pages round-trip host-side and it resumes
    mid-stream — bit-identical (greedy) to a run with a big-enough pool
    that never evicts."""
    cfg, model, params = _setup()
    prompts = np.random.default_rng(0).integers(0, cfg.vocab, (6, 8),
                                                dtype=np.int32)
    budgets = np.array([6, 8, 8, 6, 6, 6], np.int32)
    prio = np.array([0, 0, 5, 0, 0, 0], np.int64)
    mp = n_pages_for(8 + 8, 4)
    knobs = dict(slots=3, seg_len=2, max_new=budgets, eos_id=-1,
                 kv="int8", page_size=4)
    big, _ = serve_continuous(cfg, params, prompts, 8, **knobs)
    outs, stats = serve_continuous(cfg, params, prompts, 8, **knobs,
                                   n_pages=2 * mp, priority=prio)
    assert stats["evictions"] >= 1 and stats["readmissions"] >= 1
    assert stats["evicted_requests"], stats
    assert stats["status"] == [STATUS_OK] * 6
    for r, (a, b) in enumerate(zip(outs, big)):
        np.testing.assert_array_equal(a, b, err_msg=f"request {r}")


def test_eviction_requires_strictly_higher_priority():
    """Equal priorities never evict (livelock guard): the scheduler falls
    back to the PR-4 wait-for-pages behaviour."""
    cfg, model, params = _setup()
    prompts = np.random.default_rng(2).integers(0, cfg.vocab, (4, 8),
                                                dtype=np.int32)
    budgets = np.array([3, 4, 2, 3], np.int32)
    mp = n_pages_for(8 + 4, 4)
    outs, stats = serve_continuous(cfg, params, prompts, 4, slots=3,
                                   seg_len=2, max_new=budgets, eos_id=-1,
                                   kv="int8", page_size=4, n_pages=2 * mp,
                                   priority=np.zeros(4, np.int64))
    assert stats["evictions"] == 0
    assert [len(o) for o in outs] == budgets.tolist()


def test_deadline_step_cancellation():
    """A step-budget expiry cancels between segments: definite 'deadline'
    status, partial tokens kept, slot + pages recycled for the queue."""
    cfg, model, params = _setup()
    prompts = np.random.default_rng(0).integers(0, cfg.vocab, (6, 8),
                                                dtype=np.int32)
    dl = np.array([-1, 2, -1, -1, -1, -1], np.int64)
    outs, stats = serve_continuous(cfg, params, prompts, 6, slots=3,
                                   seg_len=2, max_new=BUDGETS, eos_id=-1,
                                   kv="int8", page_size=4,
                                   deadline_steps=dl)
    assert stats["status"][1] == STATUS_DEADLINE
    assert stats["deadline_cancelled"] == 1
    assert 0 < len(outs[1]) < int(BUDGETS[1])     # partial, not empty
    for r in (0, 2, 3, 4, 5):
        assert stats["status"][r] == STATUS_OK
        assert len(outs[r]) == int(BUDGETS[r])


def test_deadline_expired_while_waiting():
    """A queued request whose deadline passes before it ever gets a slot
    is cancelled with empty output, not served late."""
    cfg, model, params = _setup()
    prompts = np.random.default_rng(0).integers(0, cfg.vocab, (4, 8),
                                                dtype=np.int32)
    budgets = np.array([6, 6, 6, 4], np.int32)
    dl = np.array([-1, -1, -1, 2], np.int64)
    outs, stats = serve_continuous(cfg, params, prompts, 6, slots=2,
                                   seg_len=2, max_new=budgets, eos_id=-1,
                                   deadline_steps=dl)
    assert stats["status"][3] == STATUS_DEADLINE and len(outs[3]) == 0
    assert stats["status"][:3] == [STATUS_OK] * 3


class _FakeClock:
    """Deterministic stand-in for the ``time`` module inside
    runtime/serving.py: every ``perf_counter()`` call advances one fake
    second, so queue time and service time become countable quantities
    instead of scheduler-speed noise."""

    def __init__(self):
        self.t = 0.0

    def perf_counter(self) -> float:
        self.t += 1.0
        return self.t


def test_deadline_s_anchors_at_admission(monkeypatch):
    """Staggered admission under a wall budget (ISSUE 8 satellite): with
    one slot, request 1 queues behind request 0's 16-token stream — over
    8 engine rounds (= 8 fake seconds) of waiting.  Its 6-second wall
    budget must anchor at *admission*: service itself takes ~3 rounds, so
    it completes 'ok'.  Anchoring at serve start (the pre-fix behaviour)
    would have expired it in the queue.  A genuinely tight post-admission
    budget still expires with partial output."""
    import repro.runtime.serving as serving
    cfg, model, params = _setup()
    prompts = np.random.default_rng(0).integers(0, cfg.vocab, (2, 8),
                                                dtype=np.int32)
    budgets = np.array([16, 6], np.int32)

    def run(dl1):
        monkeypatch.setattr(serving, "time", _FakeClock())
        dl = np.array([-1.0, dl1], np.float64)
        return serve_continuous(cfg, params, prompts, 16, slots=1,
                                seg_len=2, max_new=budgets, eos_id=-1,
                                kv="int8", page_size=4, deadline_s=dl)

    outs, stats = run(6.0)
    assert stats["status"] == [STATUS_OK, STATUS_OK], stats["status"]
    assert stats["deadline_cancelled"] == 0
    assert [len(o) for o in outs] == budgets.tolist()

    outs, stats = run(0.5)           # < 1 fake second: expires in service
    assert stats["status"] == [STATUS_OK, STATUS_DEADLINE]
    assert stats["deadline_cancelled"] == 1
    assert 0 < len(outs[1]) < int(budgets[1])     # partial tokens kept


# --------------------------------------------------------------------------
# accuracy watchdog end to end: NaN and drift trips -> ladder
# --------------------------------------------------------------------------

class _InfScaleInjector(FailureInjector):
    """Deterministic NaN source: set one live dequant scale to +inf (a
    single XOR flip cannot guarantee NaN through RMSNorm's squashing,
    so the NaN-path test injects the poisoned value directly)."""

    def corrupt_cache(self, segment, cache, slot_pages):
        key = ("inf", 1)
        if segment != 1 or key in self.fired or slot_pages[0] is None:
            return cache, []
        self.fired.add(key)
        pid = int(slot_pages[0][0])
        return dict(cache, v_scale=cache["v_scale"].at[0, pid, 0]
                    .set(np.inf)), [0]


def test_nonfinite_quarantine_escalates():
    """Inf in the KV pool -> NaN logits -> the slot is quarantined the
    same segment (no probe needed), its poisoned tokens discarded, and
    the request re-served down the ladder to a full, definite output."""
    spec = "kernel:dscim2:64"
    cfg, model, params = _setup(spec)
    prompts = np.random.default_rng(0).integers(0, cfg.vocab, (4, 8),
                                                dtype=np.int32)
    budgets = np.asarray([8, 6, 8, 5], np.int32)
    mon = AccuracyWatchdog(None)      # NaN-only monitoring: no probes
    outs, stats = serve_continuous(cfg, params, prompts, 8, slots=2,
                                   seg_len=2, max_new=budgets, eos_id=-1,
                                   kv="int8", page_size=4, monitor=mon,
                                   injector=_InfScaleInjector(),
                                   snapshot_every=1, log=lambda *a: None)
    assert stats["quarantined"] == [0]
    assert stats["probes"] == 0
    esc = [e for e in stats["escalations"] if e["request"] == 0]
    assert esc and esc[0]["reason"] == "nonfinite"
    assert esc[0]["to"] == "kernel:dscim1:256" and esc[0]["accepted"]
    assert stats["status"] == [STATUS_OK] * 4
    assert [len(o) for o in outs] == budgets.tolist()


def test_macro_fault_drift_trips_and_escalates():
    """A persistent stuck-at macro fault drifts every live slot past the
    calibrated threshold; the healthy run never trips (the margin 3 this
    pins: healthy probes within 1.5x the calibrated drift, faulted ~20x)."""
    spec = "kernel:dscim2:64"
    cfg, model, params = _setup(spec)
    prompts = np.random.default_rng(0).integers(0, cfg.vocab, (4, 8),
                                                dtype=np.int32)
    budgets = np.asarray([6, 5, 6, 5], np.int32)
    knobs = dict(slots=2, seg_len=2, max_new=budgets, eos_id=-1,
                 kv="int8", page_size=4, log=lambda *a: None)
    healthy = watchdog_for_spec(spec, cfg, params, probe_every=1)
    outs_h, stats_h = serve_continuous(cfg, params, prompts, 6, **knobs,
                                       monitor=healthy)
    assert stats_h["probe_trips"] == 0 and not stats_h["quarantined"]
    rels = np.concatenate([h[np.isfinite(h)] for h in healthy.history])
    assert rels.max() < healthy.rel_threshold
    faulted = watchdog_for_spec(spec, cfg, params, probe_every=1)
    inj = FailureInjector(macro_fault_at=0, macro_fault="stuck:3:40.0")
    outs_f, stats_f = serve_continuous(cfg, params, prompts, 6, **knobs,
                                       monitor=faulted, injector=inj)
    assert stats_f["probe_trips"] >= 2
    assert stats_f["quarantined"]
    hops = {(e["frm"], e["to"]) for e in stats_f["escalations"]}
    assert ("kernel:dscim2:64", "kernel:dscim1:256") in hops
    assert stats_f["status"] == [STATUS_OK] * 4
    assert [len(o) for o in outs_f] == budgets.tolist()


def test_monitor_rejects_float_serving():
    cfg, model, params = _setup()          # dscim off: nothing to probe
    prompts = np.zeros((2, 8), np.int32)
    with pytest.raises(ValueError, match="exact-mode twin"):
        serve_continuous(cfg, params, prompts, 4, slots=2, seg_len=2,
                         eos_id=-1, monitor=AccuracyWatchdog(0.5))


# --------------------------------------------------------------------------
# fault model plumbing
# --------------------------------------------------------------------------

def test_flip_bits_float_and_int():
    x = jnp.asarray([1.0, 2.0], jnp.float32)
    y = flip_bits(x, (0,), 1 << 30)
    assert np.isinf(np.asarray(y)[0])      # 1.0 ^ exponent-msb = +inf
    assert np.asarray(y)[1] == 2.0
    q = jnp.asarray([[3, -4]], jnp.int8)
    q2 = flip_bits(q, (0, 1), 0x7f)
    assert np.asarray(q2)[0, 1] == -125   # 0xfc ^ 0x7f = 0x83 as int8
    assert np.asarray(q2)[0, 0] == 3


def test_dscim_fault_spec_wraps_operator():
    """cfg.dscim_fault pins every <stride>-th output column without
    touching params — the exact-mode probe on the same prepared weights
    stays clean (the watchdog's isolation property)."""
    from repro.models.lm import _linear_for, _parse_fault
    assert _parse_fault("stuck:5:24.0") == (5, 24.0)
    with pytest.raises(ValueError, match="dscim_fault"):
        _parse_fault("stuck:5")
    with pytest.raises(ValueError, match="stride"):
        _parse_fault("stuck:0:1.0")
    op = _linear_for("lut:dscim1:256", None, "stuck:4:7.5")
    clean = _linear_for("lut:dscim1:256")
    assert op.group_k == clean.group_k
    x = jnp.asarray(np.random.default_rng(0).normal(0, 1, (2, 8)),
                    jnp.float32)
    w = jnp.asarray(np.random.default_rng(1).normal(0, 1, (8, 12)),
                    jnp.float32)
    y = np.asarray(op(x, w))
    assert (y[:, ::4] == 7.5).all()
    np.testing.assert_array_equal(y[:, 1::4],
                                  np.asarray(clean(x, w))[:, 1::4])


# --------------------------------------------------------------------------
# the full acceptance scenario
# --------------------------------------------------------------------------

def test_chaos_drill():
    """The self-verifying end-to-end scenario: device loss + page-pool
    flips + stuck-at macro fault + deadline expiry, every assertion of
    the ISSUE 6 acceptance contract inside chaos_drill itself."""
    report = chaos_drill(log=lambda *a: None)
    assert report["replays"] == 1
    assert report["escalations"] >= 1
    assert report["deadline_cancelled"] == 1
    assert report["clean"]
