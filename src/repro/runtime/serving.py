"""Fault-tolerant continuous-batching scheduler (ISSUE 6 tentpole).

``serve_continuous_ft`` is the robustness layer above the device-resident
continuous-batching loop (launch/serve.py ``serve_continuous`` delegates
here): the jitted segment/admit functions and all generation math are
unchanged, and with every fault-tolerance knob at its default the
scheduler is behaviourally identical to the PR-4 loop.  The additions are
host-side policies that act only *between* scan segments:

* **Deadlines** (``deadline_steps`` / ``deadline_s``): per-request decode-
  step and wall-clock budgets.  Step budgets count from serve start on
  the global step ledger (deterministic, replay-safe); wall budgets are
  anchored at each request's *admission* (PR 8 fix — measuring from
  serve start silently shrank late admissions' budgets), so a queued
  request never wall-expires and every admitted request gets its full
  ``deadline_s`` of service regardless of queue position.  An expired
  request is cancelled at the next segment boundary with status
  ``'deadline'`` and keeps its partial tokens; its slot and physical
  pages recycle immediately.  The admission anchor survives eviction
  round trips (the budget covers the request's whole lifetime, parked
  time included) and rides the host snapshot through failover replays.
* **Preemptive eviction + re-admission** (``priority``, int8 KV only):
  when the page pool cannot satisfy an admission, the scheduler may evict
  a live slot of *strictly lower* priority (strictness prevents same-
  priority livelock), lowest priority first, youngest (latest-admitted)
  on ties.  Eviction snapshots the victim's physical page contents
  bit-exactly (core/kvcache.py ``extract_slot_pages``) — not its tokens-
  so-far for a re-prefill, which would break bitwise continuity through
  float reduction-order changes — and queues it for re-admission
  (``insert_slot_pages``) as pages free up.  A re-admitted request
  resumes mid-stream bit-identically under greedy decoding.
* **Snapshot / restore + failover** (``snapshot_every``, ``injector``):
  every N segment boundaries the full serve state — device pytree
  (``jax.device_get``), host scheduler bookkeeping, page-allocator free
  list — is checkpointed host-side; ``run_with_failover``
  (runtime/failover.py) wraps the segment loop so a recoverable failure
  (injected device loss, watchdog ``StepHang``) restores the latest
  snapshot and replays from that boundary bit-identically.  The
  generalized ``FailureInjector`` drives chaos tests: segment-level
  device loss, transient int8 page-pool bit flips, persistent stuck-at
  DS-CIM macro faults (``cfg.dscim_fault``, models/lm.py).
* **Accuracy watchdog + degradation ladder** (``monitor``): every
  ``probe_every`` segments one extra *exact-mode* decode of the same
  (token, cache) inputs (launch/steps.py ``make_probe_fn``) is compared
  against the segment's first-step serving logits (``aux['logits0']`` —
  computed inside the scan, so the serving side costs nothing extra).
  A slot whose relative logit RMSE exceeds the ``AccuracyWatchdog``
  threshold — a margin over the served model's healthy drift
  (``watchdog_for_spec``) — or whose
  logits go NaN/Inf (checked every segment via ``aux['bad']``) is
  *quarantined*: its poisoned tokens are discarded, its slot and pages
  recycle, and after the main loop the request is re-served from its
  prompt down the degradation ladder ``dscim2 -> dscim1 -> exact``
  (``next_ladder_spec``), each intermediate level verified against its
  exact-mode twin before acceptance.  Estimator faults are caught
  persistently; a transient finite KV corruption registers only when the
  flip lands in a probed segment (NaN corruption is caught regardless) —
  the documented probe-coverage limit.

Determinism & replay notes: a restored replay re-runs the segment
boundary loop on identical state, so greedy decoding replays bit-
identically; sampled decoding replays identically too (the PRNG key
rides the device carry inside the snapshot).  ``FailureInjector`` faults
are keyed by segment and fire once, so a replay neither re-raises the
device loss nor re-applies a transient flip.
"""
from __future__ import annotations

import contextlib
import copy
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kvcache import (PageAllocator, PrefixCache, admission_pages,
                                cow_fork, extract_slot_pages,
                                insert_slot_pages, n_pages_for)
from repro.launch.steps import (_parse_spec, init_serve_state,
                                make_admit_fn, make_extend_fn,
                                make_probe_fn, make_segment_fn)
from repro.runtime.failover import (IntegrityReplay,
                                    SimulatedHardwareFailure,
                                    run_with_failover)
from repro.runtime.integrity import IntegrityEngine, parse_integrity
from repro.runtime.watchdog import AccuracyWatchdog, StepHang

__all__ = ["STATUS_OK", "STATUS_DEADLINE", "serve_continuous_ft",
           "next_ladder_spec", "exact_probe_spec", "healthy_logit_drift",
           "watchdog_for_spec", "chaos_drill", "integrity_drill", "prefix_drill"]

STATUS_OK = "ok"
STATUS_DEADLINE = "deadline"


# --------------------------------------------------------------------------
# degradation-ladder spec algebra
# --------------------------------------------------------------------------

def exact_probe_spec(spec: str) -> str:
    """The exact-mode twin of a dscim serving spec: same variant/L/calib
    (so the same prepared int8 planes apply), same '+attn' scope, exact
    adder-tree MVMs.  'off'/'float' map to themselves."""
    if spec in ("off", "float"):
        return spec
    head, _, rest = spec.partition(":")
    base, plus, attn = head.partition("+")
    return "exact" + plus + attn + (":" + rest if rest else "")


def next_ladder_spec(spec: str) -> str | None:
    """One step down the degradation ladder, or None at the bottom.

    dscim2 (L=64, ~3.8% macro RMSE) -> dscim1:256 (~0.7%) -> the exact-
    mode twin; exact and float specs are terminal.  The mode (kernel/
    lut/...) and '+attn' scope are preserved on the dscim2 -> dscim1 hop
    so only the operating point changes."""
    if spec in ("off", "float"):
        return None
    head, _, rest = spec.partition(":")
    if head.partition("+")[0] == "exact":
        return None
    parts = rest.split(":") if rest else []
    if parts and parts[0] == "dscim2":
        parts[0] = "dscim1"
        if len(parts) > 1:
            parts[1] = "256"
        return head + ":" + ":".join(parts)
    return exact_probe_spec(spec)


def healthy_logit_drift(spec: str, cfg, params) -> float:
    """Relative logit drift of the fault-free ``spec`` estimator from its
    exact-mode twin on the served model: ``rmse(dscim - exact) /
    rms(exact)`` over the logits of one forward of 4 x 16 prompt tokens
    drawn from seed 0.

    This is the healthy scale the watchdog's probes are compared with.
    It is measured on the model being served, at its widths and with its
    real row counts, because it does not follow from the macro's per-row
    error moments: from Qwen3-0.6B's reduced widths to its published ones
    the drift of dscim1:256 doubles while dscim2:64's grows by a fifth,
    where the moments would predict one ratio for both.
    ``cfg.dscim_fault`` is ignored, so the measurement is of the healthy
    macro model.  ``params`` may be float or prepared (single device)."""
    from repro.models import get_model
    forward = get_model(cfg).forward
    healthy = dataclasses.replace(cfg, dscim=spec, dscim_fault="")
    exact = dataclasses.replace(healthy, dscim=exact_probe_spec(spec))
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab, (4, 16)), jnp.int32)
    s, e = (np.asarray(jax.jit(
        lambda p, t, c=c: forward(p, c, {"tokens": t})[0])(params, tokens),
        np.float64) for c in (healthy, exact))
    return float(np.sqrt(np.mean((s - e) ** 2) / np.mean(e * e)))


def watchdog_for_spec(spec: str, cfg, params, *, margin: float = 3.0,
                      probe_every: int = 8) -> AccuracyWatchdog:
    """AccuracyWatchdog for serving ``spec`` on this model: the drift
    threshold is ``margin`` x ``healthy_logit_drift`` of the served model.
    Margin 3 is pinned empirically on Qwen3-0.6B (tests/test_serving_ft.py
    at reduced widths, tests/test_watchdog_width.py at the published
    widths): healthy probes drift 0.4x to 1.5x the calibrated drift, and
    a stuck-at macro fault on every third output column 4.3x (dscim2:64,
    full width) to 68x (dscim1:256, reduced widths)."""
    return AccuracyWatchdog(
        margin * healthy_logit_drift(spec, cfg, params),
        probe_every=probe_every)


# --------------------------------------------------------------------------
# the scheduler
# --------------------------------------------------------------------------

def _req_array(x, R, dtype, name):
    if x is None:
        return None
    arr = np.asarray(x, dtype)
    if arr.shape != (R,):
        raise ValueError(f"{name} must be shape ({R},), got {arr.shape}")
    return arr


def serve_continuous_ft(cfg, params, prompts: np.ndarray, n_tokens: int, *,
                        slots: int = 4, seg_len: int = 4, max_new=None,
                        eos_id: int | None = None, sample: str = "greedy",
                        kv: str = "float", page_size: int = 8,
                        n_pages: int | None = None, par=None,
                        rng_seed: int = 0, paged_attn: str = "auto",
                        deadline_steps=None, deadline_s=None, priority=None,
                        monitor: AccuracyWatchdog | None = None,
                        injector=None, snapshot_every: int = 0,
                        max_replays: int = 3, watchdog=None,
                        spec: str | None = None,
                        integrity: str = "off", prefix_cache=False,
                        log=print):
    """Fault-tolerant continuous batching over already-placed ``params``
    (launch/serve.py ``serve_continuous`` is the user-facing wrapper —
    argument semantics and the failure-mode contract are documented
    there).  Returns (outputs, stats).

    ``integrity`` ('off'|'verify'|'scrub:<n>', runtime/integrity.py):
    deterministic SDC detection + targeted repair at segment boundaries.
    Every n-th boundary the engine re-digests the live int8 page pool
    against the cache's ``page_sum`` plane and the prepared weight planes
    against their golden digests.  A corrupted weight plane is restored
    bit-exactly from the golden copy (plus a snapshot replay iff poisoned
    segments already ran); a corrupted KV page triggers *slot-scoped*
    repair — the owning slot alone is rewound to the last verified
    snapshot (``insert_slot_pages``) or re-served from its prompt, every
    other slot untouched.  'off' is bit-for-bit today's behavior (the
    digest plane is never created).

    ``prefix_cache`` (ISSUE 10, int8 KV only): ``True``/'on' admits
    every request through page-aligned chunked prefill (one compiled
    ``make_extend_fn`` program at ``chunk_len == page_size``) and shares
    physical pages across page-aligned prompt prefixes via the
    refcounted ``PrefixCache`` — a hit maps its leading page-table
    entries at the donor's pages and prefills only from the first
    divergent page.  Because hit and miss admissions run the *same*
    chunk programs on the same inputs (and shared pages hold exactly
    the bytes those programs would have produced), prefix-hit serving
    is bitwise-identical to cold serving.  'cold' runs the identical
    chunked admission path with lookup/registration disabled — the
    bitwise reference leg (``prefix_drill``).  ``False`` is today's
    one-shot bucketed admission, untouched."""
    prompts = np.asarray(prompts)
    R, S = prompts.shape
    budgets = np.full((R,), n_tokens, np.int32) if max_new is None \
        else np.asarray(max_new, np.int32)
    assert budgets.shape == (R,) and (budgets >= 1).all()
    dl_steps = _req_array(deadline_steps, R, np.int64, "deadline_steps")
    dl_secs = _req_array(deadline_s, R, np.float64, "deadline_s")
    prio = _req_array(priority, R, np.int64, "priority")
    if prio is not None and kv != "int8":
        raise ValueError("priority eviction preempts physical pages; it "
                         "needs the paged cache (kv='int8')")
    if monitor is not None and monitor.rel_threshold is not None \
            and cfg.dscim in ("off", "float"):
        raise ValueError("drift probes compare against the serving spec's "
                         "exact-mode twin; float serving has no estimator "
                         "to probe (pass rel_threshold=None for NaN-only "
                         "monitoring)")
    eos = -1 if eos_id is None else eos_id
    integrity_period = parse_integrity(integrity)
    if integrity_period > 0 and kv != "int8":
        raise ValueError("integrity checksums cover the int8 paged cache; "
                         "pass kv='int8' (the float dense cache is the "
                         "watchdog's statistical territory)")
    if prefix_cache is True:
        prefix_cache = "on"
    if prefix_cache not in (False, None, "", "off", "on", "cold"):
        raise ValueError(f"prefix_cache must be one of False/'on'/'cold', "
                         f"got {prefix_cache!r}")
    use_prefix = prefix_cache in ("on", "cold")
    if use_prefix and kv != "int8":
        raise ValueError("prefix caching shares int8 physical pages; "
                         "pass kv='int8'")
    # +headroom past prompt + budget: a speculative window may write k
    # draft positions past the committed pos before rollback, and a
    # chunked prefill (prefix mode) may write up to page_size - 1 pad
    # positions past the prompt — slot capacity and page grants cover
    # whichever the serving mode can incur.
    k_spec = _parse_spec(spec)[1] if _parse_spec(spec) else 0
    headroom = max(k_spec, page_size - 1) if use_prefix else k_spec
    capacity = S + int(budgets.max()) + headroom
    mp = n_pages_for(capacity, page_size)
    state0 = init_serve_state(cfg, slots, capacity, kv=kv,
                              page_size=page_size, n_pages=n_pages,
                              seed=rng_seed, integrity=integrity_period > 0)
    alloc0 = PageAllocator(state0["cache"]["k_pages"].shape[1]) \
        if kv == "int8" else None
    pfx0 = PrefixCache(alloc0, page_size) if use_prefix else None
    pfx_box = {"pfx": pfx0}
    engine = None
    if integrity_period > 0:
        from repro.core.qweights import golden_weight_copy
        engine = IntegrityEngine(golden_weight_copy(params),
                                 period=integrity_period)
    # weight repairs must outlive failover restarts, so the served params
    # live in a mutable holder rather than the closure binding
    pholder = {"params": params}
    host0 = {
        "slot_req": [-1] * slots, "slot_pages": [None] * slots,
        "slot_seq": [0] * slots,
        "out": [[] for _ in range(R)], "status": [None] * R,
        "admit_t": [None] * R,
        "next_req": 0, "seq": 0,
        "readmit": [], "evicted": {}, "quarantine": [], "corrupted": [],
        "evicted_ever": [], "reserve": [],
        "counters": {"evictions": 0, "readmissions": 0,
                     "deadline_cancelled": 0},
        "segments": 0, "global_step": 0,
        "live_steps": 0, "total_steps": 0,
        "prefill_computed": 0, "prefill_total": 0, "admit_lat": [],
    }
    probe = None
    if monitor is not None and monitor.rel_threshold is not None:
        cfg_probe = dataclasses.replace(
            cfg, dscim=exact_probe_spec(cfg.dscim), dscim_fault="")
        probe = make_probe_fn(cfg_probe, par)
    no_pages = jnp.zeros((mp,), jnp.int32)
    holder = None
    t0 = time.perf_counter()

    def _expired(host, r, now):
        if host["status"][r] is not None:
            return False
        if dl_steps is not None and dl_steps[r] >= 0 \
                and host["global_step"] >= int(dl_steps[r]):
            return True
        # wall budgets anchor at the request's admission, not serve start:
        # a late admission gets its full budget, and a still-queued
        # request (admit_t None) never wall-expires
        if dl_secs is not None and dl_secs[r] > 0 \
                and host["admit_t"][r] is not None \
                and now - host["admit_t"][r] >= float(dl_secs[r]):
            return True
        return False

    def _snap(state, host, alloc, pfx=None):
        return {"state": jax.device_get(state),
                "host": copy.deepcopy(host),
                "alloc": alloc.snapshot() if alloc is not None else None,
                "prefix": pfx.snapshot() if pfx is not None else None}

    def _loop(snap):
        if snap is None:
            state, host, alloc, pfx = state0, host0, alloc0, pfx0
        else:
            state = jax.device_put(snap["state"])
            host = copy.deepcopy(snap["host"])
            alloc = None if snap["alloc"] is None \
                else PageAllocator.from_snapshot(snap["alloc"])
            pfx = None if snap.get("prefix") is None \
                else PrefixCache.from_snapshot(snap["prefix"], alloc)
        pfx_box["pfx"] = pfx
        if watchdog is not None:
            watchdog.reset()
        # segments run since the last weight-digest sweep: a corrupted
        # plane found with this at 0 was caught before any decode used it
        # (pure repair); otherwise poisoned tokens exist and the repair
        # must be followed by a replay from the last verified snapshot
        segs_since_wcheck = 0

        def free_slot(b):
            if alloc is not None and host["slot_pages"][b] is not None:
                alloc.free(host["slot_pages"][b])
                host["slot_pages"][b] = None
            host["slot_req"][b] = -1

        def evict(b):
            nonlocal state
            r = host["slot_req"][b]
            blob = extract_slot_pages(state["cache"], b,
                                      host["slot_pages"][b])
            blob["tok"] = int(np.asarray(state["tok"])[b])
            blob["n_out"] = int(np.asarray(state["n_out"])[b])
            blob["seq"] = host["slot_seq"][b]
            host["evicted"][r] = blob
            host["readmit"].append(r)
            if r not in host["evicted_ever"]:
                host["evicted_ever"].append(r)
            free_slot(b)
            state = dict(state, done=state["done"].at[b].set(True))
            host["counters"]["evictions"] += 1

        def grant(need, want_prio):
            """Page grant for an admission, evicting strictly-lower-
            priority live slots (lowest priority, youngest on ties) if
            the pool is exhausted and priorities are in force."""
            ids = alloc.alloc(need)
            while ids is None and want_prio is not None:
                cands = [(int(prio[host["slot_req"][b]]),
                          -host["slot_seq"][b], b)
                         for b in range(slots) if host["slot_req"][b] >= 0
                         and int(prio[host["slot_req"][b]]) < want_prio]
                if not cands:
                    return None
                evict(min(cands)[2])
                ids = alloc.alloc(need)
            return ids

        def repair_pages(coords):
            """Slot-scoped KV repair: rewind each slot owning a corrupted
            physical page to the last *verified* snapshot (its pages
            digested clean there) via the eviction blob machinery, or —
            if the request wasn't live at that snapshot — re-serve it
            from its prompt (``host['reserve']``).  Every other slot's
            state is untouched, so under greedy decoding unaffected
            requests stay bitwise identical to a fault-free run."""
            nonlocal state
            vsnap = holder["verified"]
            vstate, vhost = vsnap["state"], vsnap["host"]
            owner = {}
            for b in range(slots):
                for p in (host["slot_pages"][b] or ()):
                    owner[int(p)] = b
            for b in sorted({owner[p] for _l, p in coords if p in owner}):
                r = host["slot_req"][b]
                b0 = vhost["slot_req"].index(r) \
                    if r in vhost["slot_req"] else -1
                if b0 >= 0:
                    blob = extract_slot_pages(vstate["cache"], b0,
                                              vhost["slot_pages"][b0])
                    cache = insert_slot_pages(state["cache"], b,
                                              host["slot_pages"][b], blob)
                    state = dict(
                        state, cache=cache,
                        tok=state["tok"].at[b].set(
                            int(vstate["tok"][b0])),
                        done=state["done"].at[b].set(
                            bool(vstate["done"][b0])),
                        n_out=state["n_out"].at[b].set(
                            int(vstate["n_out"][b0])),
                        max_new=state["max_new"].at[b].set(
                            int(vstate["max_new"][b0])))
                    host["out"][r] = list(vhost["out"][r])
                    log(f"[integrity] slot {b} (request {r}) rewound to "
                        f"verified snapshot (pos {blob['pos']})")
                else:
                    # admitted after the verified snapshot: restart from
                    # the prompt (greedy determinism -> identical tokens)
                    free_slot(b)
                    state = dict(state, done=state["done"].at[b].set(True))
                    host["out"][r] = []
                    host["reserve"].append(r)
                    log(f"[integrity] request {r} re-served from prompt "
                        "(corrupted page, no verified snapshot coverage)")
                engine.note_page_repair()

        def try_readmit(b):
            nonlocal state
            for r in list(host["readmit"]):
                blob = host["evicted"][r]
                need = blob["page_count"]
                ids = grant(need,
                            int(prio[r]) if prio is not None else None)
                if ids is None:
                    continue
                host["readmit"].remove(r)
                del host["evicted"][r]
                host["slot_pages"][b] = ids
                host["slot_req"][b] = r
                host["slot_seq"][b] = blob["seq"]   # keeps its seniority
                cache = insert_slot_pages(state["cache"], b, ids, blob)
                state = dict(
                    state, cache=cache,
                    tok=state["tok"].at[b].set(blob["tok"]),
                    done=state["done"].at[b].set(False),
                    n_out=state["n_out"].at[b].set(blob["n_out"]),
                    max_new=state["max_new"].at[b].set(int(budgets[r])))
                host["counters"]["readmissions"] += 1
                return True
            return False

        while True:
            seg = host["segments"]
            if holder is not None and snapshot_every > 0 \
                    and seg % snapshot_every == 0:
                holder["snap"] = _snap(state, host, alloc, pfx)
            if injector is not None:
                injector.maybe_fail(seg)
            fault_now = injector.serving_fault(seg) \
                if injector is not None else ""
            cfg_now = cfg if not fault_now else \
                dataclasses.replace(cfg, dscim_fault=fault_now)
            admit = make_admit_fn(cfg_now, par, eos_id=eos_id, sample=sample)
            extend = make_extend_fn(cfg_now, par, page_size, eos_id=eos_id,
                                    sample=sample, paged_attn=paged_attn) \
                if pfx is not None else None
            segment = make_segment_fn(cfg_now, par, seg_len, eos_id=eos_id,
                                      sample=sample, paged_attn=paged_attn,
                                      spec=spec)
            now = time.perf_counter()
            done_h = np.asarray(state["done"])
            for b in range(slots):                 # harvest finished slots
                r = host["slot_req"][b]
                if r >= 0 and done_h[b]:
                    free_slot(b)
                    host["status"][r] = STATUS_OK
            if dl_steps is not None or dl_secs is not None:
                for r in range(R):                 # deadline sweep
                    if not _expired(host, r, now):
                        continue
                    host["status"][r] = STATUS_DEADLINE
                    host["counters"]["deadline_cancelled"] += 1
                    if r in host["evicted"]:
                        del host["evicted"][r]
                        host["readmit"].remove(r)
                    if r in host["reserve"]:
                        host["reserve"].remove(r)
                    for b in range(slots):
                        if host["slot_req"][b] == r:
                            free_slot(b)
                            state = dict(
                                state,
                                done=state["done"].at[b].set(True))
            for b in range(slots):                 # admissions
                if host["slot_req"][b] >= 0:
                    continue
                if host["readmit"] and try_readmit(b):
                    continue
                # integrity re-serves (corrupted page, no snapshot
                # coverage) go first — they were admitted once already
                reserve = bool(host["reserve"])
                if reserve:
                    rq = host["reserve"][0]
                else:
                    while host["next_req"] < R \
                            and host["status"][host["next_req"]] is not None:
                        host["next_req"] += 1      # skip cancelled waiters
                    if host["next_req"] >= R:
                        continue
                    rq = host["next_req"]
                pages = no_pages
                ids = None
                d_shared = 0
                if alloc is not None:
                    need = admission_pages(S, int(budgets[rq]), page_size,
                                           headroom)
                    shared = []
                    if pfx is not None and prefix_cache == "on":
                        _ntok, shared = pfx.acquire(prompts[rq],
                                                    (S - 1) // page_size)
                    d_shared = len(shared)
                    fresh = grant(need - d_shared,
                                  int(prio[rq]) if prio is not None else None)
                    if fresh is None:              # pool exhausted: wait
                        if shared:
                            alloc.free(shared)     # release the refs we took
                        continue
                    ids = shared + fresh
                    host["slot_pages"][b] = ids
                    if pfx is None:
                        # pad to mp with a self-owned id (never read
                        # unmasked, never flushed — pos stays under the
                        # budget's pages)
                        pages = jnp.asarray(ids + [ids[-1]] * (mp - need),
                                            jnp.int32)
                if reserve:
                    host["reserve"].pop(0)
                else:
                    host["next_req"] = rq + 1
                if host["admit_t"][rq] is None:    # re-serves keep their
                    host["admit_t"][rq] = time.perf_counter()  # anchor
                if pfx is not None:
                    # prefix-mode admission: page-aligned chunked prefill
                    # through ONE compiled extend program for hits and
                    # misses alike — a hit feeds from the first divergent
                    # page, a miss from page 0.  Same programs + same
                    # inputs + shared pages holding exactly the bytes
                    # those programs produced on the donor => warm
                    # serving is bitwise-identical to cold serving.
                    t_adm = time.perf_counter()
                    fed = d_shared * page_size
                    cache = state["cache"]
                    # COW enforcement point: everything at or past the
                    # write frontier must be private before any scatter
                    # (a checked no-op here — sharing stops strictly
                    # below the frontier by construction)
                    cache, ids, _nf = cow_fork(cache, alloc, ids,
                                               start_idx=d_shared)
                    host["slot_pages"][b] = ids
                    row = jnp.asarray(ids + [ids[-1]] * (mp - len(ids)),
                                      jnp.int32)
                    cache = dict(
                        cache,
                        page_table=cache["page_table"].at[b].set(row),
                        pos=cache["pos"].at[b].set(fed))
                    state = dict(state, cache=cache,
                                 done=state["done"].at[b].set(True))
                    tok0 = None
                    while fed < S:
                        part = prompts[rq, fed:fed + page_size]
                        n_real = len(part)
                        if n_real < page_size:
                            part = np.pad(part, (0, page_size - n_real))
                        state, tok0 = extend(
                            pholder["params"], state,
                            jnp.asarray(part[None]), jnp.int32(b),
                            jnp.int32(n_real),
                            jnp.bool_(fed + n_real >= S),
                            jnp.int32(budgets[rq]))
                        fed += n_real
                    tok0 = int(tok0)               # sync: latency is real
                    if prefix_cache == "on":
                        pfx.register(prompts[rq], ids[:S // page_size])
                    host["prefill_computed"] += S - d_shared * page_size
                    host["prefill_total"] += S
                    host["admit_lat"].append(
                        (d_shared > 0, time.perf_counter() - t_adm))
                else:
                    state, tok0 = admit(pholder["params"], state,
                                        jnp.asarray(prompts[rq:rq + 1]),
                                        jnp.int32(b), pages,
                                        jnp.int32(budgets[rq]))
                host["out"][rq].append(int(tok0))
                host["slot_req"][b] = rq
                host["seq"] += 1
                host["slot_seq"][b] = host["seq"]
            if all(rr < 0 for rr in host["slot_req"]):
                waiting = any(host["status"][r] is None
                              for r in range(host["next_req"], R))
                if not waiting and not host["readmit"] \
                        and not host["reserve"]:
                    return state, host, alloc
                nr = host["next_req"]
                what = (f"request {nr} "
                        f"({admission_pages(S, int(budgets[nr]), page_size, headroom)} "
                        "pages needed") if nr < R else \
                    (f"evicted request {host['readmit'][0]} "
                     f"({host['evicted'][host['readmit'][0]]['page_count']}"
                     " pages needed")
                raise RuntimeError(f"page pool too small for {what}, "
                                   f"{alloc.free_pages} free)")
            if np.asarray(state["done"]).all():
                continue  # all finished at admission: harvest, don't step
            live0 = np.asarray([rr >= 0 for rr in host["slot_req"]]) \
                & ~np.asarray(state["done"])
            lg_exact = None
            if probe is not None and monitor.should_probe(seg) \
                    and live0.any():
                # fetch before the donating segment call consumes state
                lg_exact = np.asarray(probe(pholder["params"], state))
            if injector is not None and alloc is not None:
                cache2, hit = injector.corrupt_cache(seg, state["cache"],
                                                     host["slot_pages"])
                if hit:
                    state = dict(state, cache=cache2)
                    for b in hit:
                        rr = host["slot_req"][b]
                        if rr >= 0 and rr not in host["corrupted"]:
                            host["corrupted"].append(rr)
            if injector is not None \
                    and getattr(injector, "weight_flips", None):
                p2, whit = injector.corrupt_weights(seg, pholder["params"])
                if whit:
                    pholder["params"] = p2
            if engine is not None and engine.due(seg):
                # injected faults land *before* this check at the same
                # boundary, so a flip due the segment a check runs is
                # caught before any decode consumes it
                reprobe = False
                bad_w = engine.check_weights(pholder["params"])
                if bad_w:
                    pholder["params"] = engine.repair_weights(
                        pholder["params"], bad_w)
                    log(f"[integrity] weight plane(s) {bad_w} restored "
                        "from golden copy")
                    if segs_since_wcheck > 0:
                        # decodes ran against the corrupted plane: every
                        # slot's tokens since the last verified snapshot
                        # are suspect — discard and replay (bit-clean,
                        # the repaired planes equal the originals)
                        engine.note_replay()
                        holder["snap"] = holder["verified"]
                        raise IntegrityReplay(
                            f"weight plane(s) {bad_w} repaired after "
                            f"{segs_since_wcheck} unverified segment(s)")
                    reprobe = True
                segs_since_wcheck = 0
                if alloc is not None:
                    # digests are under warranty only for granted, fully
                    # flushed pages — build that mask host-side
                    pos_h = np.asarray(state["cache"]["pos"])
                    live_pages = np.zeros((alloc.n_pages,), bool)
                    for b in range(slots):
                        ids = host["slot_pages"][b]
                        if ids is not None:
                            for p in ids[:int(pos_h[b]) // page_size]:
                                live_pages[int(p)] = True
                    coords = engine.check_pages(state["cache"], live_pages)
                    if coords:
                        log(f"[integrity] corrupted page(s) at "
                            f"(layer, page) {coords}")
                        repair_pages(coords)
                        reprobe = True
                # everything digests clean now: this becomes the repair
                # restore point (regular snapshots may hold state later
                # poisoned by a not-yet-detected flip; this one cannot)
                holder["verified"] = _snap(state, host, alloc, pfx)
                if reprobe and lg_exact is not None:
                    # the pre-repair probe fetch no longer matches the
                    # repaired state — re-fetch so a surgical repair can
                    # never read as watchdog drift
                    lg_exact = np.asarray(probe(pholder["params"], state))
            ctx = watchdog.step() if watchdog is not None \
                else contextlib.nullcontext()
            with ctx:
                state, toks, lives, aux = segment(pholder["params"], state)
                toks_h = np.asarray(toks)
                lives_h = np.asarray(lives)
            # under spec the segment emits seg_len * (k + 1) chronological
            # rows per slot (accepted drafts + bonus; rejected rows have
            # lives False) — the harvest is row-count agnostic
            for s in range(toks_h.shape[0]):       # harvest tokens
                for b in range(slots):
                    if lives_h[s, b] and host["slot_req"][b] >= 0:
                        host["out"][host["slot_req"][b]].append(
                            int(toks_h[s, b]))
            if monitor is not None:
                bad = np.asarray(aux["bad"]).any(axis=0)
                trip = bad.copy()
                rels = np.zeros((slots,))
                reasons = np.where(bad, "nonfinite", "drift")
                if lg_exact is not None:
                    t2, rel = monitor.check(np.asarray(aux["logits0"]),
                                            lg_exact, live0)
                    rels = rel
                    trip |= t2
                for b in np.nonzero(trip)[0]:
                    rr = host["slot_req"][int(b)]
                    if rr < 0:
                        continue
                    free_slot(int(b))
                    state = dict(state,
                                 done=state["done"].at[int(b)].set(True))
                    host["out"][rr] = []           # discard poisoned tokens
                    host["quarantine"].append({
                        "request": rr, "slot": int(b), "segment": seg,
                        "reason": str(reasons[b]),
                        "rel": float(rels[b])
                        if np.isfinite(rels[b]) else float("inf")})
            host["live_steps"] += int(lives_h.sum())
            host["total_steps"] += toks_h.shape[0] * slots
            host["segments"] += 1
            segs_since_wcheck += 1
            # drafted-but-rejected verifier positions count toward the
            # deadline ledger: a spec segment attempts seg_len * (k + 1)
            # positions per slot regardless of the acceptance outcome
            host["global_step"] += seg_len * (k_spec + 1)

    use_ft = injector is not None or snapshot_every > 0 \
        or watchdog is not None or engine is not None
    if use_ft:
        snap0 = _snap(state0, host0, alloc0, pfx0)
        # the initial state is verified-clean by construction
        holder = {"snap": snap0, "verified": snap0}
        (state, host, alloc), replays = run_with_failover(
            _loop, restore_fn=lambda: holder["snap"],
            max_restarts=max_replays,
            recoverable=(SimulatedHardwareFailure, StepHang,
                         IntegrityReplay), log=log)
    else:
        state, host, alloc = _loop(None)
        replays = 0

    esc_records: list = []
    if any(host["status"][q["request"]] is None
           for q in host["quarantine"]):
        _escalate(cfg, pholder["params"], prompts, n_tokens, host, budgets,
                  eos_id=eos_id, sample=sample, kv=kv, page_size=page_size,
                  par=par, rng_seed=rng_seed, monitor=monitor,
                  records=esc_records, log=log)
    for r in range(R):
        if host["status"][r] is None:
            host["status"][r] = STATUS_OK

    dt = time.perf_counter() - t0
    useful = sum(len(o) for o in host["out"])
    stats = {
        "wall_s": dt,
        "tok_s": useful / dt,
        "occupancy": host["live_steps"] / max(host["total_steps"], 1),
        "live_slot_steps": host["live_steps"],
        "slot_steps": host["total_steps"],
        "segments": host["segments"],
        "requests": R,
        "useful_tokens": useful,
        "status": list(host["status"]),
        "replays": replays,
        "evictions": host["counters"]["evictions"],
        "readmissions": host["counters"]["readmissions"],
        "evicted_requests": list(host["evicted_ever"]),
        "deadline_cancelled": host["counters"]["deadline_cancelled"],
        "quarantined": sorted({q["request"] for q in host["quarantine"]}),
        "escalations": esc_records,
        "corrupted_requests": sorted(host["corrupted"]),
        "probes": monitor.n_probes if monitor is not None else 0,
        "probe_trips": monitor.n_trips if monitor is not None else 0,
        "stragglers": watchdog.n_stragglers if watchdog is not None else 0,
        "pages": alloc.stats() if alloc is not None else None,
        "integrity": (dict(engine.stats(), detections=engine.detections)
                      if engine is not None else None),
        "prefix": (dict(
            pfx_box["pfx"].stats(),
            prefill_positions_computed=host["prefill_computed"],
            prefill_positions_total=host["prefill_total"],
            admit_lat_hit=[t for hit, t in host["admit_lat"] if hit],
            admit_lat_miss=[t for hit, t in host["admit_lat"] if not hit])
            if pfx_box["pfx"] is not None else None),
    }
    return [np.asarray(o, np.int32) for o in host["out"]], stats


# --------------------------------------------------------------------------
# post-loop degradation-ladder escalation
# --------------------------------------------------------------------------

def _escalate(cfg, params, prompts, n_tokens, host, budgets, *, eos_id,
              sample, kv, page_size, par, rng_seed, monitor, records, log):
    """Re-serve quarantined requests from their prompts down the ladder.

    The serving batch is one jitted program — a single slot cannot run a
    different estimator mid-batch — so escalation restarts the request
    through ``serve_batch`` on a clean config (``dscim_fault=''``) at the
    next ladder level, grouped by level to share compilations.  Each
    intermediate level is verified against its exact-mode twin (prefill
    logit relative RMSE under the monitor threshold); rows still drifting
    escalate further.  The bottom (exact / float) level is accepted
    unconditionally — it *is* the reference."""
    from repro.launch.serve import serve_batch   # lazy: serve.py imports us
    thresh = monitor.rel_threshold \
        if monitor is not None and monitor.rel_threshold is not None \
        else float("inf")
    eos = -1 if eos_id is None else eos_id
    level, reason = {}, {}
    for q in host["quarantine"]:
        r = q["request"]
        if host["status"][r] is not None:      # e.g. deadline'd meanwhile
            continue
        level.setdefault(r, cfg.dscim)
        reason.setdefault(r, q["reason"])
    pending = sorted(level)
    while pending:
        groups: dict = {}
        for r in pending:
            nxt = next_ladder_spec(level[r]) or level[r]   # off: restart
            groups.setdefault(nxt, []).append(r)
        pending = []
        for spec, rows in sorted(groups.items()):
            cfg_lvl = dataclasses.replace(cfg, dscim=spec, dscim_fault="")
            kw = dict(par=par, prepare=False, eos_id=eos,
                      max_new=[int(budgets[r]) for r in rows],
                      sample=sample, kv=kv, page_size=page_size,
                      rng_seed=rng_seed)
            toks, lgs = serve_batch(cfg_lvl, params, prompts[rows],
                                    n_tokens, **kw)
            terminal = next_ladder_spec(spec) is None
            ok = np.ones(len(rows), bool)
            rel = np.zeros(len(rows))
            if not terminal and np.isfinite(thresh):
                cfg_ex = dataclasses.replace(
                    cfg, dscim=exact_probe_spec(spec), dscim_fault="")
                _, lgs_ex = serve_batch(cfg_ex, params, prompts[rows],
                                        n_tokens, **kw)
                s = np.asarray(lgs[0], np.float64).reshape(len(rows), -1)
                e = np.asarray(lgs_ex[0], np.float64).reshape(len(rows), -1)
                rms = np.sqrt(np.mean(e * e, axis=-1))
                rel = np.sqrt(np.mean((s - e) ** 2, axis=-1)) \
                    / np.maximum(rms, 1e-9)
                ok = np.isfinite(rel) & (rel <= thresh)
            for i, r in enumerate(rows):
                records.append({"request": r, "frm": level[r], "to": spec,
                                "reason": reason[r],
                                "accepted": bool(ok[i]),
                                "rel": float(rel[i])})
                log(f"[ladder] request {r}: {level[r]} -> {spec} "
                    f"({reason[r]}; rel {rel[i]:.2e}; "
                    f"{'accepted' if ok[i] else 'still drifting'})")
                if ok[i]:
                    row = np.asarray(toks[i])
                    n_use = int(budgets[r])
                    hits = np.nonzero(row[:n_use] == eos)[0]
                    if len(hits):
                        n_use = int(hits[0]) + 1
                    host["out"][r] = row[:n_use].tolist()
                    host["status"][r] = STATUS_OK
                else:
                    level[r] = spec
                    reason[r] = "drift"
                    pending.append(r)


# --------------------------------------------------------------------------
# chaos drill: the self-verifying end-to-end robustness exercise
# --------------------------------------------------------------------------

def chaos_drill(arch: str = "qwen3-0.6b", *, seed: int = 0,
                log=print) -> dict:
    """One scripted chaos scenario over the full fault-tolerant stack,
    asserting the ISSUE 6 acceptance contract end to end:

    under one injected segment-level device loss, page-pool bit flips
    (an f32 dequant-scale upset and an int8 page upset — both *silent*
    corruption on this RMSNorm'd model, tracked via
    ``corrupted_requests``; the NaN detection path is pinned separately
    in tests/test_serving_ft.py where Inf injection is deterministic), a
    persistent stuck-at DS-CIM macro fault, and a deadline expiry,
    ``serve_continuous`` completes every admitted request with a definite
    status; requests untouched by any fault finish bitwise-identical to
    the fault-free run; the accuracy watchdog trips on the injected
    macro fault and visibly escalates dscim2 -> dscim1; and exactly one
    failover replay absorbs the device loss.

    Deterministic by construction: greedy decoding (the shared PRNG key
    is never consumed), step-based deadlines, ``snapshot_every=1`` (the
    restore point is never older than a fired transient flip), eos=-1.
    Returns a report dict (the chaos bench rows and the CI smoke both
    consume it)."""
    from repro.configs import get_arch
    from repro.launch.serve import serve_continuous
    from repro.models import get_model
    from repro.runtime.failover import FailureInjector

    spec = "kernel:dscim2:64"
    cfg = dataclasses.replace(get_arch(arch).reduced(), dscim=spec)
    model = get_model(cfg)
    params = model.init_params(cfg, jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    R, S, n = 6, 8, 8
    prompts = rng.integers(0, cfg.vocab, (R, S), dtype=np.int32)
    budgets = np.asarray([8, 6, 8, 5, 8, 6], np.int32)
    # request 3 gets a 4-decode-step budget: admitted in the first wave,
    # cancelled at the boundary after segment 2 with partial tokens
    deadlines = np.asarray([-1, -1, -1, 4, -1, -1], np.int64)
    knobs = dict(slots=3, seg_len=2, max_new=budgets, eos_id=-1,
                 kv="int8", page_size=4)

    outs_ref, _ = serve_continuous(cfg, params, prompts, n, **knobs)

    monitor = watchdog_for_spec(spec, cfg, params, probe_every=1)
    injector = FailureInjector(
        fail_at=(3,),
        page_flips={
            # f32 dequant-scale exponent upset on slot 0 and an int8 page
            # upset on slot 1: both corrupt outputs silently (RMSNorm
            # squashes the magnitude excursion back into range) — the
            # contract is that they land in corrupted_requests, never in
            # a clean request's tokens
            1: ((0, "v_scale", (0, 0, 0), 0x7f000000),),
            2: ((1, "k_pages", (0, 0, 0, 0, 0), 0x41),),
        },
        macro_fault_at=6, macro_fault="stuck:3:40.0")
    outs, stats = serve_continuous(
        cfg, params, prompts, n, **knobs, deadline_steps=deadlines,
        monitor=monitor, injector=injector, snapshot_every=1,
        max_replays=2, log=log)

    # -- the acceptance contract ------------------------------------------
    assert all(s in (STATUS_OK, STATUS_DEADLINE) for s in stats["status"]), \
        f"indefinite request status: {stats['status']}"
    assert stats["replays"] == 1, \
        f"expected the device loss to cost exactly 1 replay: {stats}"
    assert stats["status"][3] == STATUS_DEADLINE \
        and len(outs[3]) < int(budgets[3]), \
        f"deadline request not cancelled: {stats['status']}"
    escalated = {e["request"] for e in stats["escalations"]}
    assert escalated, f"no ladder escalations recorded: {stats}"
    hops = {(e["frm"], e["to"]) for e in stats["escalations"]}
    assert any("dscim2" in frm and "dscim1" in to for frm, to in hops), \
        f"dscim2 -> dscim1 escalation not visible: {sorted(hops)}"
    assert stats["probe_trips"] >= 1, "watchdog never tripped"
    affected = (set(stats["corrupted_requests"]) | escalated
                | set(stats["quarantined"])
                | {r for r in range(R)
                   if stats["status"][r] == STATUS_DEADLINE})
    clean = sorted(set(range(R)) - affected)
    assert clean, "chaos scenario left no unaffected request to compare"
    for r in clean:
        np.testing.assert_array_equal(
            outs[r], outs_ref[r],
            err_msg=f"unaffected request {r} diverged from fault-free run")
    report = {
        "seed": seed,
        "requests": R, "clean": clean, "affected": sorted(affected),
        "replays": stats["replays"], "probes": stats["probes"],
        "probe_trips": stats["probe_trips"],
        "quarantined": stats["quarantined"],
        "escalations": len(stats["escalations"]),
        "deadline_cancelled": stats["deadline_cancelled"],
        "corrupted_requests": stats["corrupted_requests"],
        "statuses": stats["status"],
        "rel_threshold": monitor.rel_threshold,
    }
    log(f"[chaos] drill ok: {report}")
    return report


def integrity_drill(arch: str = "qwen3-0.6b", *, seed: int = 0,
                    log=print) -> dict:
    """The ISSUE 9 acceptance exercise: under injected page-pool *and*
    prepared-weight bit flips with ``integrity='scrub:2'``, every flip is
    detected at its exact coordinate within one scrub period, repaired
    requests finish ``'ok'``, **every** request (affected ones included —
    stronger than the chaos drill's unaffected-only contract) ends
    bitwise-identical to the fault-free run, and no repairable flip
    escalates the watchdog ladder.

    Two legs, both greedy / step-deterministic / ``snapshot_every=1``:

    * **leg 1** (watchdog armed, ``probe_every=1``): a weight q-plane
      upset at segment 0 (caught at the boundary-0 sweep before any
      decode consumed it — pure golden-copy repair, no replay), an f32
      dequant-scale upset at segment 1 repaired by rewinding the owner
      slot to the verified snapshot, and an int8 page upset at segment 3
      hitting a request admitted *after* that snapshot — repaired by
      re-serving it from its prompt.  Asserts zero replays, zero
      quarantines/escalations (a surgical repair must never read as
      watchdog drift), and exact (path, plane) / page-layer attribution.
    * **leg 2** (no watchdog): a scale-plane upset at segment 3, an
      *unchecked* boundary — segment 3 decodes against the corrupted
      plane, so the boundary-4 sweep must repair **and** discard the
      poisoned tokens via an ``IntegrityReplay`` from the last verified
      snapshot.  Asserts exactly one replay and, again, every output
      bitwise-identical.
    """
    from repro.configs import get_arch
    from repro.core.qweights import weight_plane_index
    from repro.launch.serve import serve_continuous
    from repro.launch.steps import prepare_serving_params
    from repro.models import get_model
    from repro.runtime.failover import FailureInjector

    spec = "kernel:dscim2:64"
    cfg = dataclasses.replace(get_arch(arch).reduced(), dscim=spec)
    model = get_model(cfg)
    params = model.init_params(cfg, jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    R, S, n = 6, 8, 8
    prompts = rng.integers(0, cfg.vocab, (R, S), dtype=np.int32)
    budgets = np.asarray([8, 6, 8, 5, 8, 6], np.int32)
    knobs = dict(slots=3, seg_len=2, max_new=budgets, eos_id=-1,
                 kv="int8", page_size=4)
    # the exact plane the weight flips target, discovered from a
    # throwaway prepare (deterministic — the scheduler's internal prepare
    # builds the same tree, so the path strings match)
    widx = weight_plane_index(prepare_serving_params(cfg, params))
    assert widx, "integrity drill needs a prepared (DS-CIM) model"
    wpath = next((p for p, w in widx if "w_up" in p and w == "q"),
                 widx[0][0])
    lay = 1 if cfg.n_layers > 1 else 0

    outs_ref, _ = serve_continuous(cfg, params, prompts, n, **knobs)

    # -- leg 1: detect + repair without replay, watchdog armed ------------
    monitor = watchdog_for_spec(spec, cfg, params, probe_every=1)
    inj1 = FailureInjector(
        page_flips={
            1: ((0, "v_scale", (0, 0, 0), 0x7f000000),),
            3: ((1, "k_pages", (lay, 0, 0, 0, 0), 0x41),),
        },
        weight_flips={0: ((wpath, "q", 2026, 0x10),)})
    outs1, st1 = serve_continuous(
        cfg, params, prompts, n, **knobs, monitor=monitor, injector=inj1,
        snapshot_every=1, max_replays=2, integrity="scrub:2", log=log)
    ig1 = st1["integrity"]
    assert ig1 is not None and ig1["period"] == 2, f"no integrity stats: {st1}"
    assert all(s == STATUS_OK for s in st1["status"]), \
        f"repaired requests must finish ok: {st1['status']}"
    assert st1["replays"] == 0 and ig1["replays"] == 0, \
        f"leg 1 faults are repairable without replay: {st1}"
    assert not st1["quarantined"] and not st1["escalations"], \
        f"a repairable flip escalated the ladder: {st1}"
    assert ig1["page_mismatches"] == 2 and ig1["page_repairs"] == 2, \
        f"both page flips must be detected and repaired: {ig1}"
    assert ig1["weight_mismatches"] == 1 and ig1["weight_repairs"] == 1, \
        f"the weight flip must be detected and repaired: {ig1}"
    wdet = [d for d in ig1["detections"] if d["kind"] == "weight"]
    pdet = [d for d in ig1["detections"] if d["kind"] == "page"]
    assert len(wdet) == 1 and wdet[0]["coords"] == [(wpath, "q")], \
        f"weight detection not attributed to the exact plane: {wdet}"
    assert [d["coords"][0][0] for d in pdet] == [0, lay] \
        and all(len(d["coords"]) == 1 for d in pdet), \
        f"page detections not attributed to the exact layers: {pdet}"
    assert set(st1["corrupted_requests"]) == {0, 3}, \
        f"unexpected corruption footprint: {st1['corrupted_requests']}"
    for r in range(R):
        np.testing.assert_array_equal(
            outs1[r], outs_ref[r],
            err_msg=f"request {r} diverged from the fault-free run (leg 1)")

    # -- leg 2: poisoned segments -> repair + bounded replay --------------
    inj2 = FailureInjector(
        weight_flips={3: ((wpath, "scale", 7, 1 << 23),)})
    outs2, st2 = serve_continuous(
        cfg, params, prompts, n, **knobs, injector=inj2,
        snapshot_every=1, max_replays=2, integrity="scrub:2", log=log)
    ig2 = st2["integrity"]
    assert st2["replays"] == 1 and ig2["replays"] == 1, \
        f"poisoned segments must cost exactly one replay: {st2}"
    assert ig2["weight_mismatches"] == 1 and ig2["weight_repairs"] == 1, \
        f"leg 2 weight flip not repaired: {ig2}"
    assert all(s == STATUS_OK for s in st2["status"]), \
        f"replayed requests must finish ok: {st2['status']}"
    for r in range(R):
        np.testing.assert_array_equal(
            outs2[r], outs_ref[r],
            err_msg=f"request {r} diverged from the fault-free run (leg 2)")

    report = {
        "seed": seed, "requests": R, "weight_plane": wpath,
        "scrub_period": 2,
        "leg1": {"page_repairs": ig1["page_repairs"],
                 "weight_repairs": ig1["weight_repairs"],
                 "replays": st1["replays"], "checks": ig1["checks"],
                 "pages_verified": ig1["pages_verified"],
                 "scrub_time_s": ig1["scrub_time_s"]},
        "leg2": {"weight_repairs": ig2["weight_repairs"],
                 "replays": st2["replays"], "checks": ig2["checks"]},
        "statuses": st1["status"],
    }
    log(f"[integrity] drill ok: {report}")
    return report


def prefix_drill(arch: str = "qwen3-0.6b", *, seed: int = 0,
                 log=print) -> dict:
    """The ISSUE 10 acceptance exercise: staggered admissions sharing a
    page-aligned system prompt, served warm (``prefix_cache='on'``) vs
    cold (``prefix_cache='cold'`` — the identical chunked admission path
    with lookup/registration disabled), must agree **bitwise** per
    request while the warm leg visibly dedupes pages and skips prefill:

    * every warm output equals its cold output token for token;
    * the warm leg records prefix hits and deduped pages (requests
      admitted after the first register-then-match its shared pages —
      sharers overlap live, so refcounts > 1 are exercised, and the
      last sharers release while the index retains);
    * prefill positions actually computed drop by the shared fraction
      (the prefill-FLOPs-removed measurement the bench rows report);
    * after drain the pool holds zero live pages (retained ref-0 pages
      are not live) and the retained set is non-empty — the index kept
      the prefix resident for future admissions.

    Deterministic by construction: greedy decoding, eos=-1, one
    compiled extend program for every admission.  Returns a report dict
    (the prefix bench rows and the CI smoke both consume it)."""
    from repro.configs import get_arch
    from repro.launch.serve import serve_continuous

    spec = "kernel:dscim2:64"
    cfg = dataclasses.replace(get_arch(arch).reduced(), dscim=spec)
    from repro.models import get_model
    model = get_model(cfg)
    params = model.init_params(cfg, jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    R, S, n, ps = 6, 16, 6, 4
    prompts = rng.integers(0, cfg.vocab, (R, S), dtype=np.int32)
    # requests 0..4 share a 12-token (3-page) system prompt; request 5
    # is fully distinct (a guaranteed miss among hits)
    prompts[1:5, :12] = prompts[0, :12]
    budgets = np.asarray([6, 5, 6, 4, 6, 5], np.int32)
    knobs = dict(slots=2, seg_len=2, max_new=budgets, eos_id=-1,
                 kv="int8", page_size=ps)

    outs_cold, st_cold = serve_continuous(cfg, params, prompts, n, **knobs,
                                          prefix_cache="cold", log=log)
    outs_warm, st_warm = serve_continuous(cfg, params, prompts, n, **knobs,
                                          prefix_cache="on", log=log)

    # -- the acceptance contract ------------------------------------------
    for r in range(R):
        np.testing.assert_array_equal(
            outs_warm[r], outs_cold[r],
            err_msg=f"request {r}: prefix-hit serving diverged from cold")
    pw, pc = st_warm["prefix"], st_cold["prefix"]
    assert pc["hits"] == 0 and pc["pages_deduped"] == 0, \
        f"cold leg must not share: {pc}"
    assert pw["hits"] == 4, f"requests 1..4 must hit: {pw}"
    assert pw["pages_deduped"] == 4 * 3, \
        f"each hit shares 3 full pages: {pw}"
    assert pw["hit_tokens"] == 4 * 12, f"12 tokens per hit: {pw}"
    removed = 1.0 - pw["prefill_positions_computed"] \
        / max(pw["prefill_positions_total"], 1)
    assert removed > 0.4, \
        f"shared prefixes must remove >40% of prefill positions: {pw}"
    assert st_warm["pages"]["live_pages"] == 0 \
        and st_cold["pages"]["live_pages"] == 0, \
        "drained pools must hold zero live pages"
    assert st_warm["pages"]["retained_pages"] > 0, \
        f"the index must retain the shared prefix: {st_warm['pages']}"
    assert st_warm["pages"]["shares"] == pw["pages_deduped"], \
        f"every dedup is a share reference: {st_warm['pages']} vs {pw}"
    assert all(s == STATUS_OK for s in st_warm["status"]), \
        f"warm statuses: {st_warm['status']}"
    report = {
        "seed": seed, "requests": R,
        "hits": pw["hits"], "pages_deduped": pw["pages_deduped"],
        "hit_tokens": pw["hit_tokens"],
        "prefill_removed_frac": removed,
        "retained_pages": st_warm["pages"]["retained_pages"],
        "statuses": st_warm["status"],
    }
    log(f"[prefix] drill ok: {report}")
    return report
