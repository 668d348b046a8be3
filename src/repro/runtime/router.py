"""Async serving router: the request-granular frontend above the
continuous-batching scheduler (ISSUE 8 tentpole).

``serve_continuous`` serves a *closed* queue of fixed-length prompts; a
service faces the opposite shape — streaming requests of arbitrary prompt
length arriving at arbitrary times, each wanting its tokens back as they
are produced and a definite answer when they are not.  ``Router`` is that
layer: an asyncio in-process frontend (launch/server.py wraps it in a
thin HTTP shim) that owns the same jitted scheduler halves the
fault-tolerant loop uses (launch/steps.py ``make_admit_fn`` /
``make_segment_fn`` / ``make_extend_fn`` / ``make_probe_fn``) and drives
them request-by-request instead of queue-at-once.  Greedy deterministic
serving is schedule-independent per request (the PR 4 continuous-vs-
one-shot bitwise property), so a request admitted through the router
emits bitwise the tokens ``serve_continuous`` would have given it — the
load-test acceptance criterion.

Admission paths (the PR 4 "length bucketing" follow-on):

* **bucketed one-shot** — a prompt whose length is one of ``buckets``
  prefills in one jitted ``admit`` call; each bucket length compiles
  once (the jit shape cache), so a handful of cached admit fns replace
  the single fixed prompt shape.  Bitwise-comparable to
  ``serve_continuous`` at the same prompt length.
* **chunked** — any other length feeds through ONE compiled
  ``make_extend_fn`` program, ``chunk_len`` prompt tokens per engine
  round, final partial chunk padded and rolled back (the speculative
  write-then-rollback discipline).  Decode segments for live slots run
  between chunks, so a 10k-token admission never stalls streaming
  requests.  Sequential-decode equivalent (teacher-forced ``decode``
  parity), not bitwise-equal to the batched full-prompt prefill.
* **prefix-cached** (``prefix_cache=True``, ISSUE 10) — every
  admission routes chunked at ``chunk_len == page_size`` through one
  compiled extend program; a prompt sharing a page-aligned prefix with
  an earlier request maps its leading page-table rows to the donor's
  physical int8 pages (``PrefixCache.acquire``) and feeds only from
  the first divergent page.  Because hit and miss run the identical
  program over identical bytes, a prefix hit is bitwise-identical to
  the same prompt served cold by this router.  ``cow_fork`` guards the
  write frontier; refcounts ride the allocator snapshot so failover
  replay preserves sharing.

Robustness surface (the headline):

* **Backpressure** — ``submit`` raises a typed ``Refused`` instead of
  queueing unboundedly: ``too_large`` (the request could never fit the
  page pool/capacity — permanent, a 413), ``queue`` (admission queue at
  ``max_queue`` — transient, a 429 with a throughput-derived
  ``retry_after`` hint), ``draining`` (shutdown in progress — a 503).
  Page-pool exhaustion for admissible requests is *queueing*, not
  refusal; the queue bound is where overload sheds.
* **Deadlines** — ``deadline_s`` anchors at submission (an end-to-end
  SLO: queue time counts), ``deadline_steps`` at admission (a
  deterministic decode-step budget).  Expiry cancels at the next round
  boundary with status ``deadline`` and the partial tokens already
  streamed stay valid.
* **Cancellation** — ``handle.cancel()`` (client disconnect) frees the
  slot and recycles its pages mid-stream at the next round; status
  ``cancelled``.
* **Failover** — the engine snapshots serve state every
  ``snapshot_every`` rounds (device pytree + host bookkeeping + page
  allocator, the PR 6 machinery); a recoverable fault
  (``FailureInjector`` device loss, watchdog hang) restores and replays
  bit-identically.  Tokens already pushed to a stream are never
  re-pushed: per-request ``sent`` cursors live *outside* the snapshot,
  and the replay regrows the same token list underneath them.
* **Quarantine -> degraded** — the accuracy watchdog (``monitor``)
  quarantines a drifting/NaN slot exactly as in runtime/serving.py, but
  the router re-serves the request down the degradation ladder
  *immediately* (it cannot wait for end-of-queue: there is none) and the
  stream signals ``('restart', None)`` before the re-served tokens;
  terminal status ``degraded`` — visible, definite, trustworthy output.
* **Drain** — ``close('drain')`` stops admission, refuses the
  still-queued (retryable elsewhere), finishes live requests;
  ``close('snapshot')`` parks live+queued state in a resumable blob
  (``Router(..., resume=blob)`` picks them back up and completes them)
  and ends their streams ``cancelled``.  Either way the page pool drains
  to zero live pages — the leak check the load test asserts.

Every request ends in exactly one of ``ok | deadline | refused |
cancelled | degraded`` (docs/serving.md maps these to scheduler statuses
and HTTP codes).

Event-loop note: the jitted calls block the loop for one segment at a
time (milliseconds at serving shapes).  The engine yields between rounds,
which is what keeps submissions/cancellations responsive — this is an
in-process router, not a multi-host load balancer.
"""
from __future__ import annotations

import asyncio
import copy
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kvcache import (PageAllocator, PrefixCache, admission_pages,
                                cow_fork, n_pages_for)
from repro.launch.steps import (_parse_spec, init_serve_state, make_admit_fn,
                                make_extend_fn, make_probe_fn,
                                make_segment_fn)
from repro.runtime.failover import IntegrityReplay, SimulatedHardwareFailure
from repro.runtime.integrity import IntegrityEngine, parse_integrity
from repro.runtime.serving import exact_probe_spec, next_ladder_spec
from repro.runtime.watchdog import StepHang

__all__ = ["Router", "RequestHandle", "RouterResult", "Refused",
           "STATUS_OK", "STATUS_DEADLINE", "STATUS_REFUSED",
           "STATUS_CANCELLED", "STATUS_DEGRADED", "TERMINAL_STATUSES"]

STATUS_OK = "ok"
STATUS_DEADLINE = "deadline"
STATUS_REFUSED = "refused"
STATUS_CANCELLED = "cancelled"
STATUS_DEGRADED = "degraded"
TERMINAL_STATUSES = (STATUS_OK, STATUS_DEADLINE, STATUS_REFUSED,
                     STATUS_CANCELLED, STATUS_DEGRADED)

_RECOVERABLE = (SimulatedHardwareFailure, StepHang, IntegrityReplay)


class Refused(Exception):
    """Typed admission refusal (the 429/413/503 surface).

    ``reason``: 'queue' (transient overload — retry after ``retry_after``
    seconds), 'too_large' (permanent: the request cannot fit this
    router's capacity/page pool), 'draining' (shutdown in progress —
    retry against another replica)."""

    def __init__(self, reason: str, retry_after: float | None = None,
                 detail: str = ""):
        self.reason = reason
        self.retry_after = retry_after
        super().__init__(f"admission refused ({reason})"
                         + (f": {detail}" if detail else ""))


@dataclasses.dataclass
class RouterResult:
    status: str
    tokens: list


class _Request:
    """Host-side request record.  The snapshotable parts of a request's
    life (tokens, status, admission anchors) live in the engine's host
    dict keyed by rid; this object carries the *client-visible* half —
    the stream queue and its ``sent`` cursor — which deliberately stays
    OUT of failover snapshots so a bitwise replay never re-streams."""

    __slots__ = ("rid", "prompt", "max_new", "deadline_s", "deadline_steps",
                 "priority", "submit_t", "queue", "sent", "cancelled",
                 "restart_sent", "ended")

    def __init__(self, rid, prompt, max_new, deadline_s, deadline_steps,
                 priority, submit_t):
        self.rid = rid
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.max_new = int(max_new)
        self.deadline_s = deadline_s
        self.deadline_steps = deadline_steps
        self.priority = int(priority)
        self.submit_t = submit_t
        self.queue: asyncio.Queue = asyncio.Queue()
        self.sent = 0
        self.cancelled = False
        self.restart_sent = False
        self.ended = False

    def descriptor(self) -> dict:
        """Plain-data re-submission record for ``close('snapshot')``."""
        return {"rid": self.rid, "prompt": self.prompt.tolist(),
                "max_new": self.max_new, "deadline_s": self.deadline_s,
                "deadline_steps": self.deadline_steps,
                "priority": self.priority}


class RequestHandle:
    """Client handle: an event stream plus cancellation.

    ``events()`` yields ``('token', id)`` per streamed token,
    ``('restart', None)`` when a quarantined request is re-served down
    the degradation ladder (previously streamed tokens are void), and a
    final ``('end', status)``.  ``result()`` folds that stream into a
    ``RouterResult``.  Consume one of the two — they share the queue."""

    def __init__(self, req: _Request):
        self._req = req

    @property
    def rid(self) -> int:
        return self._req.rid

    def cancel(self) -> None:
        """Client disconnect: the engine frees the slot and recycles its
        pages at the next round boundary (status ``cancelled``)."""
        self._req.cancelled = True

    async def events(self):
        while True:
            ev = await self._req.queue.get()
            yield ev
            if ev[0] == "end":
                return

    async def result(self) -> RouterResult:
        tokens: list = []
        async for kind, val in self.events():
            if kind == "token":
                tokens.append(int(val))
            elif kind == "restart":
                tokens.clear()
            else:
                return RouterResult(status=val, tokens=tokens)
        raise AssertionError("event stream ended without a terminal status")


class Router:
    """Asyncio serving frontend over the continuous-batching scheduler.

    ``params`` are placed/prepared once at construction (the
    launch/serve.py ``_place`` rules).  ``buckets`` lists the one-shot
    prefill lengths (each compiles one admit fn); any other prompt
    length <= ``max_prompt`` takes the chunked path.  ``max_new_cap``
    bounds per-request budgets (page grants are sized from it).
    ``monitor``/``injector``/``snapshot_every`` are the PR 6 knobs with
    identical semantics; ``spec`` enables self-speculative decode
    segments (PR 7).  ``prefix_cache`` turns on page-aligned prefix
    sharing (int8 KV only; forces every admission chunked at
    ``chunk_len == page_size`` so hits stay bitwise-identical to cold).
    Call ``await start()`` before ``submit``."""

    def __init__(self, cfg, params, *, slots: int = 4, seg_len: int = 4,
                 kv: str = "int8", page_size: int = 8,
                 n_pages: int | None = None,
                 buckets: tuple = (8, 16, 32), chunk_len: int = 16,
                 max_prompt: int = 256, max_new_cap: int = 64,
                 max_queue: int = 64, eos_id: int | None = -1,
                 sample: str = "greedy", paged_attn: str = "auto",
                 spec: str | None = None, par=None, prepare: bool = True,
                 rng_seed: int = 0, monitor=None, injector=None,
                 snapshot_every: int = 0, max_replays: int = 3,
                 integrity: str = "off", prefix_cache: bool = False,
                 resume: dict | None = None, log=print):
        from repro.launch.serve import _place   # lazy: serve.py imports us
        self.cfg = cfg
        self.params = _place(cfg, params, par, prepare)
        self.slots = slots
        self.seg_len = seg_len
        self.kv = kv
        self.page_size = page_size
        self.buckets = tuple(sorted(set(int(b) for b in buckets)))
        self.chunk_len = int(chunk_len)
        self.prefix_cache = bool(prefix_cache)
        if self.prefix_cache:
            if kv != "int8":
                raise ValueError("prefix caching shares int8 physical "
                                 "pages; pass kv='int8'")
            # every admission (hit or miss) runs the one compiled extend
            # program at chunk_len == page_size, page-aligned from the
            # first divergent page — the bitwise hit-vs-cold contract
            self.chunk_len = self.page_size
        self.max_prompt = int(max_prompt)
        self.max_new_cap = int(max_new_cap)
        self.max_queue = int(max_queue)
        self.eos_id = eos_id
        self.eos = -1 if eos_id is None else eos_id
        self.sample = sample
        self.paged_attn = paged_attn
        self.spec = spec
        self.par = par
        self.rng_seed = rng_seed
        self.monitor = monitor
        self.injector = injector
        self.snapshot_every = snapshot_every
        self.max_replays = max_replays
        self.log = log

        sp = _parse_spec(spec)
        k_spec = sp[1] if sp else 0
        # in-flight write overhang past the committed position: spec
        # windows write up to k draft positions, a padded final prefill
        # chunk up to chunk_len - 1 pad positions — grants must cover
        # whichever the request's path can incur (core/kvcache.py
        # admission_pages is the shared accounting rule)
        self.headroom_bucket = k_spec
        self.headroom_chunked = max(k_spec, self.chunk_len - 1)
        self.capacity = self.max_prompt + self.max_new_cap \
            + max(self.headroom_bucket, self.headroom_chunked)
        self.mp = n_pages_for(self.capacity, page_size)
        period = parse_integrity(integrity)
        if period > 0 and kv != "int8":
            raise ValueError("integrity checksums cover the int8 paged "
                             "cache; use kv='int8' or integrity='off'")
        self._integrity = None
        if period > 0:
            from repro.core.qweights import golden_weight_copy
            self._integrity = IntegrityEngine(
                golden_weight_copy(self.params), period=period)
        self._state = init_serve_state(cfg, slots, self.capacity, kv=kv,
                                       page_size=page_size, n_pages=n_pages,
                                       seed=rng_seed, integrity=period > 0)
        self._alloc = PageAllocator(self._state["cache"]["k_pages"].shape[1]) \
            if kv == "int8" else None
        self.n_pages = self._alloc.n_pages if self._alloc is not None else None
        self._no_pages = jnp.zeros((self.mp,), jnp.int32)
        self._prefix = PrefixCache(self._alloc, page_size) \
            if self.prefix_cache else None

        self._segment = make_segment_fn(cfg, par, seg_len, eos_id=eos_id,
                                        sample=sample, paged_attn=paged_attn,
                                        spec=spec)
        self._extend = make_extend_fn(cfg, par, self.chunk_len,
                                      eos_id=eos_id, sample=sample,
                                      paged_attn=paged_attn)
        self._probe = None
        if monitor is not None and monitor.rel_threshold is not None:
            if cfg.dscim in ("off", "float"):
                raise ValueError("drift probes need a dscim serving spec "
                                 "(see runtime/serving.py)")
            cfg_probe = dataclasses.replace(
                cfg, dscim=exact_probe_spec(cfg.dscim), dscim_fault="")
            self._probe = make_probe_fn(cfg_probe, par)
        self._k_spec = k_spec

        # host bookkeeping — everything the failover snapshot must carry
        self._host = {
            "slot_rid": [-1] * slots,       # rid per slot (-1 free)
            "slot_pages": [None] * slots,
            "slot_phase": ["idle"] * slots,  # idle | prefill | decode
            "slot_fed": [0] * slots,         # chunked-prefill cursor
            "waiting": [],                   # admission queue (rids)
            "out": {},                       # rid -> [token, ...]
            "status": {},                    # rid -> None | terminal
            "restarted": {},                 # rid -> bool (ladder re-serve)
            "admit_t": {},                   # rid -> wall admission anchor
            "admit_step": {},                # rid -> global_step at admission
            "segments": 0, "global_step": 0,
            # decode slot-steps: all, those that served a live request,
            # those with no request in the slot, those held in chunked
            # prefill (the rest are done-masked tails)
            "slot_steps": 0, "live_slot_steps": 0,
            "empty_slot_steps": 0, "prefill_slot_steps": 0,
            # admissions and their summed submit -> admit seconds
            "admitted": 0, "queue_wait_s": 0.0,
            "prefill_computed": 0, "prefill_total": 0,
            "counters": {"deadline_cancelled": 0, "cancelled": 0,
                         "quarantined": 0, "degraded": 0, "refused_queue": 0,
                         "refused_too_large": 0, "refused_draining": 0},
        }
        self._requests: dict = {}            # rid -> _Request (NOT snapshot)
        self._inbox: list = []               # submitted, not yet ingested
        self._next_rid = 0
        self._replays = 0
        self._snap = None
        self._vsnap = None       # last integrity-verified snapshot
        self._draining = False
        self._drain_mode = "drain"
        self._engine_task = None
        self._wake: asyncio.Event | None = None
        self._tok_s_ema = 0.0
        self._resume_handles: dict = {}
        self._snapshot_blob = None
        if resume is not None:
            self._restore_blob(resume)

    # ------------------------------------------------------------------
    # client API
    # ------------------------------------------------------------------

    async def start(self) -> None:
        """Spawn the engine task on the running loop."""
        if self._engine_task is not None:
            return
        self._wake = asyncio.Event()
        self._engine_task = asyncio.create_task(self._engine())

    def _need_pages(self, prompt_len: int, max_new: int,
                    chunked: bool) -> int:
        head = self.headroom_chunked if chunked else self.headroom_bucket
        return admission_pages(prompt_len, max_new, self.page_size, head)

    def _queue_depth(self) -> int:
        return len(self._inbox) + len(self._host["waiting"])

    def _retry_after(self) -> float:
        """Throughput-derived backoff hint: the queued + live token debt
        over the recent useful tok/s (floored so a cold router still
        hints something finite)."""
        debt = 0
        for rid in self._host["waiting"]:
            rq = self._requests[rid]
            debt += len(rq.prompt) + rq.max_new
        for rid, rq in ((i, self._requests[i]) for i in self._inbox):
            debt += len(rq.prompt) + rq.max_new
        for b in range(self.slots):
            rid = self._host["slot_rid"][b]
            if rid >= 0:
                debt += self._requests[rid].max_new
        return debt / max(self._tok_s_ema, 1.0)

    def submit(self, prompt, max_new: int, *, deadline_s: float | None = None,
               deadline_steps: int | None = None,
               priority: int = 0) -> RequestHandle:
        """Admit one streaming request, or raise ``Refused``.

        ``prompt``: 1-D int32 token ids (any length <= ``max_prompt``).
        ``max_new``: generation budget (<= ``max_new_cap``), counted like
        the scheduler's — including the first prefill-sampled token.
        ``deadline_s`` anchors at *this call* (queue time counts);
        ``deadline_steps`` at admission.  ``priority`` orders admission
        only (higher first; FIFO within a class) — the router never
        preempts a live slot."""
        if self._draining:
            self._host["counters"]["refused_draining"] += 1
            raise Refused("draining")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        S = len(prompt)
        if S < 1 or S > self.max_prompt or max_new < 1 \
                or max_new > self.max_new_cap:
            self._host["counters"]["refused_too_large"] += 1
            raise Refused("too_large", detail=(
                f"prompt {S} tokens / budget {max_new} vs max_prompt "
                f"{self.max_prompt} / max_new_cap {self.max_new_cap}"))
        chunked = self.prefix_cache or S not in self.buckets
        if self.n_pages is not None \
                and self._need_pages(S, max_new, chunked) > self.n_pages:
            self._host["counters"]["refused_too_large"] += 1
            raise Refused("too_large", detail=(
                f"{self._need_pages(S, max_new, chunked)} pages needed, "
                f"pool holds {self.n_pages}"))
        if self._queue_depth() >= self.max_queue:
            self._host["counters"]["refused_queue"] += 1
            raise Refused("queue", retry_after=self._retry_after(),
                          detail=f"admission queue at {self.max_queue}")
        rid = self._next_rid
        self._next_rid += 1
        req = _Request(rid, prompt, max_new, deadline_s, deadline_steps,
                       priority, time.perf_counter())
        self._requests[rid] = req
        self._inbox.append(rid)
        if self._wake is not None:
            self._wake.set()
        return RequestHandle(req)

    async def close(self, mode: str = "drain") -> dict | None:
        """Graceful shutdown.  ``'drain'``: stop admission, serve live
        requests to completion, end still-queued streams ``refused``
        (retryable elsewhere).  ``'snapshot'``: stop admission, park live
        + queued request state in a resumable blob (returned; feed it to
        ``Router(..., resume=blob)``) and end their streams
        ``cancelled``.  Either way every granted page is freed."""
        if mode not in ("drain", "snapshot"):
            raise ValueError(f"close mode must be 'drain' or 'snapshot', "
                             f"got {mode!r}")
        self._draining = True
        self._drain_mode = mode
        if self._wake is not None:
            self._wake.set()
        if self._engine_task is not None:
            await self._engine_task
            self._engine_task = None
        return self._snapshot_blob

    def resume_handles(self) -> dict:
        """rid -> RequestHandle for requests revived from a resume blob
        (their streams start over from token 0 — the pre-snapshot client
        connections are gone)."""
        return dict(self._resume_handles)

    def stats(self) -> dict:
        h = self._host
        useful = sum(len(o) for o in h["out"].values())
        done = [s for s in h["status"].values() if s is not None]
        return {
            "submitted": self._next_rid,
            "completed": len(done),
            "statuses": {s: done.count(s) for s in TERMINAL_STATUSES
                         if done.count(s)},
            "refusals": {k[8:]: v for k, v in h["counters"].items()
                         if k.startswith("refused_")},
            "counters": dict(h["counters"]),
            "segments": h["segments"],
            "replays": self._replays,
            "useful_tokens": useful,
            "occupancy": h["live_slot_steps"] / max(h["slot_steps"], 1),
            **{k: h[k] for k in ("slot_steps", "live_slot_steps",
                                 "empty_slot_steps", "prefill_slot_steps",
                                 "admitted", "queue_wait_s")},
            "pages": self._alloc.stats() if self._alloc is not None else None,
            "prefix": (dict(self._prefix.stats(),
                            prefill_positions_computed=h["prefill_computed"],
                            prefill_positions_total=h["prefill_total"])
                       if self._prefix is not None else None),
            "queue_depth": self._queue_depth(),
            "integrity": (dict(self._integrity.stats(),
                               detections=self._integrity.detections)
                          if self._integrity is not None else None),
        }

    # ------------------------------------------------------------------
    # failover snapshot/restore
    # ------------------------------------------------------------------

    def _take_snapshot(self) -> dict:
        with jax.profiler.TraceAnnotation("router.snapshot"):
            return {"state": jax.device_get(self._state),
                    "host": copy.deepcopy(self._host),
                    "alloc": self._alloc.snapshot()
                    if self._alloc is not None else None,
                    "prefix": self._prefix.snapshot()
                    if self._prefix is not None else None}

    def _restore_blob(self, blob: dict) -> None:
        self._state = jax.device_put(blob["state"])
        self._host = copy.deepcopy(blob["host"])
        h = self._host
        # blobs from before the slot-step counters were renamed and split
        h.setdefault("slot_steps", h.pop("total_steps", 0))
        h.setdefault("live_slot_steps", h.pop("live_steps", 0))
        for k in ("empty_slot_steps", "prefill_slot_steps", "admitted",
                  "prefill_computed", "prefill_total"):
            h.setdefault(k, 0)
        h.setdefault("queue_wait_s", 0.0)
        if blob["alloc"] is not None:
            self._alloc = PageAllocator.from_snapshot(blob["alloc"])
        if blob.get("prefix") is not None:
            self._prefix = PrefixCache.from_snapshot(blob["prefix"],
                                                     self._alloc)
        elif self._prefix is not None:
            # prefix router resumed from a pre-prefix blob: start a
            # fresh index bound to the restored allocator
            self._prefix = PrefixCache(self._alloc, self.page_size)
        # arrivals ingested after the snapshot was taken vanish from the
        # restored host — re-ingest anything the snapshot doesn't know
        for rid in sorted(self._requests):
            if rid not in self._host["status"] and rid not in self._inbox:
                self._inbox.append(rid)
        # resumed-from-disk blobs carry request descriptors
        for d in blob.get("requests", ()):
            rid = int(d["rid"])
            if rid in self._requests:
                continue
            req = _Request(rid, d["prompt"], d["max_new"], d["deadline_s"],
                           d["deadline_steps"], d["priority"],
                           time.perf_counter())
            self._requests[rid] = req
            self._resume_handles[rid] = RequestHandle(req)
            self._next_rid = max(self._next_rid, rid + 1)

    # ------------------------------------------------------------------
    # the engine
    # ------------------------------------------------------------------

    def _finish(self, rid: int, status: str) -> None:
        if self._host["status"].get(rid) is None:
            self._host["status"][rid] = status

    def _free_slot(self, b: int) -> None:
        h = self._host
        if self._alloc is not None and h["slot_pages"][b] is not None:
            self._alloc.free(h["slot_pages"][b])
            h["slot_pages"][b] = None
        h["slot_rid"][b] = -1
        h["slot_phase"][b] = "idle"
        h["slot_fed"][b] = 0

    def _release(self, rid: int, status: str) -> None:
        """Terminal-status a request wherever it currently lives."""
        h = self._host
        self._finish(rid, status)
        if rid in h["waiting"]:
            h["waiting"].remove(rid)
        for b in range(self.slots):
            if h["slot_rid"][b] == rid:
                self._free_slot(b)
                self._state = dict(
                    self._state,
                    done=self._state["done"].at[b].set(True))

    def _expired(self, rid: int, now: float) -> bool:
        h = self._host
        if h["status"].get(rid) is not None:
            return False
        req = self._requests[rid]
        if req.deadline_steps is not None and rid in h["admit_step"] \
                and h["global_step"] - h["admit_step"][rid] \
                >= int(req.deadline_steps):
            return True
        if req.deadline_s is not None and req.deadline_s > 0 \
                and now - req.submit_t >= float(req.deadline_s):
            return True
        return False

    def _ingest(self) -> None:
        """Move submitted requests into the admission queue, priority
        first (stable within a class — submission order)."""
        h = self._host
        if not self._inbox:
            return
        for rid in self._inbox:
            h["status"].setdefault(rid, None)
            h["out"].setdefault(rid, [])
            h["waiting"].append(rid)
        self._inbox.clear()
        h["waiting"].sort(key=lambda r: (-self._requests[r].priority, r))

    def _admit_waiting(self) -> None:
        """Fill free slots head-of-line from the admission queue (no
        skip-ahead: a big request at the head holds its place — admission
        order is the priority contract)."""
        h = self._host
        for b in range(self.slots):
            if h["slot_rid"][b] >= 0 or not h["waiting"]:
                continue
            rid = h["waiting"][0]
            req = self._requests[rid]
            S = len(req.prompt)
            chunked = self.prefix_cache or S not in self.buckets
            pages = self._no_pages
            d_shared = 0
            if self._alloc is not None:
                need = self._need_pages(S, req.max_new, chunked)
                shared: list = []
                if self._prefix is not None:
                    _n, shared = self._prefix.acquire(
                        req.prompt, (S - 1) // self.page_size)
                d_shared = len(shared)
                fresh = self._alloc.alloc(need - d_shared)
                if fresh is None:
                    if shared:             # return the borrowed refs
                        self._alloc.free(shared)
                    return                     # pool exhausted: wait
                ids = shared + fresh
                h["slot_pages"][b] = ids
                pages = jnp.asarray(ids + [ids[-1]] * (self.mp - need),
                                    jnp.int32)
            h["waiting"].pop(0)
            h["slot_rid"][b] = rid
            h["admit_t"][rid] = time.perf_counter()
            h["admit_step"][rid] = h["global_step"]
            h["admitted"] += 1
            h["queue_wait_s"] += h["admit_t"][rid] - req.submit_t
            if chunked:
                # begin-admit: point the slot's page-table row at its
                # grant and rewind its position past any shared prefix;
                # the slot stays done-masked until the final chunk emits
                cache = self._state["cache"]
                if self._prefix is not None and h["slot_pages"][b]:
                    # enforcement point: writes land only on private
                    # pages — a shared page at/after the write frontier
                    # would be forked here (fresh grants never are)
                    cache, ids, _nf = cow_fork(cache, self._alloc,
                                               h["slot_pages"][b],
                                               start_idx=d_shared)
                    h["slot_pages"][b] = ids
                    pages = jnp.asarray(
                        ids + [ids[-1]] * (self.mp - len(ids)), jnp.int32)
                fed0 = d_shared * self.page_size
                upd = {"pos": cache["pos"].at[b].set(fed0)}
                if "page_table" in cache:
                    upd["page_table"] = cache["page_table"].at[b].set(pages)
                self._state = dict(self._state, cache=dict(cache, **upd),
                                   done=self._state["done"].at[b].set(True))
                h["slot_phase"][b] = "prefill"
                h["slot_fed"][b] = fed0
                h["prefill_computed"] += S - fed0
                h["prefill_total"] += S
            else:
                admit = make_admit_fn(self._cfg_now, self.par,
                                      eos_id=self.eos_id, sample=self.sample)
                self._state, tok0 = admit(
                    self.params, self._state,
                    jnp.asarray(req.prompt[None]), jnp.int32(b), pages,
                    jnp.int32(req.max_new))
                with jax.profiler.TraceAnnotation("router.wait_first_token"):
                    tok0 = int(tok0)
                h["out"][rid].append(tok0)
                h["slot_phase"][b] = "decode"

    def _feed_chunks(self) -> None:
        """One prompt chunk per prefilling slot per round — long
        admissions interleave with decode segments instead of stalling
        them."""
        h = self._host
        C = self.chunk_len
        cfg_now = self._cfg_now
        extend = self._extend if cfg_now is self.cfg else \
            make_extend_fn(cfg_now, self.par, C, eos_id=self.eos_id,
                           sample=self.sample, paged_attn=self.paged_attn)
        for b in range(self.slots):
            if h["slot_phase"][b] != "prefill":
                continue
            rid = h["slot_rid"][b]
            req = self._requests[rid]
            fed = h["slot_fed"][b]
            part = req.prompt[fed:fed + C]
            n_real = len(part)
            if n_real < C:
                part = np.pad(part, (0, C - n_real))
            emit = fed + n_real >= len(req.prompt)
            with jax.profiler.TraceAnnotation("router.extend"):
                self._state, tok0 = extend(
                    self.params, self._state, jnp.asarray(part[None]),
                    jnp.int32(b), jnp.int32(n_real), jnp.bool_(emit),
                    jnp.int32(req.max_new))
            h["slot_fed"][b] = fed + n_real
            if emit:
                with jax.profiler.TraceAnnotation("router.wait_first_token"):
                    tok0 = int(tok0)
                h["out"][rid].append(tok0)
                h["slot_phase"][b] = "decode"
                if self._prefix is not None and h["slot_pages"][b]:
                    # every fully-flushed prompt page is now immutable
                    # (writes continue past pos) — index it for reuse
                    self._prefix.register(
                        req.prompt,
                        h["slot_pages"][b][:len(req.prompt)
                                           // self.page_size])

    def _ladder_reserve(self, rid: int) -> None:
        """Quarantined request: re-serve from the prompt down the
        degradation ladder (runtime/serving.py ``_escalate`` semantics,
        request-granular), replacing its discarded tokens.  Terminal
        status ``degraded`` — the client sees a restart event and a
        definite, verified output."""
        from repro.launch.serve import serve_batch
        h = self._host
        req = self._requests[rid]
        thresh = self.monitor.rel_threshold \
            if self.monitor is not None \
            and self.monitor.rel_threshold is not None else float("inf")
        level = self.cfg.dscim
        prompt = req.prompt[None]
        kw = dict(par=self.par, prepare=False, eos_id=self.eos,
                  max_new=[req.max_new], sample=self.sample, kv=self.kv,
                  page_size=self.page_size, rng_seed=self.rng_seed)
        while True:
            spec = next_ladder_spec(level) or level
            cfg_lvl = dataclasses.replace(self.cfg, dscim=spec,
                                          dscim_fault="")
            toks, lgs = serve_batch(cfg_lvl, self.params, prompt,
                                    req.max_new, **kw)
            terminal = next_ladder_spec(spec) is None
            ok = True
            if not terminal and np.isfinite(thresh):
                cfg_ex = dataclasses.replace(
                    self.cfg, dscim=exact_probe_spec(spec), dscim_fault="")
                _, lgs_ex = serve_batch(cfg_ex, self.params, prompt,
                                        req.max_new, **kw)
                s = np.asarray(lgs[0], np.float64).ravel()
                e = np.asarray(lgs_ex[0], np.float64).ravel()
                rms = max(float(np.sqrt(np.mean(e * e))), 1e-9)
                rel = float(np.sqrt(np.mean((s - e) ** 2))) / rms
                ok = np.isfinite(rel) and rel <= thresh
            self.log(f"[router] ladder: request {rid} {level} -> {spec} "
                     f"({'accepted' if ok else 'still drifting'})")
            if ok:
                row = np.asarray(toks[0])
                n_use = req.max_new
                hits = np.nonzero(row[:n_use] == self.eos)[0]
                if len(hits):
                    n_use = int(hits[0]) + 1
                h["out"][rid] = row[:n_use].tolist()
                h["status"][rid] = STATUS_DEGRADED
                h["counters"]["degraded"] += 1
                return
            level = spec

    @property
    def _cfg_now(self):
        """The serving config in force this segment — a persistent
        injected macro fault rewrites ``dscim_fault`` exactly like the
        fault-tolerant scheduler does."""
        fault = self.injector.serving_fault(self._host["segments"]) \
            if self.injector is not None else ""
        if not fault:
            return self.cfg
        return dataclasses.replace(self.cfg, dscim_fault=fault)

    def _scrub(self) -> None:
        """Integrity sweep at a segment boundary: repair weight planes
        from the golden copy, check live KV pages; on any mismatch
        restore the last verified snapshot (``IntegrityReplay``), else
        take a new one."""
        h = self._host
        bad_w = self._integrity.check_weights(self.params)
        if bad_w:
            self.params = self._integrity.repair_weights(self.params,
                                                         bad_w)
            self.log(f"[router] integrity: weight plane(s) {bad_w} "
                     "restored from golden copy")
        coords = []
        if self._alloc is not None:
            pos_h = np.asarray(self._state["cache"]["pos"])
            live_pages = np.zeros((self._alloc.n_pages,), bool)
            for b in range(self.slots):
                ids = h["slot_pages"][b]
                if ids is not None:
                    for p in ids[:int(pos_h[b]) // self.page_size]:
                        live_pages[int(p)] = True
            coords = self._integrity.check_pages(self._state["cache"],
                                                 live_pages)
            if coords:
                self.log(f"[router] integrity: corrupted page(s) at "
                         f"(layer, page) {coords}")
        if bad_w or coords:
            # slot-scoped repair lives in the scheduler
            # (runtime/serving.py); the router takes the always-safe
            # path — restore the last *verified* snapshot and replay.
            # Repaired weights persist on self.params; transient
            # flips fire once, so the replay runs clean.
            self._integrity.note_replay()
            self._snap = self._vsnap
            raise IntegrityReplay(
                f"weights {bad_w or 'clean'}, pages {coords or 'clean'}")
        self._vsnap = self._take_snapshot()

    def _round(self) -> bool:
        """One engine round: ingest/cancel/harvest/deadline/admit, one
        chunk per prefilling slot, one decode segment if anything is
        live.  Returns True if any request can still make progress."""
        h = self._host
        seg = h["segments"]
        if self._snap is not None and self.snapshot_every > 0 \
                and seg % self.snapshot_every == 0:
            self._snap = self._take_snapshot()
        if self.injector is not None:
            self.injector.maybe_fail(seg)

        self._ingest()
        now = time.perf_counter()
        for rid, req in self._requests.items():        # cancellations
            if req.cancelled and h["status"].get(rid) is None:
                self._release(rid, STATUS_CANCELLED)
                h["counters"]["cancelled"] += 1
        with jax.profiler.TraceAnnotation("router.wait_done"):
            done_h = np.asarray(self._state["done"])
        for b in range(self.slots):                    # harvest finished
            rid = h["slot_rid"][b]
            if rid >= 0 and h["slot_phase"][b] == "decode" and done_h[b]:
                self._free_slot(b)
                self._finish(rid, STATUS_OK)
        for rid in list(h["status"]):                  # deadline sweep
            if self._expired(rid, now):
                self._release(rid, STATUS_DEADLINE)
                h["counters"]["deadline_cancelled"] += 1
        if self._draining:
            if self._drain_mode == "snapshot":
                return self._drain_snapshot()
            for rid in list(h["waiting"]):   # drain: shed the queue,
                self._release(rid, STATUS_REFUSED)     # retryable elsewhere
                h["counters"]["refused_draining"] += 1
        else:
            with jax.profiler.TraceAnnotation("router.admit"):
                self._admit_waiting()
        self._feed_chunks()

        live_b = [b for b in range(self.slots)
                  if h["slot_rid"][b] >= 0 and h["slot_phase"][b] == "decode"]
        live0 = np.zeros((self.slots,), bool)
        if live_b:
            with jax.profiler.TraceAnnotation("router.wait_done"):
                done_h = np.asarray(self._state["done"])
            for b in live_b:
                live0[b] = not done_h[b]
        if not live0.any():
            prefilling = any(p == "prefill" for p in h["slot_phase"])
            busy = bool(h["waiting"]) or bool(self._inbox) or prefilling \
                or any(r >= 0 for r in h["slot_rid"])
            return busy

        lg_exact = None
        if self._probe is not None and self.monitor.should_probe(seg):
            with jax.profiler.TraceAnnotation("router.probe"):
                lg_exact = np.asarray(self._probe(self.params, self._state))
        corrupted: list = []
        if self.injector is not None and self._alloc is not None:
            cache2, hit = self.injector.corrupt_cache(
                seg, self._state["cache"], h["slot_pages"])
            if hit:
                self._state = dict(self._state, cache=cache2)
                corrupted = hit
        if self.injector is not None \
                and getattr(self.injector, "weight_flips", None):
            p2, whit = self.injector.corrupt_weights(seg, self.params)
            if whit:
                self.params = p2
        if self._integrity is not None and self._integrity.due(seg):
            with jax.profiler.TraceAnnotation("router.integrity"):
                self._scrub()
        cfg_now = self._cfg_now
        segment = self._segment if cfg_now is self.cfg else \
            make_segment_fn(cfg_now, self.par, self.seg_len,
                            eos_id=self.eos_id, sample=self.sample,
                            paged_attn=self.paged_attn, spec=self.spec)
        n_empty = sum(rid < 0 for rid in h["slot_rid"])
        n_prefill = h["slot_phase"].count("prefill")
        with jax.profiler.TraceAnnotation("router.segment"):
            self._state, toks, lives, aux = segment(self.params, self._state)
        with jax.profiler.TraceAnnotation("router.wait_tokens"):
            toks_h = np.asarray(toks)
            lives_h = np.asarray(lives)
        with jax.profiler.TraceAnnotation("router.harvest"):
            for s in range(toks_h.shape[0]):
                for b in range(self.slots):
                    if lives_h[s, b] and h["slot_rid"][b] >= 0:
                        h["out"][h["slot_rid"][b]].append(int(toks_h[s, b]))
            if self.monitor is not None:
                bad = np.asarray(aux["bad"]).any(axis=0)
                trip = bad.copy()
                if lg_exact is not None:
                    t2, _ = self.monitor.check(np.asarray(aux["logits0"]),
                                               lg_exact, live0)
                    trip |= t2
                for b in np.nonzero(trip)[0]:
                    rid = h["slot_rid"][int(b)]
                    if rid < 0:
                        continue
                    self._free_slot(int(b))
                    self._state = dict(
                        self._state,
                        done=self._state["done"].at[int(b)].set(True))
                    h["out"][rid] = []          # discard poisoned tokens
                    h["restarted"][rid] = True
                    h["counters"]["quarantined"] += 1
                    self._ladder_reserve(rid)
            n = toks_h.shape[0]
            h["slot_steps"] += n * self.slots
            h["live_slot_steps"] += int(lives_h.sum())
            h["empty_slot_steps"] += n * n_empty
            h["prefill_slot_steps"] += n * n_prefill
            h["segments"] += 1
            h["global_step"] += self.seg_len * (self._k_spec + 1)
        return True

    def _drain_snapshot(self) -> bool:
        """snapshot-mode close: park every live/prefilling request's
        descriptor + the full serve state, free all pages, end streams
        ``cancelled``."""
        h = self._host
        parked = [rid for rid in h["status"]
                  if h["status"][rid] is None]
        blob = self._take_snapshot()
        blob["requests"] = [self._requests[rid].descriptor()
                            for rid in parked]
        self._snapshot_blob = blob
        for rid in parked:
            self._release(rid, STATUS_CANCELLED)
        return False

    def _flush_streams(self) -> None:
        """Push newly harvested tokens / terminal statuses to client
        queues.  ``sent`` cursors are not snapshot state: a failover
        replay regrows ``out`` underneath them bit-identically, so
        nothing re-streams; a ladder re-serve flips ``restarted`` and
        restreams from zero behind an explicit restart event."""
        h = self._host
        with jax.profiler.TraceAnnotation("router.flush_streams"):
            for rid, req in self._requests.items():
                if req.ended:
                    continue
                out = h["out"].get(rid)
                if out is None:
                    continue
                if h["restarted"].get(rid) and not req.restart_sent:
                    req.queue.put_nowait(("restart", None))
                    req.restart_sent = True
                    req.sent = 0
                while req.sent < len(out):
                    req.queue.put_nowait(("token", out[req.sent]))
                    req.sent += 1
                status = h["status"].get(rid)
                if status is not None and req.sent >= len(out):
                    req.queue.put_nowait(("end", status))
                    req.ended = True

    async def _engine(self) -> None:
        use_ft = self.injector is not None or self.snapshot_every > 0 \
            or self._integrity is not None
        if use_ft:
            # the initial state is integrity-verified by construction
            self._snap = self._take_snapshot()
            self._vsnap = self._snap
        emitted_before = 0
        t_last = time.perf_counter()
        while True:
            try:
                with jax.profiler.TraceAnnotation("router.round"):
                    busy = self._round()
            except _RECOVERABLE as e:
                self._replays += 1
                self.log(f"[router] {type(e).__name__}: {e}; replay "
                         f"{self._replays}/{self.max_replays}")
                if self._snap is None or self._replays > self.max_replays:
                    # unrecoverable: every non-terminal request still
                    # gets a definite status
                    self._ingest()
                    for rid in list(self._host["status"]):
                        if self._host["status"][rid] is None:
                            self._release(rid, STATUS_CANCELLED)
                    self._flush_streams()
                    return
                self._restore_blob(self._snap)
                continue
            self._flush_streams()
            # throughput EMA for retry-after hints
            emitted = sum(len(o) for o in self._host["out"].values())
            now = time.perf_counter()
            if now - t_last > 1e-3:
                inst = (emitted - emitted_before) / (now - t_last)
                self._tok_s_ema = inst if self._tok_s_ema == 0.0 \
                    else 0.8 * self._tok_s_ema + 0.2 * inst
                emitted_before, t_last = emitted, now
            if self._draining and not busy and not self._inbox:
                self._flush_streams()
                return
            if busy or self._inbox:
                await asyncio.sleep(0)
            else:
                # idle: wait for a submission/cancel/close, waking
                # periodically so wall deadlines on queued work expire
                self._wake.clear()
                try:
                    await asyncio.wait_for(self._wake.wait(), timeout=0.05)
                except asyncio.TimeoutError:
                    pass
