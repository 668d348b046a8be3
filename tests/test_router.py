"""Async serving router (ISSUE 8, runtime/router.py): bucketed one-shot
admission bitwise-equal to the continuous scheduler, chunked prefill
invariant under chunk size (sequential-decode equivalence), typed
admission refusals, mid-stream cancellation, submission-anchored wall
deadlines, failover replay invisibility, quarantine -> degraded streams,
snapshot-drain -> resume completion, and the zero-page-leak drain
invariant; the slot-step and queue-wait counters and the profiler spans of
the round and the HTTP front end — plus the loadtest helpers' trace
shape."""
import asyncio
import collections
import dataclasses
import glob
import json
import os
import socket
import time

import jax
import numpy as np
import pytest

from repro.configs import get_arch
from repro.launch.serve import serve_continuous
from repro.models import get_model
from repro.runtime.failover import FailureInjector
from repro.runtime.router import Refused, Router
from repro.runtime.watchdog import AccuracyWatchdog

V = 151                    # > any token the tests draw


def _setup(dscim="off"):
    cfg = get_arch("qwen3-0.6b").reduced()
    if dscim != "off":
        cfg = dataclasses.replace(cfg, dscim=dscim)
    model = get_model(cfg)
    return cfg, model.init_params(cfg, jax.random.PRNGKey(0))


# shared knobs: identical (cfg, knob) tuples hit the lru-cached jitted
# builders across Router instances, so the file compiles each program once
KN = dict(seg_len=2, kv="int8", page_size=4, buckets=(4, 8), chunk_len=4,
          max_prompt=24, max_new_cap=8, log=lambda *a: None)


def _router(cfg, params, **kw):
    return Router(cfg, params, **{**KN, **kw})


async def _drained(router):
    await router.close()
    assert router.stats()["pages"]["live_pages"] == 0, router.stats()


def test_bucket_admission_bitwise_vs_serve_continuous():
    """One-shot (bucket-length) admissions through the router emit
    bitwise the tokens serve_continuous gives the same prompts — greedy
    deterministic serving is schedule-independent, and the router reuses
    the scheduler's jitted admit/segment programs."""
    cfg, params = _setup()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, V, s).astype(np.int32)
               for s in (4, 8, 4, 8, 4)]
    budgets = [5, 3, 6, 4, 2]

    async def run():
        r = _router(cfg, params, slots=3)
        await r.start()
        res = await asyncio.gather(*[r.submit(p, b).result()
                                     for p, b in zip(prompts, budgets)])
        await _drained(r)
        return res

    res = asyncio.run(run())
    assert [x.status for x in res] == ["ok"] * 5
    for length in (4, 8):
        rows = [i for i, p in enumerate(prompts) if len(p) == length]
        outs, _ = serve_continuous(
            cfg, params, np.stack([prompts[i] for i in rows]),
            max(budgets[i] for i in rows), slots=2, seg_len=2, kv="int8",
            page_size=4, max_new=[budgets[i] for i in rows], eos_id=-1,
            log=lambda *a: None)
        for j, i in enumerate(rows):
            assert res[i].tokens == outs[j].tolist(), (i, length)


def test_chunked_prefill_chunk_size_invariance():
    """Chunked prefill is sequential-decode equivalent: chunk_len=1 IS
    sequential decode (one prompt token per decode_multi call), so every
    other chunking — including a padded, rolled-back final chunk — must
    produce bitwise the same stream."""
    cfg, params = _setup()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, V, s).astype(np.int32) for s in (11, 6)]

    async def run(chunk_len):
        r = _router(cfg, params, slots=2, buckets=(64,),
                    chunk_len=chunk_len)
        await r.start()
        res = await asyncio.gather(*[r.submit(p, 5).result()
                                     for p in prompts])
        await _drained(r)
        return [x.tokens for x in res]

    ref = asyncio.run(run(1))
    assert all(len(t) == 5 for t in ref)
    for chunk_len in (3, 4, 6):
        assert asyncio.run(run(chunk_len)) == ref, chunk_len


def test_refusals_typed():
    """submit() backpressure is typed, not a hang: too_large is permanent
    (could never fit), queue is transient with a retry hint, draining is
    the shutdown surface.  None of them create a request."""
    cfg, params = _setup()
    rng = np.random.default_rng(2)

    async def run():
        r = _router(cfg, params, slots=1, max_queue=2)
        await r.start()
        with pytest.raises(Refused) as e:
            r.submit(rng.integers(1, V, 30), 4)      # > max_prompt
        assert e.value.reason == "too_large"
        with pytest.raises(Refused) as e:
            r.submit(rng.integers(1, V, 4), 99)      # > max_new_cap
        assert e.value.reason == "too_large"
        hs = [r.submit(rng.integers(1, V, 4), 4) for _ in range(2)]
        with pytest.raises(Refused) as e:
            r.submit(rng.integers(1, V, 4), 4)       # queue full
        assert e.value.reason == "queue"
        assert e.value.retry_after is not None and e.value.retry_after > 0
        res = await asyncio.gather(*[h.result() for h in hs])
        assert [x.status for x in res] == ["ok", "ok"]
        await _drained(r)
        with pytest.raises(Refused) as e:
            r.submit(rng.integers(1, V, 4), 4)
        assert e.value.reason == "draining"
        st = r.stats()
        assert st["refusals"] == {"queue": 1, "too_large": 2,
                                  "draining": 1}

    asyncio.run(run())


def test_cancel_mid_stream_recycles_pages():
    """handle.cancel() (the client-disconnect path) ends the stream with
    'cancelled' at the next round, frees the slot, and returns its pages
    to the pool while other requests keep streaming."""
    cfg, params = _setup()
    rng = np.random.default_rng(3)

    async def run():
        r = _router(cfg, params, slots=2)
        await r.start()
        h_other = r.submit(rng.integers(1, V, 4), 8)
        h = r.submit(rng.integers(1, V, 4), 8)
        got = []
        async for kind, val in h.events():
            if kind == "token":
                got.append(val)
                if len(got) == 2:
                    h.cancel()
            else:
                status = val
        assert status == "cancelled"
        assert len(got) < 8             # genuinely cut short
        other = await h_other.result()
        assert other.status == "ok" and len(other.tokens) == 8
        assert r.stats()["counters"]["cancelled"] == 1
        await _drained(r)

    asyncio.run(run())


def test_deadline_s_anchored_at_submission():
    """Router wall deadlines are end-to-end SLOs: the clock starts at
    submit(), so a request stuck behind a long stream can expire while
    still queued (0 tokens) — and an admitted request past its budget
    ends 'deadline' with its partial tokens intact."""
    cfg, params = _setup()
    rng = np.random.default_rng(4)

    async def run():
        r = _router(cfg, params, slots=1)
        await r.start()
        h0 = r.submit(rng.integers(1, V, 4), 8)          # hog the slot
        hq = r.submit(rng.integers(1, V, 4), 2, deadline_s=1e-3)
        res0, resq = await asyncio.gather(h0.result(), hq.result())
        assert res0.status == "ok"
        assert resq.status == "deadline" and resq.tokens == []
        h1 = r.submit(rng.integers(1, V, 4), 8, deadline_steps=2)
        res1 = await h1.result()
        assert res1.status == "deadline"
        assert 1 <= len(res1.tokens) < 8                 # partial kept
        await _drained(r)

    asyncio.run(run())


def test_failover_replay_is_invisible():
    """An injected device loss mid-serve restores the latest snapshot and
    replays; streams see no duplicate or missing tokens and the final
    outputs are bitwise the unfaulted run's."""
    cfg, params = _setup()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, V, 4).astype(np.int32) for _ in range(3)]

    async def run(injector):
        r = _router(cfg, params, slots=2, injector=injector,
                    snapshot_every=1)
        await r.start()
        res = await asyncio.gather(*[r.submit(p, 6).result()
                                     for p in prompts])
        stats = r.stats()
        await _drained(r)
        return res, stats

    faulted, st = asyncio.run(run(FailureInjector(fail_at=(2,))))
    clean, _ = asyncio.run(run(None))
    assert st["replays"] == 1
    for a, b in zip(faulted, clean):
        assert a.status == b.status == "ok"
        assert a.tokens == b.tokens


class _InfScaleInjector(FailureInjector):
    """Deterministic NaN source (see tests/test_serving_ft.py): one live
    dequant scale set to +inf at segment 1."""

    def corrupt_cache(self, segment, cache, slot_pages):
        key = ("inf", 1)
        if segment != 1 or key in self.fired or slot_pages[0] is None:
            return cache, []
        self.fired.add(key)
        pid = int(slot_pages[0][0])
        return dict(cache, v_scale=cache["v_scale"].at[0, pid, 0]
                    .set(np.inf)), [0]


def test_quarantine_streams_restart_and_degraded():
    """A NaN-quarantined request is re-served down the degradation ladder
    immediately; the client sees an explicit ('restart', None) voiding
    the streamed prefix, the full re-served output, and a terminal
    'degraded' — never silently-poisoned tokens."""
    cfg, params = _setup("kernel:dscim2:64")
    rng = np.random.default_rng(6)

    async def run():
        r = _router(cfg, params, slots=2, injector=_InfScaleInjector(),
                    monitor=AccuracyWatchdog(None), snapshot_every=1)
        await r.start()
        h = r.submit(rng.integers(1, V, 4), 6)
        events = []
        async for ev in h.events():
            events.append(ev)
        stats = r.stats()
        await _drained(r)
        return events, stats

    events, stats = asyncio.run(run())
    kinds = [k for k, _ in events]
    assert events[-1] == ("end", "degraded")
    assert "restart" in kinds
    tail = kinds[kinds.index("restart") + 1:]
    assert tail.count("token") == 6       # the full re-served output
    assert stats["counters"]["quarantined"] == 1
    assert stats["counters"]["degraded"] == 1


def test_drain_snapshot_resume_completes():
    """close('snapshot') parks live + queued requests in a blob (streams
    end 'cancelled', pages freed); Router(resume=blob) revives them and
    serves to completion with outputs bitwise an uninterrupted run's."""
    cfg, params = _setup()
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, V, 4).astype(np.int32) for _ in range(3)]

    async def interrupted():
        r = _router(cfg, params, slots=2)
        await r.start()
        hs = [r.submit(p, 6) for p in prompts]
        await asyncio.sleep(0)                   # let a round or two run
        blob = await r.close("snapshot")
        assert r.stats()["pages"]["live_pages"] == 0
        res = await asyncio.gather(*[h.result() for h in hs])
        assert {x.status for x in res} == {"cancelled"}
        assert blob is not None and blob["requests"]
        r2 = _router(cfg, params, slots=2, resume=blob)
        await r2.start()
        handles = r2.resume_handles()
        assert set(handles) == {d["rid"] for d in blob["requests"]}
        out = {rid: await h.result() for rid, h in handles.items()}
        await _drained(r2)
        return out

    async def uninterrupted():
        r = _router(cfg, params, slots=2)
        await r.start()
        res = await asyncio.gather(*[r.submit(p, 6).result()
                                     for p in prompts])
        await _drained(r)
        return res

    out = asyncio.run(interrupted())
    ref = asyncio.run(uninterrupted())
    for rid, got in out.items():
        assert got.status == "ok"
        assert got.tokens == ref[rid].tokens, rid


def test_loadtest_trace_shape():
    """The synthetic trace keeps its promises: arrival times are
    monotone, lengths/budgets respect the caps, both admission paths and
    at least one deadline/disconnect appear at realistic sizes."""
    from benchmarks.loadtest import make_trace
    trace = make_trace(0, 400, buckets=(4, 8), max_prompt=12,
                       max_new_cap=8)
    assert len(trace) == 400
    ts = [r["t"] for r in trace]
    assert ts == sorted(ts)
    lens = {len(r["prompt"]) for r in trace}
    assert lens & {4, 8}                        # bucketed one-shot path
    assert lens - {4, 8}                        # chunked path
    assert all(2 <= len(r["prompt"]) <= 12 for r in trace)
    assert all(1 <= r["max_new"] <= 8 for r in trace)
    assert any(r["deadline_steps"] is not None for r in trace)
    assert any(r["deadline_s"] is not None for r in trace)
    assert any(r["disconnect_after"] is not None for r in trace)
    # same seed, same trace — the reproducibility contract
    again = make_trace(0, 400, buckets=(4, 8), max_prompt=12,
                       max_new_cap=8)
    assert all(np.array_equal(a["prompt"], b["prompt"])
               and a["t"] == b["t"] and a["max_new"] == b["max_new"]
               for a, b in zip(trace, again))


def test_slot_step_counters_partition_the_segments():
    """Each decode slot-step of a segment is live, empty, held in chunked
    prefill, or the done-masked tail of a request that ended mid-segment:
    the counters add up, ``occupancy`` is live over all, every token but a
    request's prefill-sampled first is a live step, and every admission is
    counted."""
    cfg, params = _setup()
    rng = np.random.default_rng(8)
    # bucketed (4, 8) and chunked (11: three chunks of 4; 6: two) prompts
    prompts = [rng.integers(1, V, s).astype(np.int32)
               for s in (4, 11, 8, 11, 6)]
    budgets = [6, 3, 5, 2, 4]
    slots, seg_len = 2, KN["seg_len"]

    async def run():
        r = _router(cfg, params, slots=slots)
        await r.start()
        res = await asyncio.gather(*[r.submit(p, b).result()
                                     for p, b in zip(prompts, budgets)])
        st = r.stats()
        await _drained(r)
        return res, st

    res, st = asyncio.run(run())
    assert [x.status for x in res] == ["ok"] * len(prompts)
    total, live = st["slot_steps"], st["live_slot_steps"]
    empty, pre = st["empty_slot_steps"], st["prefill_slot_steps"]
    assert total == st["segments"] * seg_len * slots
    assert st["occupancy"] == live / total
    assert live == st["useful_tokens"] - len(prompts)
    assert empty > 0 and pre > 0
    assert live + empty + pre <= total
    assert total - (live + empty + pre) \
        <= st["segments"] * slots * (seg_len - 1)
    assert st["admitted"] == len(prompts)
    assert "tok_s" not in st


def test_queue_wait_counts_time_behind_a_full_slot():
    """A request submitted while every slot is busy is admitted only once
    the slot frees: its submit -> admit time spans the first request's
    whole decode, several engine rounds."""
    cfg, params = _setup()
    rng = np.random.default_rng(9)

    async def run():
        r = _router(cfg, params, slots=1)
        await r.start()
        h0 = r.submit(rng.integers(1, V, 4), 8)
        h1 = r.submit(rng.integers(1, V, 4), 8)
        t_submitted = time.perf_counter()
        async for kind, _ in h0.events():
            if kind == "token":
                t_last = time.perf_counter()
        assert (await h1.result()).status == "ok"
        st = r.stats()
        await _drained(r)
        return st, t_last - t_submitted

    st, behind = asyncio.run(run())
    assert st["admitted"] == 2
    # 7 decode steps of 2 per segment: at least 4 rounds behind the first
    assert st["segments"] >= 8
    assert st["queue_wait_s"] >= behind > 0


def _record(tmp_path, coro):
    """Run ``coro`` under the profiler (host spans, no Python tracer) and
    return the host spans as name -> [(thread, start_ns, end_ns)]."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        asyncio.run(coro)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    spans = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                s = int(ev.start_ns)
                spans[ev.name].append((line.name, s,
                                       s + int(ev.duration_ns)))
    return spans


def _inside(span, outer) -> bool:
    th, s, e = span
    return any(t == th and a <= s and e <= b for t, a, b in outer)


def test_round_spans_nest_and_do_not_overlap(tmp_path):
    """Under the profiler each round is one ``router.round`` span; the
    round's phases lie inside one, rounds never overlap (no span holds an
    await), and streams are flushed between rounds."""
    cfg, params = _setup()
    rng = np.random.default_rng(10)

    async def run():
        r = _router(cfg, params, slots=2)
        await r.start()
        hs = [r.submit(rng.integers(1, V, s), 5) for s in (4, 6, 11)]
        res = await asyncio.gather(*[h.result() for h in hs])
        assert [x.status for x in res] == ["ok"] * 3
        await _drained(r)

    spans = _record(tmp_path, run())
    rounds = sorted(spans["router.round"], key=lambda x: x[1])
    assert len(rounds) >= 3
    for (t0, _, e0), (t1, s1, _) in zip(rounds, rounds[1:]):
        assert t0 == t1 and e0 <= s1
    for name in ("router.segment", "router.wait_tokens", "router.harvest",
                 "router.wait_done", "router.admit", "router.extend",
                 "router.wait_first_token"):
        assert spans[name], name
        assert all(_inside(sp, rounds) for sp in spans[name]), name
    assert spans["router.flush_streams"]
    assert not any(_inside(sp, rounds) for sp in spans["router.flush_streams"])


def test_http_spans(tmp_path):
    """The front end marks parsing and submitting a request
    (``http.submit``) and writing each streamed event (``http.write``)."""
    from repro.launch.server import HttpFrontend
    cfg, params = _setup()
    prompt = np.random.default_rng(11).integers(1, V, 6).tolist()

    async def run():
        with socket.socket() as sk:
            sk.bind(("127.0.0.1", 0))
            port = sk.getsockname()[1]
        front = HttpFrontend(_router(cfg, params, slots=2), "127.0.0.1",
                             port, log=lambda *a: None)
        server = asyncio.create_task(front.serve())
        while front._server is None:
            await asyncio.sleep(0.01)
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        body = json.dumps({"prompt": prompt, "max_new": 3}).encode()
        writer.write(b"POST /v1/generate HTTP/1.1\r\nContent-Length: "
                     + str(len(body)).encode() + b"\r\n\r\n" + body)
        await writer.drain()
        reply = await reader.read()
        writer.close()
        front.request_drain()
        await server
        return reply

    spans = _record(tmp_path, run())
    assert len(spans["http.submit"]) == 1
    # three tokens and the terminal status
    assert len(spans["http.write"]) == 4
    rounds = spans["router.round"]
    assert not any(_inside(sp, rounds)
                   for sp in spans["http.submit"] + spans["http.write"])


def test_restore_fills_counters_missing_from_older_blobs():
    """A snapshot blob written before the slot-step counters were renamed
    and split restores with its counts under the new names."""
    cfg, params = _setup()
    r = _router(cfg, params, slots=2)
    blob = r._take_snapshot()
    host = blob["host"]
    for k in ("slot_steps", "live_slot_steps", "empty_slot_steps",
              "prefill_slot_steps", "admitted", "queue_wait_s"):
        del host[k]
    host["total_steps"], host["live_steps"] = 12, 9
    r._restore_blob(blob)
    st = r.stats()
    assert (st["slot_steps"], st["live_slot_steps"]) == (12, 9)
    assert st["occupancy"] == 0.75
    assert st["empty_slot_steps"] == st["prefill_slot_steps"] == 0
    assert st["admitted"] == 0 and st["queue_wait_s"] == 0.0
