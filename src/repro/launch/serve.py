"""Batched serving driver: device-resident generation with the DS-CIM
approximate-MVM path as a first-class serving option (--dscim).

Generation is **scanned** by default: ``serve_batch`` builds one jitted
``generate`` (launch/steps.py ``make_generate_fn``) that runs prefill plus
up to (n_tokens-1) decode steps on device — the host dispatches exactly
once per request instead of once per token, the KV cache lives in the
loop carry (buffers reused in place, never copied back to host), and
tokens accumulate on device.  The legacy host loop (one jitted decode
dispatch per token, cache donated between calls) is kept behind
``scan=False`` as the dispatch-overhead A/B; benchmarks/serve_bench
records both as tok/s trajectory rows.

Only-live-work serving (ISSUE 4):

* **EOS early exit** (``eos_id=...`` / ``--eos``): the scanned loop
  becomes a ``lax.while_loop`` that stops the moment every slot has
  emitted EOS (or hit its per-slot ``max_new`` budget).  Finished slots
  are done-masked — their cache position stops advancing, their tokens
  pin to ``pad_id`` — so completion is ragged, and no decode steps run
  past the last live slot.
* **Sampling in the scan** (``sample=...`` / ``--temp --top-k --top-p``):
  greedy argmax remains the default; 'temp:<t>', 'topk:<k>[:<t>]' and
  'topp:<p>[:<t>]' (nucleus) draw inside the jitted loop with the PRNG
  key riding the carry (one split per step — the while and scan drivers
  sample identically).
* **Int8 paged KV cache** (``kv='int8'`` / ``--kv int8``): decode reads
  an int8 block-paged cache with per-page per-kv-head scales
  (core/kvcache.py) — ~4x fewer resident decode cache bytes, dequant
  fused into the paged flash attention inner loop, capacity decoupled
  from request length via the page table.  Since ISSUE 5 the read loop
  is a single-launch Pallas kernel for 'kernel' dscim modes
  (kernels/paged_attention.py; ``--paged-attn kernel|jnp`` /
  ``REPRO_PAGED_ATTN`` forces either path — the jnp gather scan stays
  the reference).
* **Continuous batching** (``serve_continuous`` / ``--continuous``): a
  scheduler above the scanned loop — requests are admitted into freed
  slots between fixed-size scan segments (launch/steps.py
  ``make_segment_fn``/``make_admit_fn``), carries (cache, per-slot
  positions, done mask, RNG) persist across segments, pages are
  allocated at admission and recycled at completion, and throughput is
  reported per *live* slot-step so occupancy is visible.
* **Self-speculative decoding** (``spec='dscim2:<k>'`` / ``--spec``,
  ISSUE 7): the *same* prepared weights run twice — k greedy draft
  tokens through the cheaper stochastic estimator (dscim2/L64 or
  dscim1/L256; the paper's two operating points), then one batched
  verify forward over the k+1-token window through the serving
  estimator, accepting drafts by the standard speculative rule.  The
  whole draft/verify/accept window lives inside the device-resident
  loop (``lax.while_loop`` / segment scan carry — never a host
  round-trip per window), the KV cache (dense float and int8 paged)
  follows a write-then-rollback discipline for provisional draft
  positions, and greedy emission is **bitwise-identical** to non-spec
  greedy serving; sampled emission replays the carried PRNG key chain
  (replay-deterministic).  Interaction contract (what the flag means
  next to the ISSUE 6 fault-tolerance knobs):

  - **"step" accounting / deadlines** — one window *attempts* k+1
    verifier positions, so under ``--spec`` a segment advances the
    global step ledger by ``seg_len * (k+1)``: drafted-but-rejected
    positions count toward ``deadline_steps``.  A request therefore
    never outlives the deadline it would have had without speculation
    (rejections only spend budget faster); deadline checks stay at
    segment boundaries.
  - **eviction / re-admission** — rollback happens inside the window
    (before the segment returns), so evicted slots park *committed*
    state only; page grants are sized with +k headroom at admission and
    pages are never allocated, freed, or leaked mid-window.
  - **watchdog / quarantine** — the exact-mode probe compares against
    the segment's first-window *verify* logits at position 0, i.e. it
    probes the verifier estimator on exactly the (token, cache) inputs
    it re-decodes; the drafter is never probed (a bad drafter can only
    cost acceptance rate, never output quality).  A request whose
    verify path trips the watchdog is quarantined and re-served down
    the usual ladder (dscim2 -> dscim1 -> exact) **without
    speculation** — escalation is about trust, so the re-serve takes
    the plain verified path and the request still ends ``'ok'`` (or
    ``'quarantined'`` only if even exact re-serving fails its twin).

DS-CIM modes map to DSCIMLinear backends (core/dscim_layer.py):
  exact        — int8 adder-tree baseline (DCIM)
  lut          — bit-exact DS-CIM emulation (joint-count LUT, the oracle)
  kernel       — the serving hot path: fused single-launch Pallas kernel
                 (kernels/dscim_fused.py)
  paper_inject — paper-style per-output error injection (fast)
A '+attn' mode suffix (e.g. kernel+attn:dscim1:256) additionally routes the
attention projections through the macro.

Prepare-once weights (default, --no-prepare to A/B) and multi-chip meshes
(--mesh, e.g. --mesh model=4) behave as in PR 2/3: prepared int8 planes
shard N over 'model', the whole loop runs under the mesh, bit-identical
to single-device serving on CPU devices (on TPU v5e chips the decode step
is not yet bitwise; PERF.md has the measurements).  The paged KV pool
shards over the DP axes like the request batch (launch/sharding.py
``cache_partition``).

The serve report compares greedy tokens + logit RMSE against the float
path, which is the model-level reproduction of the paper's Table II
methodology on our own checkpoints.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.launch.steps import (make_decode_step, make_generate_fn,
                                make_prefill_step, prepare_serving_params)
from repro.models import get_model

__all__ = ["serve_batch", "serve_continuous", "logit_drift_rmse", "main"]


def _place(cfg, params, par, prepare):
    if prepare:
        params = prepare_serving_params(cfg, params, par)
    if par is not None:
        from repro.launch.sharding import param_specs, to_shardings
        params = jax.device_put(
            params, to_shardings(par.mesh, param_specs(cfg, par, params)))
    return params


def serve_batch(cfg, params, prompts: np.ndarray, n_tokens: int,
                par=None, prepare: bool = True, scan: bool = True,
                trace_logits: bool = False, eos_id: int | None = None,
                sample: str = "greedy", kv: str = "float",
                page_size: int = 8, max_new=None, rng_seed: int = 0,
                paged_attn: str = "auto", spec: str | None = None,
                spec_stats: bool = False):
    """prompts (B, S) int32 -> generated (B, n_tokens) int32, logits list.

    ``par``: ParallelCtx for multi-chip serving — params are placed by the
    launch/sharding.py rules (prepared qweights N-sharded over 'model')
    and the whole generation loop runs under the mesh.
    ``prepare``: quantize DS-CIM-eligible weights once before jitting
    (no-op when cfg.dscim is 'off'/'float'); pass False to A/B the legacy
    per-call weight-quantization path.
    ``scan``: device-resident generation (default — one dispatch per
    request); False runs the legacy host loop (one dispatch per token,
    cache donated between steps; greedy/float-KV/fixed-length only).
    ``trace_logits``: also return the per-step logit trace (off the hot
    path by default: the returned list then holds only prefill logits).
    ``eos_id``: EOS early-exit — the loop becomes a ``lax.while_loop``
    that stops once every row is finished; tokens past a row's EOS are
    pinned to pad.  ``max_new`` ((B,) ints, optional) adds per-slot token
    budgets (counted including the first, prefill-sampled token).
    ``sample``: 'greedy' | 'temp:<t>' | 'topk:<k>[:<t>]' | 'topp:<p>[:<t>]'
    (``rng_seed`` seeds the in-scan PRNG key).
    ``kv``: 'float' (dense cache) | 'int8' (block-paged quantized cache,
    ``page_size`` tokens per page).
    ``paged_attn``: int8 read path — 'kernel' (fused Pallas paged
    attention) / 'jnp' (gather reference) pin it (and key the builder
    cache, so in-process A/Bs are safe); 'auto' follows cfg.dscim.
    ``spec``: '<variant>:<k>' self-speculative decoding (module
    docstring) — draft k tokens per window with the cheaper estimator,
    verify in one batched forward; greedy output is bitwise the non-spec
    output.  ``spec_stats=True`` additionally returns a third element
    ``{"windows": (B,), "emitted": (B,)}`` np.int32 — per-row verify
    windows and emitted tokens, whose ratio is accepted-tokens-per-verify
    (serve_bench's serve/spec_* rows)."""
    from repro.launch.steps import _parse_spec
    sp = _parse_spec(spec)
    params = _place(cfg, params, par, prepare)
    batch = {"tokens": jnp.asarray(prompts)}
    if max_new is not None:
        batch["max_new"] = jnp.asarray(max_new, jnp.int32)
        if eos_id is None:
            raise ValueError("max_new budgets need the early-exit variant; "
                             "pass eos_id (any id, e.g. -1, works)")
    if sample != "greedy":
        batch["rng"] = jax.random.PRNGKey(rng_seed)
    if scan:
        generate = make_generate_fn(cfg, par, n_tokens,
                                    trace_logits=trace_logits,
                                    eos_id=eos_id, sample=sample,
                                    kv=kv, page_size=page_size,
                                    paged_attn=paged_attn, spec=spec)
        if sp is not None:
            tokens, logits, sstats = generate(params, batch)
        else:
            tokens, logits = generate(params, batch)
            sstats = None
        trace = list(np.asarray(logits)) if trace_logits else [logits]
        if spec_stats:
            sstats = (None if sstats is None else
                      {k: np.asarray(v) for k, v in sstats.items()})
            return np.asarray(tokens), trace, sstats
        return np.asarray(tokens), trace
    # legacy host loop (dispatch-overhead A/B baseline)
    if eos_id is not None or sample != "greedy" or kv != "float" \
            or sp is not None:
        raise ValueError("the legacy host loop serves the greedy fixed-"
                         "length float-KV path only (scan=True for "
                         "eos/sampling/paged-KV/spec)")
    capacity = prompts.shape[1] + n_tokens
    prefill = jax.jit(make_prefill_step(cfg, par, capacity=capacity))
    if trace_logits:
        # per-step logits ride along so the two drivers A/B the full trace
        decode_lg = jax.jit(make_decode_step(cfg, par, return_logits=True),
                            donate_argnums=(2,))
    else:
        decode = jax.jit(make_decode_step(cfg, par), donate_argnums=(2,))
    logits, cache = prefill(params, batch)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    out, logit_trace = [tok], [logits]
    for _ in range(n_tokens - 1):
        if trace_logits:
            tok, logits, cache = decode_lg(params, {"token": tok}, cache)
            logit_trace.append(logits)
        else:
            tok, cache = decode(params, {"token": tok}, cache)
        out.append(tok)
    return np.stack([np.asarray(t) for t in out], axis=1), logit_trace


def serve_continuous(cfg, params, prompts: np.ndarray, n_tokens: int, *,
                     slots: int = 4, seg_len: int = 4, max_new=None,
                     eos_id: int | None = None, sample: str = "greedy",
                     kv: str = "float", page_size: int = 8,
                     n_pages: int | None = None, par=None,
                     prepare: bool = True, rng_seed: int = 0,
                     paged_attn: str = "auto", spec: str | None = None,
                     deadline_steps=None,
                     deadline_s=None, priority=None, monitor=None,
                     injector=None, snapshot_every: int = 0,
                     max_replays: int = 3, watchdog=None,
                     integrity: str = "off", prefix_cache=False, log=print):
    """Continuous-batching scheduler: serve a queue of R requests through
    ``slots`` persistent decode slots.

    prompts (R, S) int32 — the request queue (fixed prompt length per
    scheduler; length bucketing is a follow-on).  Between fixed-size scan
    segments (``seg_len`` done-masked decode steps in one dispatch,
    launch/steps.py ``make_segment_fn``) the host admits waiting requests
    into freed slots with one jitted prefill each (``make_admit_fn``) —
    the KV cache, per-slot positions, done mask and RNG key persist across
    segments.  A request completes on EOS (``eos_id``) or its per-request
    budget (``max_new`` (R,), default ``n_tokens``), releasing its slot
    (and, for ``kv='int8'``, its physical pages — ``n_pages`` sizes the
    pool independently of slots x max_len) for the next admission.

    Returns (outputs, stats): ``outputs[r]`` is request r's np.int32 token
    array (<= its budget, ending at EOS if hit); ``stats`` records wall
    time, end-to-end tok/s over *useful* tokens (i.e. credited per live
    slot-step — dead/padded slot-steps earn nothing), batch occupancy
    = live slot-steps / total slot-steps, and the fault-tolerance
    counters below.

    Failure semantics (ISSUE 6 — runtime/serving.py implements these; with
    every knob at its default the scheduler behaves exactly like the
    plain loop):

    * **Statuses.**  ``stats['status'][r]`` is always definite:
      ``'ok'`` — the request ran to EOS/budget (possibly after a failover
      replay, an eviction round trip, or a ladder escalation), its tokens
      are complete and trustworthy; ``'deadline'`` — cancelled at a
      segment boundary when its budget expired, ``outputs[r]`` holds the
      partial tokens generated so far (possibly none if it was still
      queued).  A client should treat ``'deadline'`` as retryable with a
      larger budget; tokens already returned remain valid prefixes.
    * **Deadlines.**  ``deadline_steps`` (R,) — global decode-step budget,
      deterministic and replay-safe (a negative entry = none);
      ``deadline_s`` (R,) — wall-clock seconds from the request's
      *admission* (<= 0 = none): a late admission gets its full budget
      and a queued request never wall-expires (PR 8 — previously
      measured from serve start, silently shrinking late admissions').
      Both are checked between segments only: a request can overrun by
      at most one segment (``seg_len`` steps).
    * **Eviction / re-admission** (``priority`` (R,), int8 KV only).
      When the page pool blocks an admission, live requests of *strictly*
      lower priority are preempted (lowest priority first, youngest on
      ties): their page contents are snapshotted host-side bit-exactly
      and the request re-enters mid-stream once pages free — under greedy
      decoding the round trip is bitwise-invisible in its output.
      ``stats['evictions']/['readmissions']/['evicted_requests']`` count
      the traffic.
    * **Snapshot / restore** (``snapshot_every`` > 0).  Full serve-state
      checkpoints (device pytree + scheduler bookkeeping + allocator)
      every N boundaries; recoverable failures (injected device loss,
      watchdog hangs) restore the latest snapshot and replay bit-
      identically, up to ``max_replays`` times (``stats['replays']``).
      ``injector`` (runtime/failover.py ``FailureInjector``) drives chaos
      tests — device loss, transient page-pool bit flips
      (``stats['corrupted_requests']``), persistent stuck-at macro faults.
    * **Accuracy watchdog + degradation ladder** (``monitor``, an
      ``AccuracyWatchdog``).  NaN/Inf logits are checked every segment;
      every ``probe_every`` segments an exact-mode decode of the same
      (token, cache) inputs bounds the serving path's logit drift.  A
      tripped request is quarantined (poisoned tokens discarded) and
      re-served from its prompt down the ladder dscim2 -> dscim1 ->
      exact (``stats['quarantined']/['escalations']``), each level
      verified against its exact twin before acceptance — so a returned
      ``'ok'`` is trustworthy even under estimator faults.  ``watchdog``
      (a runtime/watchdog.py ``Watchdog``) additionally wraps each
      segment for straggler/hang detection (``stats['stragglers']``).
    * **Prefix caching** (``prefix_cache``, int8 KV only, ISSUE 10).
      ``True``/'on': admissions run page-aligned chunked prefill and
      share physical pages across page-aligned prompt prefixes via the
      refcounted allocator + prefix-hash index — a hit skips prefill
      (and quantization) for the shared pages entirely, bitwise-
      identically to cold serving; 'cold' runs the same chunked path
      with sharing disabled (the drill's reference leg).
      ``stats['prefix']`` reports hits, hit tokens, pages deduped, and
      prefill positions computed vs total (docs/serving.md has the full
      operator contract).
    """
    from repro.runtime.serving import serve_continuous_ft
    params = _place(cfg, params, par, prepare)
    return serve_continuous_ft(
        cfg, params, prompts, n_tokens, slots=slots, seg_len=seg_len,
        max_new=max_new, eos_id=eos_id, sample=sample, kv=kv,
        page_size=page_size, n_pages=n_pages, par=par, rng_seed=rng_seed,
        paged_attn=paged_attn, spec=spec, deadline_steps=deadline_steps,
        deadline_s=deadline_s, priority=priority, monitor=monitor,
        injector=injector, snapshot_every=snapshot_every,
        max_replays=max_replays, watchdog=watchdog, integrity=integrity,
        prefix_cache=prefix_cache, log=log)


def _sample_spec(args) -> str:
    # `is not None` so --temp 0 reaches the sampler's t > 0 validation
    # instead of silently degrading to greedy / t=1
    if args.top_k is not None and args.top_p is not None:
        raise SystemExit("--top-k and --top-p are mutually exclusive")
    if args.top_k is not None:
        return f"topk:{args.top_k}:" \
               f"{args.temp if args.temp is not None else 1.0}"
    if args.top_p is not None:
        return f"topp:{args.top_p}:" \
               f"{args.temp if args.temp is not None else 1.0}"
    if args.temp is not None:
        return f"temp:{args.temp}"
    return "greedy"


def _useful_lengths(tokens: np.ndarray, eos_id: int | None) -> np.ndarray:
    """Per-row token count up to and including the first EOS."""
    n = tokens.shape[1]
    if eos_id is None:
        return np.full((tokens.shape[0],), n)
    out = []
    for row in tokens:
        hits = np.nonzero(row == eos_id)[0]
        out.append(int(hits[0]) + 1 if len(hits) else n)
    return np.asarray(out)


def _useful_tokens(tokens: np.ndarray, eos_id: int | None) -> int:
    """Tokens up to and including each row's first EOS — the early-exit
    report must not credit the pad tokens past it."""
    return int(_useful_lengths(tokens, eos_id).sum())


def logit_drift_rmse(tokens_ref, tokens_alt, logits_ref, logits_alt):
    """RMSE between two drivers' per-step logit traces on the teacher-
    matched prefix: per row, steps up to and including the first token
    divergence — past it the drivers feed different tokens back, so the
    comparison would measure feedback divergence, not the perturbation
    under test (e.g. int8 KV quantization).  ``logits_*`` are the
    trace_logits stacks ((n_steps, B, V) after np.stack), ``tokens_*``
    the (B, n_steps) token outputs.  Shared by benchmarks/serve_bench.py
    and the acceptance test so the metric can't drift between them."""
    lf, lq = np.stack(logits_ref), np.stack(logits_alt)
    tokens_ref, tokens_alt = np.asarray(tokens_ref), np.asarray(tokens_alt)
    n = tokens_ref.shape[1]
    errs = []
    for b in range(tokens_ref.shape[0]):
        mism = np.nonzero(tokens_ref[b] != tokens_alt[b])[0]
        end = mism[0] + 1 if len(mism) else n
        errs.append(((lf[:end, b] - lq[:end, b]) ** 2).ravel())
    return float(np.sqrt(np.mean(np.concatenate(errs))))


def _agreement(a: np.ndarray, b: np.ndarray, eos_id: int | None) -> float:
    """Token agreement over the reference rows' useful prefixes only —
    pad-vs-pad positions past EOS would otherwise inflate the metric."""
    lens = _useful_lengths(b, eos_id)
    hits = sum(int((a[i, :l] == b[i, :l]).sum()) for i, l in enumerate(lens))
    return hits / max(int(lens.sum()), 1)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve the arch's reduced widths (default); "
                         "--no-reduced serves its published widths")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--dscim", default="off",
                    help="off | <mode>[+attn]:<variant>:<L>  e.g. "
                         "kernel:dscim1:256 (fused Pallas hot path) or "
                         "lut:dscim1:256 (oracle)")
    ap.add_argument("--no-prepare", action="store_true",
                    help="keep float weights and re-quantize per call "
                         "(legacy hot path; default is prepare-once int8)")
    ap.add_argument("--host-loop", action="store_true",
                    help="legacy one-dispatch-per-token host loop instead "
                         "of the scanned device-resident generate (A/B)")
    ap.add_argument("--mesh", default=None, metavar="SPEC",
                    help="serve under a mesh, e.g. 'model=4' or "
                         "'data=2,model=4' (needs that many jax devices; "
                         "prepared qweights shard N over 'model')")
    ap.add_argument("--eos", type=int, default=None, metavar="ID",
                    help="EOS token id: the scanned loop becomes a "
                         "lax.while_loop that exits once every row has "
                         "finished (done-masked ragged completion)")
    ap.add_argument("--temp", type=float, default=None,
                    help="temperature sampling inside the scan (default "
                         "greedy argmax)")
    ap.add_argument("--top-k", type=int, default=None,
                    help="top-k sampling inside the scan (combines with "
                         "--temp)")
    ap.add_argument("--top-p", type=float, default=None,
                    help="top-p (nucleus) sampling inside the scan: keep "
                         "the smallest probability mass >= p (combines "
                         "with --temp; exclusive with --top-k)")
    ap.add_argument("--spec", default=None, metavar="VARIANT:K",
                    help="self-speculative decoding, e.g. 'dscim2:4': "
                         "draft K tokens per window with the cheaper "
                         "estimator on the same prepared weights, verify "
                         "with one batched forward through --dscim; "
                         "greedy output is bitwise the non-spec output "
                         "(module docstring documents the deadline/"
                         "eviction/watchdog contract)")
    ap.add_argument("--paged-attn", choices=("auto", "kernel", "jnp"),
                    default="auto",
                    help="--kv int8 read path: the fused Pallas paged-"
                         "attention kernel or the jnp gather reference "
                         "(auto = kernel for 'kernel' dscim modes)")
    ap.add_argument("--kv", choices=("float", "int8"), default="float",
                    help="KV cache layout: dense float (default) or the "
                         "block-paged int8 cache (core/kvcache.py)")
    ap.add_argument("--page-size", type=int, default=8,
                    help="tokens per KV page for --kv int8")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching: serve --requests prompts "
                         "through --batch persistent slots, admitting "
                         "between --segment-len step scan segments")
    ap.add_argument("--requests", type=int, default=8,
                    help="queue length for --continuous")
    ap.add_argument("--segment-len", type=int, default=4,
                    help="decode steps per scan segment for --continuous")
    ap.add_argument("--chaos", action="store_true",
                    help="run the self-verifying chaos drill "
                         "(runtime/serving.py chaos_drill): injected "
                         "device loss + page-pool bit flips + a stuck-at "
                         "macro fault + a deadline expiry over the fault-"
                         "tolerant scheduler, asserting the failure-"
                         "semantics contract end to end")
    ap.add_argument("--integrity", default="off", metavar="MODE",
                    help="serving integrity checks (runtime/integrity.py): "
                         "'off', 'verify' (every segment boundary) or "
                         "'scrub:<n>' (every n-th) — checksummed int8 KV "
                         "pages + prepared-weight plane digests with "
                         "targeted self-healing; requires --kv int8")
    ap.add_argument("--integrity-drill", action="store_true",
                    help="run the self-verifying integrity drill "
                         "(runtime/serving.py integrity_drill): injected "
                         "page-pool and weight-plane bit flips under "
                         "--integrity scrub:2 — asserts exact-coordinate "
                         "detection, surgical repair, and bitwise-"
                         "identical outputs vs the fault-free run")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share int8 KV pages across page-aligned prompt "
                         "prefixes on the --continuous run (refcounted "
                         "copy-on-write pages + prefix-hash index, "
                         "core/kvcache.py): hits skip prefill for the "
                         "shared pages, bitwise-identically; requires "
                         "--kv int8")
    ap.add_argument("--prefix-drill", action="store_true",
                    help="run the self-verifying prefix-cache drill "
                         "(runtime/serving.py prefix_drill): staggered "
                         "admissions with a shared system prompt, warm "
                         "vs cold legs — asserts bitwise parity, visible "
                         "page dedup, >40% prefill positions removed, "
                         "and a drained pool")
    ap.add_argument("--sampled-chaos", action="store_true",
                    help="arm a FailureInjector.sampled schedule (seeded "
                         "by --chaos-seed) on the --continuous run: device "
                         "losses + page/weight bit upsets; pairs with "
                         "--integrity to exercise detect/repair/replay "
                         "under randomized faults")
    ap.add_argument("--chaos-seed", type=int, default=0, metavar="SEED",
                    help="--chaos determinism pin: seeds the drill's "
                         "params/prompts so a CI chaos failure reproduces "
                         "exactly from the logged seed (default 0, the CI "
                         "seed)")
    ap.add_argument("--tune", action="store_true",
                    help="consult the fused-kernel tile autotuner (the "
                         "checked-in cache makes this a lookup for the "
                         "serving decode shapes)")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_compilation_cache
    use_compilation_cache()
    if args.chaos:
        from repro.runtime.serving import chaos_drill
        chaos_drill(args.arch, seed=args.chaos_seed)
        return 0
    if args.integrity_drill:
        from repro.runtime.serving import integrity_drill
        integrity_drill(args.arch, seed=args.chaos_seed)
        return 0
    if args.prefix_drill:
        from repro.runtime.serving import prefix_drill
        prefix_drill(args.arch, seed=args.chaos_seed)
        return 0
    if args.tune:
        import os
        os.environ["REPRO_DSCIM_TUNE"] = "1"
    par = None
    if args.mesh:
        from repro.launch.mesh import parallel_ctx_from_spec
        par = parallel_ctx_from_spec(args.mesh)
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.dscim != "off":
        import dataclasses
        cfg_ds = dataclasses.replace(cfg, dscim=args.dscim)
    model = get_model(cfg)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    sample = _sample_spec(args)

    if args.continuous:
        cfgs = [("float", cfg)] + ([(args.dscim, cfg_ds)]
                                   if args.dscim != "off" else [])
        prompts = rng.integers(0, cfg.vocab, (args.requests, args.prompt_len),
                               dtype=np.int32)
        if args.prefix_cache:
            # a shared page-aligned "system prompt" over 3/4 of the queue
            # so the prefix index has something to hit
            shared = max(args.prompt_len // 2, args.page_size)
            prompts[:args.requests * 3 // 4, :shared] = prompts[0, :shared]
        # skewed per-request budgets exercise slot recycling
        budgets = rng.integers(max(2, args.tokens // 4), args.tokens + 1,
                               (args.requests,), dtype=np.int32)
        for tag, c in cfgs:
            injector = None
            snapshot_every = 0
            if args.sampled_chaos:
                from repro.core.qweights import split_dscim_mode
                from repro.runtime.failover import FailureInjector
                prepared = split_dscim_mode(
                    getattr(c, "dscim", "off"))[0] not in ("off", "float")
                # a fresh injector per leg: the fired-once set is stateful
                injector = FailureInjector.sampled(
                    args.chaos_seed, segments=8, slots=args.batch,
                    n_layers=c.n_layers, page_size=args.page_size,
                    device_losses=1, flips=2,
                    weight_paths=("layers/mlp/w_up",) if prepared else (),
                    weight_flip_count=1 if prepared else 0)
                snapshot_every = 1
            outs, stats = serve_continuous(
                c, params, prompts, args.tokens, slots=args.batch,
                seg_len=args.segment_len, max_new=budgets,
                eos_id=args.eos if args.eos is not None else -1,
                sample=sample, kv=args.kv, page_size=args.page_size,
                par=par, prepare=not args.no_prepare,
                paged_attn=args.paged_attn, spec=args.spec,
                injector=injector, snapshot_every=snapshot_every,
                integrity=args.integrity,
                prefix_cache=args.prefix_cache)
            extra = ""
            if stats.get("integrity"):
                ig = stats["integrity"]
                extra = (f", integrity: {ig['checks']} checks, "
                         f"{ig['page_mismatches']}p/"
                         f"{ig['weight_mismatches']}w mismatches, "
                         f"{ig['page_repairs'] + ig['weight_repairs']} "
                         f"repairs, {ig['replays']} replays")
            if stats.get("prefix"):
                pf = stats["prefix"]
                extra += (f", prefix: {pf['hits']}/{pf['lookups']} hits, "
                          f"{pf['pages_deduped']} pages deduped, "
                          f"{pf['prefill_positions_computed']}/"
                          f"{pf['prefill_positions_total']} prefill "
                          "positions computed")
            print(f"[serve-cb] {tag}: {stats['tok_s']:.1f} tok/s over "
                  f"{stats['useful_tokens']} useful tokens, occupancy "
                  f"{stats['occupancy']:.2f} "
                  f"({stats['live_slot_steps']}/{stats['slot_steps']} "
                  f"slot-steps live, "
                  f"{stats['segments']} segments of {args.segment_len}"
                  f"){extra}")
        return 0

    mode = "host-loop" if args.host_loop else "scanned"
    prompts = rng.integers(0, cfg.vocab, (args.batch, args.prompt_len),
                           dtype=np.int32)
    t0 = time.time()
    base_tokens, base_logits = serve_batch(
        cfg, params, prompts, args.tokens, par=par, scan=not args.host_loop,
        eos_id=args.eos, sample=sample, kv=args.kv,
        page_size=args.page_size, paged_attn=args.paged_attn)
    dt = time.time() - t0
    useful = _useful_tokens(base_tokens, args.eos)
    tps = useful / dt
    print(f"[serve] float path ({mode}"
          f"{', mesh ' + args.mesh if args.mesh else ''}"
          f"{', kv=int8' if args.kv == 'int8' else ''}): {tps:.1f} tok/s "
          f"({useful} useful tokens, batch={args.batch}, "
          f"{args.tokens} steps)")

    if args.dscim != "off":
        t0 = time.time()
        out = serve_batch(
            cfg_ds, params, prompts, args.tokens, par=par,
            prepare=not args.no_prepare, scan=not args.host_loop,
            eos_id=args.eos, sample=sample, kv=args.kv,
            page_size=args.page_size, paged_attn=args.paged_attn,
            spec=args.spec, spec_stats=args.spec is not None)
        dt = time.time() - t0
        if args.spec is not None:
            ds_tokens, ds_logits, sstats = out
        else:
            ds_tokens, ds_logits = out
            sstats = None
        agree = _agreement(ds_tokens, base_tokens, args.eos)
        rmse = float(jnp.sqrt(jnp.mean(
            (ds_logits[0] - base_logits[0]) ** 2)))
        acc = ""
        if sstats is not None:
            tpv = (sstats["emitted"] - 1).sum() / max(
                int(sstats["windows"].sum()), 1)
            acc = f", {tpv:.2f} accepted tok/verify (--spec {args.spec})"
        print(f"[serve] dscim={args.dscim}: "
              f"{_useful_tokens(ds_tokens, args.eos) / dt:.1f} "
              f"tok/s, token agreement {agree:.3f}, "
              f"prefill logit RMSE {rmse:.4f}{acc}")
    return 0


if __name__ == "__main__":
    main()
