"""Step-function builders shared by train.py, serve.py and dryrun.py —
plus ``prepare_serving_params``, the quantize-once entry of the DS-CIM
serve path (convert every eligible weight matrix to a resident int8
``QuantizedLinearWeight`` before jitting the prefill/decode steps, so no
weight quantization appears in the decode-step HLO), and
``make_generate_fn``, the device-resident generation loop (prefill + an
n-token ``lax.scan`` of decode steps inside one jit — the host sees one
dispatch per request instead of one per token).

ISSUE 4 additions — "only do live work" on the decode hot path:
  * ``make_generate_fn(eos_id=...)`` switches the fixed-length scan to a
    ``lax.while_loop`` that exits as soon as every slot has emitted EOS
    (or hit its optional per-slot ``batch["max_new"]`` budget), with
    per-slot done-masking: finished rows stop advancing their cache
    position and their tokens are pinned to ``pad_id``.
  * in-scan sampling: ``sample`` selects greedy (default, bit-compatible
    with PR 3) or ``'temp:<t>'`` / ``'topk:<k>[:<t>]'`` /
    ``'topp:<p>[:<t>]'`` (nucleus, ISSUE 5) — the PRNG key rides the
    scan/while carry, one split per step in both variants so the drivers
    draw identically.
  * ``kv='int8'`` serves from the block-paged int8 KV cache
    (core/kvcache.py) instead of the dense fixed-capacity one.
  * ``make_admit_fn`` / ``make_segment_fn`` / ``init_serve_state`` are the
    jitted halves of the continuous-batching scheduler (launch/serve.py):
    admission prefills one request into a free slot of a live batch
    (carries persist), segments run fixed-size scans of done-masked
    decode steps and report per-step live-slot occupancy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import get_model
from repro.models.lm import lm_loss
from repro.optim.adamw import AdamW
from repro.parallel import ParallelCtx

__all__ = ["make_train_step", "make_prefill_step", "make_decode_step",
           "make_eval_step", "make_generate_fn", "prepare_serving_params",
           "make_admit_fn", "make_segment_fn", "make_extend_fn",
           "init_serve_state", "make_probe_fn"]


def prepare_serving_params(cfg: ArchConfig, params,
                           par: ParallelCtx | None = None, *,
                           golden: bool = False):
    """Quantize-once weight preparation for DS-CIM serving.

    No-op for 'off'/'float' specs.  Otherwise every DS-CIM-eligible matrix
    (MLP, MoE shared expert, LM head — plus attention projections for
    '+attn' modes) is converted to a window-packed int8
    ``QuantizedLinearWeight`` with the serving layer's ``group_k``, matching
    the on-the-fly quantization bit for bit under f32 compute; under bf16
    compute the per-call path quantizes cast weights, prepare-once the f32
    originals (core/qweights.py).

    The MoE shared expert is prepared under a mesh too: its resident int8
    planes replicate across the mesh (launch/sharding.py) and the shard_map
    MoE body computes it locally, bit-identically to single-device serving
    (models/lm.py ``_moe_apply``) — the former float-only guard is gone.

    ``golden=True`` returns ``(prepared, golden_blob)`` where the blob is
    the host-side bit-exact copy + digest vector of every prepared plane
    (core/qweights.golden_weight_copy) — the integrity layer's repair
    source of truth, taken here because this is the one moment the planes
    are known-good by construction.  'off'/'float' specs have no prepared
    planes; their blob is ``None``."""
    from repro.core.qweights import (prepare_dscim_params, split_dscim_mode,
                                     golden_weight_copy)
    spec = getattr(cfg, "dscim", "off")
    if split_dscim_mode(spec)[0] in ("off", "float"):
        return (params, None) if golden else params
    from repro.models.lm import _linear_for
    lin = _linear_for(spec)
    prepared = prepare_dscim_params(params, cfg,
                                    group_k=lin.group_k if lin else 128)
    if golden:
        return prepared, golden_weight_copy(prepared)
    return prepared

AUX_WEIGHT = 0.01  # MoE load-balance loss weight


def make_train_step(cfg: ArchConfig, par: ParallelCtx | None,
                    opt: AdamW):
    model = get_model(cfg)

    def train_step(params, opt_state, batch):
        def loss_fn(p):
            logits, aux = model.forward(p, cfg, batch, par)
            return lm_loss(logits, batch["labels"]) + AUX_WEIGHT * aux

        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, opt_state, gnorm = opt.update(params, grads, opt_state)
        return params, opt_state, {"loss": loss, "grad_norm": gnorm}

    return train_step


def make_eval_step(cfg: ArchConfig, par: ParallelCtx | None = None):
    model = get_model(cfg)

    def eval_step(params, batch):
        logits, _ = model.forward(params, cfg, batch, par)
        return lm_loss(logits, batch["labels"])

    return eval_step


def make_prefill_step(cfg: ArchConfig, par: ParallelCtx | None,
                      capacity: int | None = None):
    model = get_model(cfg)

    def prefill_step(params, batch):
        return model.prefill(params, cfg, batch, par, capacity=capacity)

    return prefill_step


def make_decode_step(cfg: ArchConfig, par: ParallelCtx | None,
                     greedy: bool = True, return_logits: bool = False):
    """One greedy decode step.  ``return_logits``: also return the step's
    logits — the host-loop logit-trace driver (launch/serve.py) rides the
    same step function instead of re-implementing it."""
    model = get_model(cfg)

    def decode_step(params, batch, cache):
        logits, cache = model.decode(params, cfg, batch, cache, par)
        token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if return_logits:
            return token, logits, cache
        return token, cache

    return decode_step


def _make_sampler(sample: str):
    """Decode-rule factory: 'greedy' -> None (argmax, no RNG);
    'temp:<t>' -> temperature sampling; 'topk:<k>[:<t>]' -> top-k with
    optional temperature; 'topp:<p>[:<t>]' -> nucleus sampling (keep the
    smallest prefix of the temperature-scaled distribution with cumulative
    probability >= p — 'topp:1.0:<t>' is exactly 'temp:<t>').  The
    returned callable draws (key, logits) -> (B,) int32 inside the jitted
    loop."""
    if sample == "greedy":
        return None
    parts = sample.split(":")
    k = p = None
    if parts[0] == "temp" and len(parts) == 2:
        t = float(parts[1])
    elif parts[0] == "topk" and len(parts) in (2, 3):
        k = int(parts[1])
        t = float(parts[2]) if len(parts) == 3 else 1.0
    elif parts[0] == "topp" and len(parts) in (2, 3):
        p = float(parts[1])
        t = float(parts[2]) if len(parts) == 3 else 1.0
        if not 0.0 < p <= 1.0:
            raise ValueError(f"top-p must be in (0, 1], got {p}")
    else:
        raise ValueError(f"bad sample spec {sample!r}; want 'greedy', "
                         "'temp:<t>', 'topk:<k>[:<t>]' or 'topp:<p>[:<t>]'")
    if t <= 0:
        raise ValueError(f"temperature must be > 0, got {t}")

    def draw(key, logits):
        lg = logits.astype(jnp.float32) / t
        if k is not None:
            kth = jax.lax.top_k(lg, k)[0][..., -1:]
            lg = jnp.where(lg >= kth, lg, -jnp.inf)
        if p is not None:
            # nucleus: sort descending, keep tokens whose *exclusive*
            # cumulative probability is < p (the top token always stays),
            # i.e. the smallest set with inclusive cumsum >= p
            srt = -jnp.sort(-lg, axis=-1)
            probs = jax.nn.softmax(srt, axis=-1)
            excl = jnp.cumsum(probs, axis=-1) - probs
            nkeep = jnp.sum(excl < p, axis=-1, keepdims=True)
            kth = jnp.take_along_axis(srt, nkeep - 1, axis=-1)
            lg = jnp.where(lg >= kth, lg, -jnp.inf)
        # degenerate-row guard (ISSUE 6): a row whose masked logits hold a
        # NaN, a +inf, or no finite entry at all would make categorical's
        # gumbel-argmax return an arbitrary (or NaN-poisoned) id; fall
        # back to greedy argmax over the NaN-cleaned *original* logits for
        # that row (argmax is scale-invariant, so temperature is moot).
        # Healthy rows see bit-identical draws: their lg is untouched.
        bad = jnp.isnan(lg).any(-1) | jnp.isposinf(lg).any(-1) \
            | ~jnp.isfinite(lg).any(-1)
        clean = jnp.where(jnp.isnan(logits), -jnp.inf,
                          logits.astype(jnp.float32))
        greedy = jnp.argmax(clean, axis=-1).astype(jnp.int32)
        safe = jnp.where(bad[..., None], 0.0, lg)
        drawn = jax.random.categorical(key, safe, axis=-1).astype(jnp.int32)
        return jnp.where(bad, greedy, drawn)

    return draw


def _next_fn(sampler):
    """(logits, key) -> (token, key): greedy argmax, or one split + draw
    per step — the identical split sequence in the fixed-length scan and
    the EOS while_loop keeps the two drivers' draws bit-identical."""
    if sampler is None:
        return lambda logits, key: (
            jnp.argmax(logits, axis=-1).astype(jnp.int32), key)

    def nxt(logits, key):
        key, sub = jax.random.split(key)
        return sampler(sub, logits), key

    return nxt


_SPEC_L = {"dscim1": 256, "dscim2": 64}   # the paper's two operating points


def _parse_spec(spec: str | None):
    """Self-speculative decoding spec: '<variant>:<k>' (e.g. 'dscim2:4')
    -> (draft_variant, k).  k = 0 (or None/'') disables speculation —
    the builders fall through to the plain drivers, so 'dscim2:0' is the
    plain path, not a degenerate window."""
    if not spec:
        return None
    parts = spec.split(":")
    if len(parts) != 2 or parts[0] not in _SPEC_L:
        raise ValueError(f"bad spec {spec!r}; want 'dscim1:<k>' or "
                         "'dscim2:<k>', e.g. 'dscim2:4'")
    try:
        k = int(parts[1])
    except ValueError:
        raise ValueError(f"bad spec {spec!r}: draft depth {parts[1]!r} is "
                         "not an int") from None
    if k < 0:
        raise ValueError(f"spec draft depth must be >= 0, got {k}")
    return (parts[0], k) if k else None


def _draft_cfg(cfg: ArchConfig, variant: str) -> ArchConfig:
    """The drafter's config: same weights and architecture, the cheaper
    stochastic estimator.  Rewrites the serving dscim spec's variant and
    sample length (dscim2 -> L64, dscim1 -> L256), keeping mode[+attn] and
    calibration — the prepared ``QuantizedLinearWeight`` planes are shared
    by every estimator mode, so draft and verify serve the *same* resident
    weights (that is what makes this self-speculation).  'off'/'float'
    serving specs draft through themselves (degenerate self-draft: every
    greedy draft is accepted — useful as a plumbing check)."""
    import dataclasses

    from repro.core.qweights import split_dscim_mode
    spec = getattr(cfg, "dscim", "off")
    if spec == "off" or split_dscim_mode(spec)[0] in ("off", "float"):
        return cfg
    parts = spec.split(":")
    parts[1] = variant
    parts[2] = str(_SPEC_L[variant])
    return dataclasses.replace(cfg, dscim=":".join(parts))


def _check_spec(model, cfg: ArchConfig):
    if not hasattr(model, "decode_multi"):
        raise ValueError("speculative decoding needs a model family with a "
                         f"batched verify forward, not {cfg.family!r}")
    if cfg.stub_frontend:
        raise ValueError("speculative decoding needs token inputs; "
                         "stub-frontend configs are unsupported")


def _make_spec_window(model, cfg: ArchConfig, cfg_draft: ArchConfig, par,
                      nxt, k: int, eos: int, pad_id: int, pin: dict):
    """One self-speculative draft/verify window, fully device-resident.

    Drafts ``k`` tokens with the cheap estimator (greedy argmax — drafting
    consumes no RNG; only emissions draw, keeping the carried key chain
    aligned with the non-spec drivers), verifies the k+1-token window with
    one batched forward through the serving estimator
    (``models.lm.decode_multi``), then folds the standard accept rule over
    the window inside a ``lax.scan``: position t emits the token the
    *verifier* decides (argmax, or one RNG draw per emitting position), and
    the window continues past t only while the draft at t+1 equals the
    emitted token.  Greedy emission is therefore bitwise what target-only
    serving would emit; every window emits at least one token per live row
    (progress is unconditional), and position k's emission is the standard
    bonus token.

    Draft decodes write provisional KV at the window positions; the verify
    forward rewinds to the window start and overwrites every one of those
    writes before reading it, so the verifier sees a cache bitwise equal to
    non-spec serving — and ``kvcache.spec_rollback`` truncates back to the
    last accepted position after the fold.  Pages are never allocated or
    freed in here: callers size every slot's grant with +k headroom.

    Returns ``(tok', done', n_out', cache', key', em (B, k+1) int32,
    vm (B, k+1) bool, bad (B, k+1) bool, logits0 (B, Vp) f32)`` — ``em``
    holds the emitted token where ``vm`` is set (pad elsewhere), ``bad``
    flags emitted-from non-finite verifier logits, ``logits0`` is the
    verify logits at window position 0 (the accuracy-watchdog probe plane:
    same (token, cache) inputs the exact-mode probe decodes).
    """
    from repro.core import kvcache

    def window(params, tok, done, n_out, budget, cache, key):
        B = tok.shape[0]
        pos0 = cache["pos"]
        paged = "k_pages" in cache
        tails0 = (cache["k_tail"], cache["v_tail"]) if paged else None

        def dstep(carry, _):
            dtok, dcache = carry
            dlogits, dcache = model.decode(
                params, cfg_draft, {"token": dtok, "done": done, **pin},
                dcache, par)
            nd = jnp.argmax(dlogits, axis=-1).astype(jnp.int32)
            return (nd, dcache), nd

        (_, dcache), drafts = jax.lax.scan(dstep, (tok, cache), None,
                                           length=k)
        drafts = jnp.moveaxis(drafts, 0, 1)                    # (B, k)

        window_toks = jnp.concatenate([tok[:, None], drafts], axis=1)
        # rewind pos: the verify pass overwrites every draft write before
        # reading it.  Paged caches also restore the pre-window tail: a
        # draft that crossed a page boundary wrapped the tail buffer and
        # clobbered committed entries below pos0 % ps, which verify reads
        # for the window's first page (it only rewrites offsets >= pos0 %
        # ps); every later page starts at offset 0 and needs no restore.
        vcache = dict(dcache, pos=pos0)
        if paged:
            vcache["k_tail"], vcache["v_tail"] = tails0
        vlogits, vcache, win_kv = model.decode_multi(
            params, cfg, {"tokens": window_toks, "done": done, **pin},
            vcache, par)

        # the draft after each position (what must match to keep going);
        # -1 after the bonus position — never equal to a real token
        d_next = jnp.concatenate(
            [drafts, jnp.full((B, 1), -1, jnp.int32)], axis=1)

        def astep(carry, xs):
            acc, dn, nout, kkey, last = carry
            lg, dnx = xs
            cand, k2 = nxt(lg, kkey)
            emit = acc
            # consume the split only if some row emitted at this position
            # (greedy nxt returns the key untouched, so this is a no-op
            # there); in the lockstep case this is exactly one split per
            # emitted token — the non-spec chain
            any_e = jnp.any(emit)
            kkey = jax.tree.map(lambda n, o: jnp.where(any_e, n, o),
                                k2, kkey)
            tok_t = jnp.where(emit, cand, pad_id)
            nout2 = nout + jnp.where(emit, 1, 0)
            stop = (tok_t == eos) | (nout2 >= budget)
            dn2 = dn | (emit & stop)
            acc2 = emit & ~stop & (dnx >= 0) & (cand == dnx)
            last2 = jnp.where(emit, cand, last)
            return (acc2, dn2, nout2, kkey, last2), (tok_t, emit)

        (_, done2, n_out2, key2, tok2), (em, vm) = jax.lax.scan(
            astep, (~done, done, n_out, key, tok),
            (jnp.moveaxis(vlogits, 1, 0), jnp.moveaxis(d_next, 1, 0)))
        em = jnp.moveaxis(em, 0, 1)                            # (B, k+1)
        vm = jnp.moveaxis(vm, 0, 1)
        bad = vm & ~jnp.isfinite(vlogits).all(axis=-1)

        n_emit = jnp.sum(vm, axis=1).astype(jnp.int32)
        cache2 = kvcache.spec_rollback(vcache, pos0, pos0 + n_emit,
                                       tails0, win_kv)
        logits0 = vlogits[:, 0].astype(jnp.float32)
        return tok2, done2, n_out2, cache2, key2, em, vm, bad, logits0

    return window


def _check_kv(cfg: ArchConfig, kv: str):
    if kv not in ("float", "int8"):
        raise ValueError(f"kv must be 'float' or 'int8', got {kv!r}")
    if kv == "int8" and cfg.family not in ("dense", "moe"):
        raise ValueError("the paged int8 KV cache needs an attention-"
                         f"family model, not {cfg.family!r}")


def _paged_kernel_flag(paged_attn: str):
    """'auto' | 'kernel' | 'jnp' -> the static read-path bool the decode
    batch carries (None = follow cfg.dscim / REPRO_PAGED_ATTN, see
    layers/attention.py).  An explicit choice is part of every jitted
    builder's lru_cache key, so A/B-ing the two paths can never hand back
    a stale executable traced for the other one."""
    try:
        return {"auto": None, "kernel": True, "jnp": False}[paged_attn]
    except KeyError:
        raise ValueError(f"paged_attn must be 'auto', 'kernel' or 'jnp', "
                         f"got {paged_attn!r}") from None


@functools.lru_cache(maxsize=16)
def make_generate_fn(cfg: ArchConfig, par: ParallelCtx | None = None,
                     n_tokens: int = 16, *, trace_logits: bool = False,
                     jit: bool = True, eos_id: int | None = None,
                     sample: str = "greedy", pad_id: int = 0,
                     kv: str = "float", page_size: int = 8,
                     paged_attn: str = "auto", spec: str | None = None):
    """Device-resident generation: prefill + up to (n_tokens-1) decode
    steps inside a single jit.

    The host dispatches exactly once per request; the KV cache lives in the
    loop carry (XLA reuses its buffers in place — no per-token host round
    trip, no per-token cache copy), and the generated tokens accumulate on
    device.  ``generate(params, batch)`` with ``batch = {"tokens": (B, S)
    int32}`` returns ``(tokens (B, n_tokens) int32, logits)`` where
    ``logits`` is the prefill last-token logits by default — the per-token
    logit trace is off the hot path and only materialized (stacked,
    (n_tokens, B, Vp)) under ``trace_logits=True`` (fixed-length scan only).

    ``eos_id``: switch the fixed-length ``lax.scan`` to a ``lax.while_loop``
    that exits as soon as every slot has emitted ``eos_id`` (and/or reached
    its optional per-slot ``batch["max_new"]`` (B,) int32 budget, counted
    including the prefill token).  Finished slots are done-masked: their
    cache position stops advancing and their remaining tokens are pinned
    to ``pad_id`` — ragged completion with no dead-token decode work once
    the whole batch is finished.

    ``sample``: 'greedy' (default, bit-compatible with the PR 3 scan) or
    'temp:<t>' / 'topk:<k>[:<t>]' / 'topp:<p>[:<t>]' — the RNG key
    (``batch["rng"]``, a PRNGKey) rides the loop carry with one split per
    step.

    ``kv``: 'float' serves from the dense fixed-capacity cache; 'int8'
    from the block-paged per-head-quantized KV cache (core/kvcache.py,
    ~4x fewer resident decode cache bytes, dequant fused into the paged
    flash attention inner loop).  ``paged_attn``: the int8 read path —
    'kernel' (fused Pallas paged attention) or 'jnp' (gather reference)
    pin it and key this builder's cache; 'auto' (default) follows
    cfg.dscim ('kernel' modes -> kernel) with the trace-time
    ``REPRO_PAGED_ATTN`` env override.

    Under a mesh (``par`` given) the whole loop runs inside the one jit
    with the params' committed shardings — prepared DS-CIM weights route
    through the model-axis sharded fused MVM (core/dscim_layer.py) with no
    per-token host sync.  The builder is cached, so repeated ``serve_batch``
    calls with the same options reuse the compiled executable.

    ``spec``: ``'<variant>:<k>'`` (e.g. ``'dscim2:4'``) turns on
    self-speculative decoding — draft k tokens per window with the cheaper
    estimator (``_draft_cfg``), verify them in one batched forward through
    the serving estimator, accept by the standard rule
    (``_make_spec_window``).  The driver becomes a window-granular
    ``lax.while_loop`` (accept/reject never round-trips to the host);
    greedy emission is bitwise-identical to the non-spec drivers, sampled
    emission replays the carried key chain.  The KV allocation gains +k
    headroom for in-flight draft positions.  Returns a third element,
    ``{"windows": (B,), "emitted": (B,)}`` — per-row verify-window
    participation and emitted-token counts, the
    accepted-tokens-per-verify numerator/denominator serve_bench reports.
    """
    model = get_model(cfg)
    nxt = _next_fn(_make_sampler(sample))
    _check_kv(cfg, kv)
    sp = _parse_spec(spec)
    k_spec = sp[1] if sp else 0
    if sp:
        _check_spec(model, cfg)
    pk = _paged_kernel_flag(paged_attn)
    # static read-path pin, merged into the decode batches built inside
    # the jitted loop (absent under 'auto' — plain python values in a
    # dict literal constructed during tracing, never traced operands)
    pin = {} if pk is None else {"paged_kernel": pk}
    if trace_logits and eos_id is not None:
        raise ValueError("trace_logits is a fixed-length-scan feature; the "
                         "EOS early-exit variant keeps logits off the path")
    if trace_logits and sp:
        raise ValueError("trace_logits is a fixed-length-scan feature; "
                         "speculative windows keep logits off the path")

    def _prefill(params, batch):
        B, S = batch["tokens"].shape
        if kv == "float":
            return model.prefill(params, cfg, {"tokens": batch["tokens"]},
                                 par, capacity=S + n_tokens + k_spec)
        from repro.core.kvcache import n_pages_for, paged_from_dense
        logits0, dense = model.prefill(params, cfg,
                                       {"tokens": batch["tokens"]}, par)
        mp = n_pages_for(S + n_tokens + k_spec, page_size)
        return logits0, paged_from_dense(dense["k"], dense["v"], page_size,
                                         n_pages=B * mp, max_pages=mp)

    def generate(params, batch):
        B = batch["tokens"].shape[0]
        logits0, cache = _prefill(params, batch)
        key = batch.get("rng", jax.random.PRNGKey(0))
        tok0, key = nxt(logits0, key)

        if sp is not None:
            # self-speculative window driver: while_loop over draft/verify
            # windows, per-row output cursors (rows desync by acceptance)
            variant, kd = sp
            window = _make_spec_window(model, cfg, _draft_cfg(cfg, variant),
                                       par, nxt, kd,
                                       -1 if eos_id is None else eos_id,
                                       pad_id, pin)
            budget = jnp.full((B,), n_tokens, jnp.int32)
            if "max_new" in batch:
                budget = jnp.minimum(budget, batch["max_new"])
            done0 = (tok0 == (-1 if eos_id is None else eos_id)) \
                | (budget <= 1)
            if kv == "float":      # per-row positions from the first window
                cache = dict(cache,
                             pos=jnp.full((B,), cache["pos"], jnp.int32))
            toks0 = jnp.full((B, n_tokens), pad_id,
                             jnp.int32).at[:, 0].set(tok0)
            cnt0 = jnp.ones((B,), jnp.int32)          # emitted (incl. tok0)
            wn0 = jnp.zeros((B,), jnp.int32)          # windows participated

            def cond(c):
                w, _, done = c[0], c[1], c[2]
                return (w < n_tokens) & ~jnp.all(done)

            def body(c):
                w, tok, done, toks, cnt, wn, cache, key = c
                wn = wn + jnp.where(done, 0, 1)
                tok, ndone, cnt2, cache, key, em, vm, _, _ = window(
                    params, tok, done, cnt, budget, cache, key)
                rows = jnp.arange(B)[:, None]
                idx = cnt[:, None] + jnp.arange(kd + 1,
                                                dtype=jnp.int32)[None, :]
                idx = jnp.where(vm, idx, n_tokens)    # drop non-emissions
                toks = toks.at[rows, idx].set(em, mode="drop")
                return w + 1, tok, ndone, toks, cnt2, wn, cache, key

            _, _, _, toks, cnt, wn, _, _ = jax.lax.while_loop(
                cond, body,
                (jnp.int32(1), tok0, done0, toks0, cnt0, wn0, cache, key))
            return toks, logits0, {"windows": wn, "emitted": cnt}

        if eos_id is None:
            # fixed-length scan (the PR 3 path)
            def step(carry, _):
                tok, cache, key = carry
                logits, cache = model.decode(params, cfg,
                                             {"token": tok, **pin},
                                             cache, par)
                tok, key = nxt(logits, key)
                return (tok, cache, key), ((tok, logits) if trace_logits
                                           else tok)

            (_, cache, _), ys = jax.lax.scan(step, (tok0, cache, key), None,
                                             length=n_tokens - 1)
            toks = ys[0] if trace_logits else ys
            tokens = jnp.concatenate(
                [tok0[:, None], jnp.moveaxis(toks, 0, 1)], axis=1)
            if trace_logits:
                return tokens, jnp.concatenate([logits0[None], ys[1]],
                                               axis=0)
            return tokens, logits0

        # EOS early-exit while_loop: stop the moment the whole batch is
        # done; per-slot done-masking gives ragged completion inside it
        done0 = tok0 == eos_id
        if "max_new" in batch:
            done0 = done0 | (batch["max_new"] <= 1)
        if kv == "float":          # ragged completion needs per-slot pos
            cache = dict(cache,
                         pos=jnp.full((B,), cache["pos"], jnp.int32))
        toks0 = jnp.full((B, n_tokens), pad_id, jnp.int32).at[:, 0].set(tok0)

        def cond(c):
            i, _, done, _, _, _ = c
            return (i < n_tokens) & ~jnp.all(done)

        def body(c):
            i, tok, done, toks, cache, key = c
            logits, cache = model.decode(
                params, cfg, {"token": tok, "done": done, **pin}, cache,
                par)
            new, key = nxt(logits, key)
            new = jnp.where(done, pad_id, new)
            ndone = done | (new == eos_id)
            if "max_new" in batch:
                ndone = ndone | (i + 1 >= batch["max_new"])
            toks = jax.lax.dynamic_update_slice(toks, new[:, None], (0, i))
            return i + 1, new, ndone, toks, cache, key

        _, _, _, toks, _, _ = jax.lax.while_loop(
            cond, body, (jnp.int32(1), tok0, done0, toks0, cache, key))
        return toks, logits0

    return jax.jit(generate) if jit else generate


# ---------------------------------------------------------------------------
# continuous batching: jitted admit / segment halves of the scheduler
# ---------------------------------------------------------------------------

def init_serve_state(cfg: ArchConfig, slots: int, capacity: int, *,
                     kv: str = "float", page_size: int = 8,
                     n_pages: int | None = None, seed: int = 0,
                     integrity: bool = False):
    """Idle scheduler state: every slot free (done), empty KV cache of the
    requested layout, shared PRNG key.  ``capacity`` is the per-slot token
    budget (prompt + generated); for ``kv='int8'`` the page pool defaults
    to slots x pages-per-sequence but can be sized independently
    (``n_pages``) — capacity is a pool knob, not slots x max_len.

    ``integrity=True`` (int8 only) adds the per-page checksum plane to the
    cache; every jitted builder branches on the cache *structure* at trace
    time, so the flag changes no builder cache keys."""
    _check_kv(cfg, kv)
    B = slots
    if kv == "float":
        if integrity:
            raise ValueError("integrity checksums need the int8 paged "
                             "cache (kv='int8'); the float dense cache "
                             "is rewritten in place every step")
        cdt = jnp.dtype(cfg.cache_dtype)
        cache = {"k": jnp.zeros((cfg.n_layers, B, capacity, cfg.n_kv,
                                 cfg.head_dim), cdt),
                 "v": jnp.zeros((cfg.n_layers, B, capacity, cfg.n_kv,
                                 cfg.head_dim), cdt),
                 "pos": jnp.zeros((B,), jnp.int32)}
    else:
        from repro.core.kvcache import init_paged_cache, n_pages_for
        mp = n_pages_for(capacity, page_size)
        cache = init_paged_cache(cfg.n_layers, B,
                                 B * mp if n_pages is None else n_pages,
                                 page_size, mp, cfg.n_kv, cfg.head_dim,
                                 integrity=integrity)
    return {"tok": jnp.zeros((B,), jnp.int32),
            "done": jnp.ones((B,), bool),
            "n_out": jnp.zeros((B,), jnp.int32),
            "max_new": jnp.ones((B,), jnp.int32),
            "cache": cache,
            "rng": jax.random.PRNGKey(seed)}


@functools.lru_cache(maxsize=16)
def make_admit_fn(cfg: ArchConfig, par: ParallelCtx | None = None, *,
                  eos_id: int | None = None, sample: str = "greedy",
                  jit: bool = True):
    """One jitted request admission: prefill a (1, S) prompt, write its KV
    into free slot ``slot`` of the live cache (dense row overwrite, or
    host-allocated physical pages for the paged layout — the cache layout
    is picked up from the state structure), seed the slot's first token /
    budget / done flag.  Runs between segments; carries persist."""
    model = get_model(cfg)
    nxt = _next_fn(_make_sampler(sample))
    eos = -1 if eos_id is None else eos_id

    def admit(params, state, prompt, slot, page_ids, max_new):
        from repro.core import kvcache
        logits0, dense = model.prefill(params, cfg, {"tokens": prompt}, par)
        tok0, key = nxt(logits0, state["rng"])
        tok0 = tok0[0]
        cache = state["cache"]
        if "k_pages" in cache:
            cache = kvcache.admit_request(cache, dense["k"], dense["v"],
                                          slot, page_ids)
        else:
            cache = kvcache.admit_dense(cache, dense["k"], dense["v"], slot)
        done0 = (tok0 == eos) | (max_new <= 1)
        return dict(state,
                    tok=state["tok"].at[slot].set(tok0),
                    done=state["done"].at[slot].set(done0),
                    n_out=state["n_out"].at[slot].set(1),
                    max_new=state["max_new"].at[slot].set(max_new),
                    cache=cache, rng=key), tok0

    # the state (KV cache included) is donated: admissions between
    # segments update the pool in place instead of copying it
    return jax.jit(admit, donate_argnums=(1,)) if jit else admit


@functools.lru_cache(maxsize=16)
def make_segment_fn(cfg: ArchConfig, par: ParallelCtx | None = None,
                    seg_len: int = 4, *, eos_id: int | None = None,
                    sample: str = "greedy", pad_id: int = 0,
                    jit: bool = True, paged_attn: str = "auto",
                    spec: str | None = None):
    """One jitted continuous-batching segment: a fixed-size ``lax.scan`` of
    ``seg_len`` done-masked decode steps over the whole slot batch.  Slots
    finish on EOS or their per-slot budget and stop advancing their cache
    position; the scheduler admits new requests into freed slots *between*
    segments.  Returns (state', toks (seg_len, B) int32, live (seg_len, B)
    bool, aux) where ``live[s, b]`` marks that slot b did useful work at
    step s — the occupancy/live-tok-s accounting the serve report uses.

    ``aux`` carries the fault-tolerant scheduler's monitoring planes
    (runtime/serving.py), computed inside the same scan so the hot path
    gains no extra dispatches: ``aux["bad"]`` (seg_len, B) bool flags
    steps whose logits went NaN/Inf (corrupted KV pages, a poisoned
    estimator), and ``aux["logits0"]`` (B, Vp) f32 is the *first* step's
    logits — the serving side of the accuracy-watchdog probe, which
    decodes the same (token, cache) inputs through the exact reference
    (``make_probe_fn``) and compares.  Both stay as unfetched device
    buffers unless the scheduler is monitoring.

    ``spec`` ('<variant>:<k>'): each of the ``seg_len`` scan steps becomes
    a self-speculative draft/verify *window* (``_make_spec_window``) —
    still one host dispatch per segment; accept/reject lives in the scan
    carry.  Outputs stay step-shaped: toks/live/``aux["bad"]`` come back
    as (seg_len * (k+1), B) with window emissions laid out chronologically
    and non-emitted positions dead (``live`` False, token ``pad_id``) —
    the host harvest loop is unchanged, it just sees more rows, and the
    deadline ledger counts all seg_len * (k+1) attempted verifier
    positions.  ``aux["logits0"]`` stays the segment's first (token,
    cache) decode — under spec that is the first window's verify logits
    at position 0, i.e. still the *verifier* estimator on exactly the
    inputs the exact-mode probe decodes."""
    model = get_model(cfg)
    nxt = _next_fn(_make_sampler(sample))
    eos = -1 if eos_id is None else eos_id
    pin = {} if _paged_kernel_flag(paged_attn) is None \
        else {"paged_kernel": _paged_kernel_flag(paged_attn)}
    sp = _parse_spec(spec)
    if sp:
        _check_spec(model, cfg)
        variant, kd = sp
        window = _make_spec_window(model, cfg, _draft_cfg(cfg, variant),
                                   par, nxt, kd, eos, pad_id, pin)

        def segment(params, state):
            def step(carry, _):
                tok, done, n_out, max_new, cache, key, i, lg0 = carry
                tok, done, n_out, cache, key, em, vm, bad, l0 = window(
                    params, tok, done, n_out, max_new, cache, key)
                lg0 = jnp.where(i == 0, l0, lg0)
                return (tok, done, n_out, max_new, cache, key, i + 1,
                        lg0), (em, vm, bad)

            B = state["tok"].shape[0]
            lg0_init = jnp.zeros((B, cfg.vocab_padded), jnp.float32)
            carry = (state["tok"], state["done"], state["n_out"],
                     state["max_new"], state["cache"], state["rng"],
                     jnp.int32(0), lg0_init)
            (tok, done, n_out, max_new, cache, key, _, lg0), \
                (ems, vms, bads) = \
                jax.lax.scan(step, carry, None, length=seg_len)
            if "page_sum" in state["cache"]:        # trace-time structure
                from repro.core.kvcache import refresh_page_checksums
                # draft windows flush up to k positions past the committed
                # pos; every such page is re-digested from live content
                cache = refresh_page_checksums(
                    cache, state["cache"]["pos"], cache["pos"] + kd,
                    seg_len * (kd + 1) + kd)

            def rows(x):     # (seg_len, B, k+1) -> (seg_len * (k+1), B)
                return jnp.moveaxis(x, 2, 1).reshape(seg_len * (kd + 1), B)

            return dict(state, tok=tok, done=done, n_out=n_out,
                        max_new=max_new, cache=cache, rng=key), \
                rows(ems), rows(vms), {"bad": rows(bads), "logits0": lg0}

        return jax.jit(segment, donate_argnums=(1,)) if jit else segment

    def segment(params, state):
        def step(carry, _):
            tok, done, n_out, max_new, cache, key, i, lg0 = carry
            live = ~done
            logits, cache = model.decode(
                params, cfg, {"token": tok, "done": done, **pin}, cache,
                par)
            lg0 = jnp.where(i == 0, logits.astype(jnp.float32), lg0)
            bad = live & ~jnp.isfinite(logits).all(axis=-1)
            new, key = nxt(logits, key)
            new = jnp.where(done, pad_id, new)
            n_out = n_out + jnp.where(done, 0, 1)
            ndone = done | (new == eos) | (n_out >= max_new)
            return (new, ndone, n_out, max_new, cache, key, i + 1, lg0), \
                (new, live, bad)

        B = state["tok"].shape[0]
        lg0_init = jnp.zeros((B, cfg.vocab_padded), jnp.float32)
        carry = (state["tok"], state["done"], state["n_out"],
                 state["max_new"], state["cache"], state["rng"],
                 jnp.int32(0), lg0_init)
        (tok, done, n_out, max_new, cache, key, _, lg0), \
            (toks, lives, bads) = \
            jax.lax.scan(step, carry, None, length=seg_len)
        if "page_sum" in state["cache"]:            # trace-time structure
            from repro.core.kvcache import refresh_page_checksums
            cache = refresh_page_checksums(
                cache, state["cache"]["pos"], cache["pos"], seg_len)
        return dict(state, tok=tok, done=done, n_out=n_out, max_new=max_new,
                    cache=cache, rng=key), toks, lives, \
            {"bad": bads, "logits0": lg0}

    # donate the carried state so each segment reuses the KV cache
    # buffers in place (the host loop's donate_argnums=(2,) analogue)
    return jax.jit(segment, donate_argnums=(1,)) if jit else segment


@functools.lru_cache(maxsize=16)
def make_extend_fn(cfg: ArchConfig, par: ParallelCtx | None = None,
                   chunk_len: int = 16, *, eos_id: int | None = None,
                   sample: str = "greedy", paged_attn: str = "auto",
                   trace_logits: bool = False, jit: bool = True):
    """One jitted chunked-prefill step for the serving router
    (runtime/router.py): feed ``chunk_len`` prompt tokens of ONE slot
    through the batched verify forward (``models.lm.decode_multi``) while
    every other slot is done-masked (frozen position, writes suppressed —
    layers/attention.py), then roll the window back to the chunk's real
    length with the speculative write-then-rollback discipline
    (``core/kvcache.spec_rollback``).  A prompt of arbitrary length S is
    admitted as ceil(S / chunk_len) extend calls against ONE compiled
    program — between calls the router keeps serving decode segments, so
    a long admission never stalls live slots.

    Position semantics: the slot's KV at positions ``pos .. pos+n_real-1``
    after the call is bitwise what ``n_real`` successive single-token
    teacher-forced ``decode`` steps would have written (the decode_multi
    exact-replay guarantee).  On the kernel read path with
    ``chunk_len > 1`` that holds by construction, not by a per-position
    scan: each layer flushes the pages the chunk fills up front, then one
    launch of the paged kernel's chunk entry reads, for each position,
    the pool pages before its own and the bf16 copy of its own page
    (layers/attention.py ``decode_attention_paged_multi``); the attention
    output matches the sequential replay to f32 accumulation order.
    ``chunk_len = 1`` and the jnp read path replay it per position.
    Chunked prefill is *sequential-decode*
    equivalent, not bitwise-equal to the batched full-prompt prefill
    (XLA reduces the S-position attention in a different order), which is
    why the router's bucketed one-shot path exists for common lengths.
    The final chunk may be padded up to ``chunk_len``: pad positions sit
    causally after every real one, their KV writes are rolled back, and
    the page a padded flush may have garbage-quantized sits at logical
    index >= the committed position, where the tail overlay masks it
    until a later whole-page flush rewrites it (the spec-window
    argument).

    ``extend(params, state, toks (1, chunk_len) int32, slot, n_real,
    emit, max_new) -> (state', tok0)``: writes the chunk's KV for
    ``slot``; under ``emit`` (the last chunk) also samples the first
    output token from the final real position's logits — one ``nxt``
    call against the carried key, exactly like ``make_admit_fn`` — and
    arms the slot (tok/done/n_out=1/max_new).  Non-emitting calls leave
    the slot done-masked so interleaved segments skip it.  The state is
    donated; the slot's page-table row must already hold its granted
    pages (the router writes it host-side at begin-admit).

    ``trace_logits=True`` compiles a separate program returning a third
    element — the slot's full-chunk logits ((chunk_len, Vp) f32, pad
    positions included) — the prefix-cache bitwise-parity tests compare
    these traces hit-vs-cold; the serving paths never pay for them."""
    from repro.core import kvcache
    model = get_model(cfg)
    _check_spec(model, cfg)
    nxt = _next_fn(_make_sampler(sample))
    eos = -1 if eos_id is None else eos_id
    pk = _paged_kernel_flag(paged_attn)
    pin = {} if pk is None else {"paged_kernel": pk}

    def extend(params, state, toks, slot, n_real, emit, max_new):
        cache = state["cache"]
        B = state["tok"].shape[0]
        rows = jnp.arange(B, dtype=jnp.int32)
        is_t = rows == slot
        tokens = jnp.zeros((B, chunk_len), jnp.int32).at[slot].set(toks[0])
        pos0 = cache["pos"]
        paged = "k_pages" in cache
        tails0 = (cache["k_tail"], cache["v_tail"]) if paged else None
        logits, vcache, win_kv = model.decode_multi(
            params, cfg, {"tokens": tokens, "done": ~is_t, **pin},
            cache, par)
        new_pos = pos0 + jnp.where(is_t, n_real, 0)
        cache2 = kvcache.spec_rollback(vcache, pos0, new_pos, tails0,
                                       win_kv)
        if paged and "page_sum" in cache:
            # a chunk (pad positions included) may have flushed pages up
            # to chunk_len past the slot's entry position — re-digest them
            cache2 = kvcache.refresh_page_checksums(
                cache2, pos0, pos0 + jnp.where(is_t, chunk_len, 0),
                chunk_len)
        # emission: sample the first output token from the last *real*
        # position's logits — the chunked-path analogue of admit's
        # prefill-logits draw; the key is consumed only when emitting
        # (and never under greedy), keeping the carried chain aligned
        lg = jax.lax.dynamic_index_in_dim(logits[slot], n_real - 1,
                                          keepdims=False)
        tok0, key2 = nxt(lg[None], state["rng"])
        tok0 = tok0[0]
        key = jax.tree.map(lambda n, o: jnp.where(emit, n, o),
                           key2, state["rng"])
        done0 = jnp.where(emit, (tok0 == eos) | (max_new <= 1), True)
        old = state["tok"][slot]
        state2 = dict(
            state, cache=cache2,
            tok=state["tok"].at[slot].set(jnp.where(emit, tok0, old)),
            done=state["done"].at[slot].set(done0),
            n_out=state["n_out"].at[slot].set(
                jnp.where(emit, 1, state["n_out"][slot])),
            max_new=state["max_new"].at[slot].set(
                jnp.where(emit, max_new, state["max_new"][slot])),
            rng=key)
        if trace_logits:
            return state2, tok0, logits[slot].astype(jnp.float32)
        return state2, tok0

    return jax.jit(extend, donate_argnums=(1,)) if jit else extend


@functools.lru_cache(maxsize=16)
def make_probe_fn(cfg_ref: ArchConfig, par: ParallelCtx | None = None, *,
                  jit: bool = True):
    """The exact-reference half of the accuracy-watchdog probe: one
    non-donating decode of the serve state's (token, cache) inputs under
    ``cfg_ref`` — normally the serving spec's exact-mode, fault-free
    counterpart (``dscim='exact:...'``, ``dscim_fault=''``).

    The exact backend accepts the same prepared ``QuantizedLinearWeight``
    planes the stochastic serving path uses (core/dscim_layer.py), so the
    probe needs no second parameter copy and isolates exactly the
    estimator's contribution: same int8 weights, same int8 KV cache, same
    token — only the MVM estimator differs.  The scheduler compares the
    returned (B, Vp) logits against the segment's ``aux["logits0"]``
    (same inputs, serving estimator) via ``AccuracyWatchdog.check``.

    The decoded cache is discarded (functional decode — the pool pages
    are never written), so probing does not perturb serving state."""
    model = get_model(cfg_ref)

    def probe(params, state):
        logits, _ = model.decode(
            params, cfg_ref, {"token": state["tok"], "done": state["done"]},
            state["cache"], par)
        return logits

    return jax.jit(probe) if jit else probe
