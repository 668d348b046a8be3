"""Generic decoder-only transformer LM (dense + MoE families).

Covers: olmo-1b, qwen3-0.6b, starcoder2-7b, codeqwen1.5-7b (dense),
deepseek-moe-16b, granite-moe-1b-a400m (moe), musicgen-large, pixtral-12b
(stub-frontend decoder backbones).

Structure: scan-over-stacked-layers with full remat (HLO is O(1) in depth —
this is what keeps the 512-device AOT dry-runs fast), flash-style chunked
attention, functional KV-cache prefill/decode, optional MoE expert
parallelism via shard_map (see layers/moe.py), optional sequence-parallel
residual stream.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core.qweights import QuantizedLinearWeight
from repro.layers.attention import (attention, decode_attention,
                                    decode_attention_multi,
                                    decode_attention_paged,
                                    decode_attention_paged_multi,
                                    init_attention)
from repro.layers.mlp import init_mlp, mlp
from repro.layers.moe import init_moe, moe, moe_local
from repro.layers.norms import init_rmsnorm, layernorm, rmsnorm
from repro.parallel import ParallelCtx

__all__ = ["init_params", "forward", "prefill", "decode", "decode_multi",
           "cache_specs", "lm_loss"]


def _parse_dscim(dscim_spec: str):
    """'<mode>[+attn]:<variant>:<L>[:calib]' -> (mode, attn, variant, L,
    calib).  The '+attn' suffix opt-ins the attention projections (default
    scope is MLP matmuls + LM head, DESIGN.md §6)."""
    from repro.core.qweights import split_dscim_mode
    parts = dscim_spec.split(":")
    if len(parts) < 3:
        raise ValueError(f"bad dscim spec {dscim_spec!r}; want "
                         "'<mode>[+attn]:<variant>:<L>[:calib]', e.g. "
                         "'kernel:dscim1:256' or 'kernel+attn:dscim1:256'")
    mode, attn_suffix = split_dscim_mode(dscim_spec)
    calib = parts[3] if len(parts) > 3 else "paper"
    return mode, attn_suffix, parts[1], int(parts[2]), calib


def _parse_fault(fault: str):
    """'stuck:<stride>:<value>' -> (stride, value): every <stride>-th
    output column of a DS-CIM linear reads back the constant <value> —
    the trace-level model of stuck-at OR-accumulation columns in the CIM
    array (runtime/failover.py injects it via cfg.dscim_fault)."""
    parts = fault.split(":")
    if len(parts) != 3 or parts[0] != "stuck":
        raise ValueError(f"bad dscim_fault {fault!r}; want "
                         "'stuck:<stride>:<value>'")
    stride = int(parts[1])
    if stride < 1:
        raise ValueError(f"dscim_fault stride must be >= 1, got {stride}")
    return stride, float(parts[2])


@functools.lru_cache(maxsize=16)
def _linear_for(dscim_spec: str, par: ParallelCtx | None = None,
                fault: str = ""):
    """DS-CIM linear operator for cfg.dscim (see ``_parse_dscim``).

    Applied to the MLP matmuls, the MoE shared expert and the LM head (the
    dominant MVMs).  Returns None when 'off'.

    ``par``: under a mesh, the 'kernel' mode operator carries the mesh so
    prepared weights route through the sharded fused MVM
    (kernels/dscim_fused.py ``dscim_fused_mvm_sharded`` — a Pallas call
    must run inside shard_map on a multi-device mesh; N shards over the TP
    axis, the request batch over the DP axes, and the windows-stay-local
    decomposition is bit-identical to single-device).  The pure-jnp
    backends partition fine under GSPMD and ignore the mesh.

    ``fault`` (cfg.dscim_fault): 'stuck:<stride>:<value>' wraps the
    operator so every <stride>-th output column is stuck at <value> —
    the chaos-testing model of a hard macro fault.  The params are never
    touched, so an exact-mode probe on the *same* prepared weights stays a
    clean reference (runtime/serving.py's accuracy watchdog relies on
    this)."""
    if dscim_spec == "off":
        return None
    from repro.core.dscim_layer import make_linear
    mode, _, variant, length, calib = _parse_dscim(dscim_spec)
    mesh = par.mesh if (par is not None and mode == "kernel") else None
    axis = par.tp_axis if par is not None else "model"
    dp = par.dp_axes if (par is not None and mode == "kernel") else ()
    op = make_linear(variant, length, mode, calib, mesh=mesh,
                     shard_axis=axis, batch_axes=dp)
    if not fault:
        return op
    stride, value = _parse_fault(fault)

    def faulted(x, w, key=None, *, salt=None):
        y = op(x, w, key, salt=salt)
        stuck = (jnp.arange(y.shape[-1]) % stride) == 0
        return jnp.where(stuck, jnp.asarray(value, y.dtype), y)

    faulted.group_k = op.group_k   # prepare_serving_params reads this
    return faulted


@functools.lru_cache(maxsize=16)
def _attn_linear_for(dscim_spec: str, par: ParallelCtx | None = None,
                     fault: str = ""):
    """The attention-projection DS-CIM operator — non-None only for
    '<mode>+attn' specs."""
    if dscim_spec == "off" or not _parse_dscim(dscim_spec)[1]:
        return None
    return _linear_for(dscim_spec, par, fault)


def _norm(cfg: ArchConfig, x, params):
    if cfg.norm == "rmsnorm":
        return rmsnorm(x, params)
    return layernorm(x, params)  # layernorm / layernorm_np


def _init_norm(cfg: ArchConfig, dim):
    if cfg.norm == "layernorm_np":
        return {}
    return init_rmsnorm(dim, parametric=True)


def _init_block(cfg: ArchConfig, key):
    ka, km = jax.random.split(key)
    p = {
        "ln1": _init_norm(cfg, cfg.d_model),
        "ln2": _init_norm(cfg, cfg.d_model),
        "attn": init_attention(ka, cfg.d_model, cfg.n_heads, cfg.n_kv,
                               cfg.head_dim, cfg.qk_norm,
                               pad_to=cfg.head_pad_to),
    }
    if cfg.family == "moe":
        p["moe"] = init_moe(km, cfg.d_model, cfg.d_ff, cfg.moe_experts,
                            cfg.moe_topk, cfg.moe_shared)
    else:
        p["mlp"] = init_mlp(km, cfg.d_model, cfg.d_ff, cfg.mlp_kind)
    return p


def init_params(cfg: ArchConfig, key):
    ke, kl, kh = jax.random.split(key, 3)
    layer_keys = jax.random.split(kl, cfg.n_layers)
    layers = jax.vmap(lambda k: _init_block(cfg, k))(layer_keys)
    params = {"layers": layers, "final_norm": _init_norm(cfg, cfg.d_model)}
    if not cfg.stub_frontend:
        params["embed"] = jax.random.normal(
            ke, (cfg.vocab_padded, cfg.d_model), jnp.float32) * 0.02
    if not cfg.tie_embeddings or cfg.stub_frontend:
        params["lm_head"] = jax.random.normal(
            kh, (cfg.d_model, cfg.vocab_padded), jnp.float32) \
            * cfg.d_model ** -0.5
    return params


# ---------------------------------------------------------------------------
# MoE dispatch: shard_map under a mesh, local fallback otherwise
# ---------------------------------------------------------------------------

def _moe_apply(lp_moe, h, cfg: ArchConfig, par: ParallelCtx | None,
               salt=None):
    if par is None:
        out, aux = moe_local(lp_moe, h, top_k=cfg.moe_topk,
                             capacity_factor=cfg.moe_capacity,
                             has_shared=cfg.moe_shared > 0,
                             linear=_linear_for(
                                 cfg.dscim, fault=cfg.dscim_fault),
                             salt=salt)
        return out, aux
    # Shared expert under the mesh: a prepared (resident int8) shared expert
    # replicates across the mesh (launch/sharding.py keeps its planes
    # unsharded) and the shard_map body computes it locally — bit-identical
    # per token to single-device serving, no FSDP gather of int8 planes.
    # Float shared weights keep the FSDP-shard + gather path; either way the
    # gathered/replicated weights feed the same DS-CIM linear as the local
    # path (the operator must be the *local* one — no nested shard_map).
    shared_prepared = bool(cfg.moe_shared) and isinstance(
        lp_moe.get("shared", {}).get("w_gate"), QuantizedLinearWeight)
    fsdp = par.dp_axes[-1]
    tp = par.tp_axis
    dp = par.dp_axes
    especs = {"w_gate": P(tp, None, fsdp), "w_up": P(tp, None, fsdp),
              "w_down": P(tp, fsdp, None)}
    pspecs = {"router": P(None, None), "experts": especs}
    if cfg.moe_shared:
        if shared_prepared:
            from repro.core.qweights import qweight_replicated_specs
            pspecs["shared"] = {k: qweight_replicated_specs(v)
                                for k, v in lp_moe["shared"].items()}
        else:
            pspecs["shared"] = {"w_gate": P(None, fsdp),
                                "w_up": P(None, fsdp),
                                "w_down": P(fsdp, None)}

    def inner(lp, x, *s):
        # FSDP: gather the weight shards before use (explicit ZeRO-3)
        e = lp["experts"]
        e = {"w_gate": jax.lax.all_gather(e["w_gate"], fsdp, axis=2, tiled=True),
             "w_up": jax.lax.all_gather(e["w_up"], fsdp, axis=2, tiled=True),
             "w_down": jax.lax.all_gather(e["w_down"], fsdp, axis=1, tiled=True)}
        lp2 = dict(lp, experts=e)
        if cfg.moe_shared and not shared_prepared:
            sh = lp["shared"]
            lp2["shared"] = {
                "w_gate": jax.lax.all_gather(sh["w_gate"], fsdp, axis=1, tiled=True),
                "w_up": jax.lax.all_gather(sh["w_up"], fsdp, axis=1, tiled=True),
                "w_down": jax.lax.all_gather(sh["w_down"], fsdp, axis=0, tiled=True)}
        out, aux = moe(lp2, x, top_k=cfg.moe_topk, ep_axis=tp,
                       capacity_factor=cfg.moe_capacity,
                       has_shared=cfg.moe_shared > 0,
                       linear=_linear_for(cfg.dscim, fault=cfg.dscim_fault),
                       salt=s[0] if s else None)
        return out, jax.lax.pmean(aux, (*dp, tp))

    # the (possibly traced) salt rides as an explicit replicated operand —
    # shard_map bodies must not close over tracers
    operands = (lp_moe, h)
    in_specs = (pspecs, P(dp, None, None))
    if salt is not None:
        operands += (jnp.asarray(salt, jnp.int32),)
        in_specs += (P(),)
    return jax.shard_map(
        inner, mesh=par.mesh, in_specs=in_specs,
        out_specs=(P(dp, None, None), P()), check_vma=False,
    )(*operands)


# ---------------------------------------------------------------------------
# forward / prefill / decode
# ---------------------------------------------------------------------------

def _cast(tree, dtype):
    """Cast f32 leaves to the compute dtype.  Prepared weights pass through
    untouched — their int8 planes are the compute representation and their
    dequant scales must stay f32 for bit-exactness vs the float-weight path.
    """
    def f(a):
        if isinstance(a, QuantizedLinearWeight):
            return a
        return a.astype(dtype) if a.dtype == jnp.float32 else a
    return jax.tree.map(f, tree,
                        is_leaf=lambda a: isinstance(a, QuantizedLinearWeight))


def _constraint(x, cfg, par: ParallelCtx | None):
    if par is None:
        return x
    spec = (P(par.dp_axes, par.tp_axis, None) if par.sp
            else P(par.dp_axes, None, None))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(par.mesh, spec))


def _embed_in(params, cfg: ArchConfig, batch, dt):
    if cfg.stub_frontend:
        x = batch["embeds"].astype(dt)
    else:
        x = params["embed"].astype(dt)[batch["tokens"]]
    return x


def _head(params, cfg: ArchConfig, x, par: ParallelCtx | None = None):
    lin = _linear_for(cfg.dscim, par, cfg.dscim_fault)
    head = params.get("lm_head")
    if isinstance(head, QuantizedLinearWeight):
        # prepare-once serve path: the head (incl. the tied-embedding head,
        # materialized from embed.T at prepare time) is resident int8
        return lin(x.astype(jnp.float32), head,
                   salt=8 * cfg.n_layers).astype(jnp.float32)
    if cfg.tie_embeddings and not cfg.stub_frontend:
        w = params["embed"].astype(x.dtype).T
    else:
        w = params["lm_head"].astype(x.dtype)
    if lin is not None:
        return lin(x.astype(jnp.float32), w.astype(jnp.float32),
                   salt=8 * cfg.n_layers).astype(jnp.float32)
    return (x @ w).astype(jnp.float32)


def _block_apply(cfg: ArchConfig, par, lp, x, positions, collect_kv: bool,
                 layer_idx=None):
    # per-layer salt space: mlp/shared-expert sites 0..2, attention 4..7,
    # head 8*n_layers — decorrelates the DS-CIM noise backends' fallback
    # keys across layers and matmul sites (dscim_layer.py docstring)
    salt = None if layer_idx is None else layer_idx * 8
    h_attn, kv = attention(lp["attn"], _norm(cfg, x, lp["ln1"]), cfg,
                           positions, cfg.q_chunk, cfg.kv_chunk,
                           return_kv=collect_kv,
                           linear=_attn_linear_for(cfg.dscim, par,
                                                   cfg.dscim_fault),
                           salt=salt)
    x = x + h_attn
    x = _constraint(x, cfg, par)
    hn = _norm(cfg, x, lp["ln2"])
    if cfg.family == "moe":
        h_ff, aux = _moe_apply(lp["moe"], hn, cfg, par, salt=salt)
    else:
        h_ff, aux = mlp(lp["mlp"], hn, cfg.mlp_kind,
                        linear=_linear_for(cfg.dscim, par,
                                           cfg.dscim_fault),
                        salt=salt), 0.0
    x = _constraint(x + h_ff, cfg, par)
    return x, aux, kv


def forward(params, cfg: ArchConfig, batch, par: ParallelCtx | None = None):
    """Training/scoring forward. Returns (logits f32, aux_loss)."""
    dt = jnp.dtype(cfg.compute_dtype)
    x = _embed_in(params, cfg, batch, dt)
    B, S, _ = x.shape
    positions = jnp.arange(S, dtype=jnp.int32)[None, :]

    def body(carry, xs):
        x, aux = carry
        lp, li = xs
        lp = _cast(lp, dt)
        x, aux_l, _ = _block_apply(cfg, par, lp, x, positions, False,
                                   layer_idx=li)
        return (x, aux + aux_l), None

    if cfg.remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)
    (x, aux), _ = jax.lax.scan(
        body, (x, jnp.float32(0.0)),
        (params["layers"], jnp.arange(cfg.n_layers, dtype=jnp.int32)))
    x = _norm(cfg, x, params["final_norm"])
    return _head(params, cfg, x, par), aux / cfg.n_layers


def prefill(params, cfg: ArchConfig, batch, par: ParallelCtx | None = None,
            capacity: int | None = None):
    """Forward + KV-cache construction. Returns (last-token logits, cache).

    ``capacity``: total cache length to allocate (>= prompt length) so decode
    steps have headroom; defaults to the prompt length (dry-run convention,
    where the decode cells allocate their own full-length cache specs)."""
    dt = jnp.dtype(cfg.compute_dtype)
    cdt = jnp.dtype(cfg.cache_dtype)
    x = _embed_in(params, cfg, batch, dt)
    B, S, _ = x.shape
    positions = jnp.arange(S, dtype=jnp.int32)[None, :]

    def body(x, xs):
        lp, li = xs
        lp = _cast(lp, dt)
        x, _, kv = _block_apply(cfg, par, lp, x, positions, True,
                                layer_idx=li)
        return x, (kv[0].astype(cdt), kv[1].astype(cdt))

    if cfg.remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)
    x, (ks, vs) = jax.lax.scan(
        body, x,
        (params["layers"], jnp.arange(cfg.n_layers, dtype=jnp.int32)))
    if capacity is not None and capacity > S:
        pad = [(0, 0), (0, 0), (0, capacity - S), (0, 0), (0, 0)]
        ks = jnp.pad(ks, pad)
        vs = jnp.pad(vs, pad)
    x = _norm(cfg, x[:, -1:], params["final_norm"])
    logits = _head(params, cfg, x, par)[:, 0]
    return logits, {"k": ks, "v": vs, "pos": jnp.int32(S)}


def _decode_embed(params, cfg: ArchConfig, batch, dt):
    if cfg.stub_frontend:
        return batch["embed"].astype(dt)              # (B,1,D)
    return params["embed"].astype(dt)[batch["token"]][:, None]


def _advance(pos, done):
    """Per-slot position advance: finished rows stop moving (ragged
    completion).  Scalar pos (lockstep PR 3 path) stays scalar."""
    if done is None:
        return pos + 1
    return pos + jnp.where(done, 0, 1).astype(jnp.int32)


def _decode_ff(cfg: ArchConfig, par, lp, x, h_attn, salt):
    """Post-attention half of one decode layer (residual + FF/MoE) —
    shared by the dense and paged decode bodies so the two cache layouts
    can't drift apart."""
    x = x + h_attn
    hn = _norm(cfg, x, lp["ln2"])
    if cfg.family == "moe":
        h_ff, _ = _moe_apply(lp["moe"], hn, cfg, par, salt=salt)
    else:
        h_ff = mlp(lp["mlp"], hn, cfg.mlp_kind,
                   linear=_linear_for(cfg.dscim, par, cfg.dscim_fault),
                   salt=salt)
    return x + h_ff


def decode(params, cfg: ArchConfig, batch, cache,
           par: ParallelCtx | None = None):
    """One-token decode against the cache. Returns (logits (B,Vp), cache).

    ``cache["pos"]`` may be a scalar (all rows in lockstep) or per-slot
    (B,) for ragged completion; ``batch["done"]`` (optional, (B,) bool)
    marks finished slots, which stop advancing their position.  A cache
    carrying ``k_pages`` is the int8 block-paged layout (core/kvcache.py)
    and routes through ``decode_attention_paged``."""
    if "k_pages" in cache:
        return _decode_paged(params, cfg, batch, cache, par)
    dt = jnp.dtype(cfg.compute_dtype)
    x = _decode_embed(params, cfg, batch, dt)
    pos = cache["pos"]
    done = batch.get("done")

    def body(x, xs):
        lp, ck, cv, li = xs
        lp = _cast(lp, dt)
        salt = li * 8
        h, nk, nv = decode_attention(lp["attn"], _norm(cfg, x, lp["ln1"]),
                                     ck, cv, pos, cfg,
                                     linear=_attn_linear_for(
                                         cfg.dscim, par, cfg.dscim_fault),
                                     salt=salt)
        return _decode_ff(cfg, par, lp, x, h, salt), (nk, nv)

    x, (nk, nv) = jax.lax.scan(
        body, x, (params["layers"], cache["k"], cache["v"],
                  jnp.arange(cfg.n_layers, dtype=jnp.int32)))
    x = _norm(cfg, x, params["final_norm"])
    logits = _head(params, cfg, x, par)[:, 0]
    return logits, {"k": nk, "v": nv, "pos": _advance(pos, done)}


def _decode_paged(params, cfg: ArchConfig, batch, cache,
                  par: ParallelCtx | None = None):
    """One-token decode against the int8 block-paged KV cache: per-layer
    page pools ride the layer scan as xs (like the dense k/v planes); the
    page table and per-slot positions are layer-shared carry state.

    ``batch["paged_kernel"]`` (a static Python bool, set by the serving
    loop builders from their ``paged_attn`` option) pins the read path —
    Pallas kernel vs jnp gather; absent, the path follows cfg.dscim (see
    layers/attention.py ``decode_attention_paged``)."""
    dt = jnp.dtype(cfg.compute_dtype)
    x = _decode_embed(params, cfg, batch, dt)
    pos = cache["pos"]
    page_table = cache["page_table"]
    done = batch.get("done")
    use_kernel = batch.get("paged_kernel")

    def body(x, xs):
        lp, kp, vp, ks, vs, kt, vt, li = xs
        lp = _cast(lp, dt)
        salt = li * 8
        view = {"k_pages": kp, "v_pages": vp, "k_scale": ks, "v_scale": vs,
                "k_tail": kt, "v_tail": vt, "page_table": page_table,
                "pos": pos}
        h, planes = decode_attention_paged(
            lp["attn"], _norm(cfg, x, lp["ln1"]), view, cfg,
            linear=_attn_linear_for(cfg.dscim, par, cfg.dscim_fault),
            salt=salt, done=done,
            par=par, use_kernel=use_kernel)
        return _decode_ff(cfg, par, lp, x, h, salt), planes

    x, (kp, vp, ks, vs, kt, vt) = jax.lax.scan(
        body, x, (params["layers"], cache["k_pages"], cache["v_pages"],
                  cache["k_scale"], cache["v_scale"],
                  cache["k_tail"], cache["v_tail"],
                  jnp.arange(cfg.n_layers, dtype=jnp.int32)))
    x = _norm(cfg, x, params["final_norm"])
    logits = _head(params, cfg, x, par)[:, 0]
    # dict(cache, ...) rebuild: bookkeeping planes that ride the cache but
    # are not rewritten per step (the integrity ``page_sum`` digests) must
    # pass through, not be dropped by an explicit-key reconstruction
    return logits, dict(cache, k_pages=kp, v_pages=vp, k_scale=ks,
                        v_scale=vs, k_tail=kt, v_tail=vt,
                        page_table=page_table, pos=_advance(pos, done))


def decode_multi(params, cfg: ArchConfig, batch, cache,
                 par: ParallelCtx | None = None):
    """Speculative-verify decode: score T consecutive tokens per row in one
    forward — the verifier half of self-speculative decoding
    (launch/steps.py).  ``batch["tokens"]`` (B, T) int32; ``cache["pos"]``
    per-slot (B,) (a scalar is broadcast).  Position t of the returned
    logits is bitwise what ``decode`` would produce after feeding tokens
    0..t-1 (same weights, same salts, same ``_head`` path — see
    layers/attention.py ``decode_attention_multi`` for the exact-replay
    argument and the statistical/paper_inject carve-out).

    Returns (logits (B, T, Vp) f32, cache, win_kv) where win_kv is
    (win_k, win_v) (n_layers, B, T, KV, HD) tail-dtype window projections
    for the paged layout (``core/kvcache.spec_rollback`` consumes them) and
    None for the dense layout (dense rollback is position truncation only).
    """
    if cfg.stub_frontend:
        raise ValueError("decode_multi (speculative verify) needs token "
                         "inputs; stub-frontend configs are unsupported")
    dt = jnp.dtype(cfg.compute_dtype)
    B, T = batch["tokens"].shape
    x = params["embed"].astype(dt)[batch["tokens"]]       # (B,T,D)
    pos = cache["pos"]
    if getattr(pos, "ndim", 0) == 0:
        pos = jnp.full((B,), pos, jnp.int32)
    done = batch.get("done")
    adv = T if done is None else jnp.where(done, 0, T).astype(jnp.int32)
    lin = _attn_linear_for(cfg.dscim, par, cfg.dscim_fault)

    if "k_pages" in cache:
        page_table = cache["page_table"]
        use_kernel = batch.get("paged_kernel")

        def pbody(x, xs):
            lp, kp, vp, ks, vs, kt, vt, li = xs
            lp = _cast(lp, dt)
            salt = li * 8
            view = {"k_pages": kp, "v_pages": vp, "k_scale": ks,
                    "v_scale": vs, "k_tail": kt, "v_tail": vt,
                    "page_table": page_table, "pos": pos}
            h, planes, wkv = decode_attention_paged_multi(
                lp["attn"], _norm(cfg, x, lp["ln1"]), view, cfg,
                linear=lin, salt=salt, done=done,
                par=par, use_kernel=use_kernel)
            return _decode_ff(cfg, par, lp, x, h, salt), planes + wkv

        x, (kp, vp, ks, vs, kt, vt, wk, wv) = jax.lax.scan(
            pbody, x, (params["layers"], cache["k_pages"], cache["v_pages"],
                       cache["k_scale"], cache["v_scale"],
                       cache["k_tail"], cache["v_tail"],
                       jnp.arange(cfg.n_layers, dtype=jnp.int32)))
        # pass-through rebuild so the integrity digest plane (if present)
        # survives the verify forward
        new_cache = dict(cache, k_pages=kp, v_pages=vp, k_scale=ks,
                         v_scale=vs, k_tail=kt, v_tail=vt,
                         page_table=page_table, pos=pos + adv)
        win_kv = (wk, wv)
    else:
        def body(x, xs):
            lp, ck, cv, li = xs
            lp = _cast(lp, dt)
            salt = li * 8
            h, nk, nv = decode_attention_multi(
                lp["attn"], _norm(cfg, x, lp["ln1"]), ck, cv, pos, cfg,
                linear=lin, salt=salt, done=done)
            return _decode_ff(cfg, par, lp, x, h, salt), (nk, nv)

        x, (nk, nv) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"],
                      jnp.arange(cfg.n_layers, dtype=jnp.int32)))
        new_cache = {"k": nk, "v": nv, "pos": pos + adv}
        win_kv = None
    x = _norm(cfg, x, params["final_norm"])
    logits = _head(params, cfg, x, par)                   # (B,T,Vp)
    return logits, new_cache, win_kv


def cache_specs(cfg: ArchConfig, batch: int, seq: int):
    cdt = jnp.dtype(cfg.cache_dtype)
    f = jax.ShapeDtypeStruct
    return {
        "k": f((cfg.n_layers, batch, seq, cfg.n_kv, cfg.head_dim), cdt),
        "v": f((cfg.n_layers, batch, seq, cfg.n_kv, cfg.head_dim), cdt),
        "pos": f((), jnp.int32),
    }


def lm_loss(logits, labels, mask=None):
    """Token-mean cross-entropy; logits (B,S,Vp) f32, labels (B,S) int32."""
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
