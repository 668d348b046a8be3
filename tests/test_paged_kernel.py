"""Fused Pallas paged-attention decode kernel (kernels/paged_attention.py)
vs the jnp gather reference (layers/attention.py ``_paged_read_jnp``):
kernel-level parity across page sizes {4, 8, 16}, GQA and MHA geometries,
ragged positions and arbitrary page-table permutations; the one-launch
multi-position path against the per-position scan it replaces (outputs,
and the pool, scales and tails bitwise); model-level logit
parity through ``decode_attention_paged`` (pinned via the cache-keyed
``paged_attn`` serve option; the dscim-mode default-on selection and its
``REPRO_PAGED_ATTN`` env override are covered separately); done-masked
ragged serving equality; and the autotune plumbing (checked-in winners
for the serving shapes, candidate validity)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.kernels.paged_attention import (paged_attention_decode,
                                           use_paged_kernel)
from repro.layers.attention import (_paged_multi_kernel, _paged_multi_scan,
                                    _paged_read_jnp)
from repro.models import get_model


def _rand_paged(rng, B, KV, R, HD, ps, MP, extra_pages=2):
    """Random pool + permuted table + ragged positions, pool larger than
    the table needs (untouched pages must stay untouched)."""
    P = B * MP + extra_pages
    view = {
        "k_pages": jnp.asarray(rng.integers(-127, 128, (P, ps, KV, HD)),
                               jnp.int8),
        "v_pages": jnp.asarray(rng.integers(-127, 128, (P, ps, KV, HD)),
                               jnp.int8),
        "k_scale": jnp.asarray(rng.uniform(0.005, 0.02, (P, KV)),
                               jnp.float32),
        "v_scale": jnp.asarray(rng.uniform(0.005, 0.02, (P, KV)),
                               jnp.float32),
        "page_table": jnp.asarray(
            rng.permutation(P)[:B * MP].reshape(B, MP), jnp.int32),
        "pos": jnp.asarray(rng.integers(0, MP * ps, (B,)), jnp.int32),
    }
    kt = jnp.asarray(rng.normal(0, 1, (B, ps, KV, HD)), jnp.bfloat16)
    vt = jnp.asarray(rng.normal(0, 1, (B, ps, KV, HD)), jnp.bfloat16)
    q = jnp.asarray(rng.normal(0, 1, (B, KV, R, HD)), jnp.float32)
    return q, view, kt, vt


@pytest.mark.parametrize("ps", [4, 8, 16])
@pytest.mark.parametrize("KV,R,HD", [(2, 2, 16),   # GQA (the serve config)
                                     (4, 1, 8)])   # MHA (n_rep = 1)
def test_kernel_matches_jnp_reference(ps, KV, R, HD):
    """Every (page size, geometry, cell tiling) combination agrees with
    the jnp reference scan to float-accumulation tolerance on random
    pools with permuted page tables and ragged per-slot positions."""
    rng = np.random.default_rng(ps * 100 + KV)
    B, MP = 3, 3
    q, view, kt, vt = _rand_paged(rng, B, KV, R, HD, ps, MP)
    ref = _paged_read_jnp(q, view, kt, vt)
    for gh in [g for g in (1, 2, 4) if KV % g == 0]:
        for qp in sorted({R, 8}):
            out = paged_attention_decode(
                q, view["k_pages"], view["v_pages"], view["k_scale"],
                view["v_scale"], kt, vt, view["page_table"], view["pos"],
                gh=gh, qp=qp, interpret=True)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=1e-6, err_msg=f"gh={gh} qp={qp}")


def test_kernel_edge_positions():
    """pos pinned to the page boundaries the masking must get right:
    0 (only the tail's first token), ps-1 (exactly one full logical page
    worth in the tail), ps (first token of page 1), MP*ps-1 (last valid)."""
    KV, R, HD, ps, MP = 2, 2, 16, 4, 3
    rng = np.random.default_rng(7)
    q, view, kt, vt = _rand_paged(rng, 4, KV, R, HD, ps, MP)
    view["pos"] = jnp.asarray([0, ps - 1, ps, MP * ps - 1], jnp.int32)
    ref = _paged_read_jnp(q, view, kt, vt)
    out = paged_attention_decode(
        q, view["k_pages"], view["v_pages"], view["k_scale"],
        view["v_scale"], kt, vt, view["page_table"], view["pos"],
        interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


# (page size, window T, entry positions, done rows, (KV, n_rep, HD)):
# entries at and inside page boundaries, windows that cross one or more
# pages, a window that ends on the table's last page (the padded final
# chunk of a prompt), and done rows anywhere in the batch
CHUNK_CASES = [
    (4, 3, [0, 5, 2], None, (2, 2, 16)),
    (4, 8, [1, 4, 9], [False, True, False], (2, 2, 16)),
    (4, 16, [3, 0, 6], [False, False, True], (2, 2, 16)),
    (8, 3, [7, 8, 6], None, (2, 2, 16)),
    (8, 8, [0, 13, 4], [True, False, False], (2, 2, 16)),
    (8, 16, [3, 0, 17], [False, False, True], (2, 2, 16)),
    (4, 8, [2, 7, 0], None, (16, 1, 8)),       # two kv-head groups
]


@pytest.mark.parametrize("ps,T,pos,done,geom", CHUNK_CASES)
def test_chunk_matches_per_position_scan(ps, T, pos, done, geom):
    """The one-launch multi-position path (pre-flush + the chunk entry)
    against the per-position scan of the decode entry it replaces: live
    rows' outputs within 1e-5, the pool, scales and tails bitwise equal
    (pad positions included), done rows' outputs finite."""
    KV, R, HD = geom
    B = len(pos)
    rng = np.random.default_rng(ps * 1000 + T * 10 + KV)
    MP = -(-(max(pos) + T) // ps)        # the longest window ends on it
    q, view, kt, vt = _rand_paged(rng, B, KV, R, HD, ps, MP)
    view = dict(view, pos=jnp.asarray(pos, jnp.int32), k_tail=kt, v_tail=vt)
    qf = jnp.asarray(rng.normal(0, 1, (B, T, KV, R, HD)), jnp.float32)
    wk, wv = (jnp.asarray(rng.normal(0, 1, (B, T, KV, HD)), jnp.bfloat16)
              for _ in range(2))
    d = None if done is None else jnp.asarray(done)
    ref, ref_planes = jax.jit(
        lambda *a: _paged_multi_scan(*a, None, True))(qf, view, wk, wv, d)
    out, planes = jax.jit(
        lambda *a: _paged_multi_kernel(*a, None))(qf, view, wk, wv, d)
    live = ~np.asarray(done if done is not None else [False] * B)
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(ref)[live],
                               atol=1e-5)
    assert np.isfinite(np.asarray(out)).all()
    for name, a, b in zip(("k_pages", "v_pages", "k_scale", "v_scale",
                           "k_tail", "v_tail"), planes, ref_planes):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def _serve_logits(cfg, params, prompts, n_tokens, path, **kw):
    """serve_batch trace under a pinned read path — ``paged_attn`` is part
    of the jitted builder's cache key, so back-to-back A/Bs are safe."""
    from repro.launch.serve import serve_batch
    return serve_batch(cfg, params, prompts, n_tokens, trace_logits=True,
                       prepare=False, kv="int8", paged_attn=path, **kw)


@pytest.mark.parametrize("ps", [4, 8, 16])
def test_serve_logits_parity_across_page_sizes(ps):
    """Model-level acceptance: the kernel read path reproduces the jnp
    path's full per-step logit trace to <= 1e-5 through
    decode_attention_paged (tail writes, flushes and the layer scan
    included), at every supported page size."""
    cfg = get_arch("qwen3-0.6b").reduced()
    model = get_model(cfg)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    prompts = np.random.default_rng(0).integers(0, cfg.vocab, (3, 8),
                                                dtype=np.int32)
    tk, lk = _serve_logits(cfg, params, prompts, 8, "kernel", page_size=ps)
    tj, lj = _serve_logits(cfg, params, prompts, 8, "jnp", page_size=ps)
    np.testing.assert_array_equal(tk, tj)
    np.testing.assert_allclose(np.stack(lk), np.stack(lj), atol=1e-5)


def test_serve_logits_parity_mha():
    """MHA geometry (n_kv == n_heads, n_rep == 1) through the model."""
    cfg = get_arch("qwen3-0.6b").reduced()
    cfg = dataclasses.replace(cfg, n_kv=cfg.n_heads)
    model = get_model(cfg)
    params = model.init_params(cfg, jax.random.PRNGKey(1))
    prompts = np.random.default_rng(1).integers(0, cfg.vocab, (2, 8),
                                                dtype=np.int32)
    tk, lk = _serve_logits(cfg, params, prompts, 6, "kernel", page_size=4)
    tj, lj = _serve_logits(cfg, params, prompts, 6, "jnp", page_size=4)
    np.testing.assert_array_equal(tk, tj)
    np.testing.assert_allclose(np.stack(lk), np.stack(lj), atol=1e-5)


def test_serve_ragged_done_masked_parity():
    """Ragged/done-masked serving (EOS early-exit with skewed per-slot
    budgets): the kernel path's tokens match the jnp path's bit for bit —
    frozen positions on finished slots mask identically in-kernel."""
    from repro.launch.serve import serve_batch
    cfg = get_arch("qwen3-0.6b").reduced()
    model = get_model(cfg)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    prompts = np.random.default_rng(2).integers(0, cfg.vocab, (4, 8),
                                                dtype=np.int32)
    out = {path: serve_batch(cfg, params, prompts, 8, kv="int8",
                             page_size=4, eos_id=-1, max_new=[2, 8, 5, 3],
                             paged_attn=path)[0]
           for path in ("kernel", "jnp")}
    np.testing.assert_array_equal(out["kernel"], out["jnp"])


def test_continuous_paged_attn_paths_agree():
    """The continuous-batching scheduler threads paged_attn through
    make_segment_fn: both read paths produce identical per-request
    outputs through staggered admission and slot recycling."""
    from repro.launch.serve import serve_continuous
    cfg = get_arch("qwen3-0.6b").reduced()
    model = get_model(cfg)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    prompts = np.random.default_rng(3).integers(0, cfg.vocab, (4, 8),
                                                dtype=np.int32)
    budgets = np.asarray([2, 5, 3, 4], np.int32)
    outs = {}
    for path in ("kernel", "jnp"):
        outs[path], _ = serve_continuous(cfg, params, prompts, 5, slots=2,
                                         seg_len=2, max_new=budgets,
                                         eos_id=-1, kv="int8", page_size=4,
                                         paged_attn=path)
    for a, b in zip(outs["kernel"], outs["jnp"]):
        np.testing.assert_array_equal(a, b)


def test_dscim_kernel_mode_selects_kernel_path(monkeypatch):
    """Selection policy: default-on exactly for 'kernel' dscim modes; the
    env knob forces either path regardless of mode."""
    assert use_paged_kernel("kernel:dscim1:256")
    assert use_paged_kernel("kernel+attn:dscim1:256")
    assert not use_paged_kernel("off")
    assert not use_paged_kernel("lut:dscim1:256")
    monkeypatch.setenv("REPRO_PAGED_ATTN", "kernel")
    assert use_paged_kernel("off")
    monkeypatch.setenv("REPRO_PAGED_ATTN", "jnp")
    assert not use_paged_kernel("kernel:dscim1:256")


def test_autotune_serving_shapes_are_cache_hits():
    """The checked-in cache ships paged-attention winners for the decode
    serving geometry at every supported page size — cold-start tuning is
    a lookup (no sweep), and the winner is a valid (gh, qp) cell."""
    import json

    from repro.kernels import autotune
    with open(autotune.DEFAULT_CACHE) as f:
        disk = json.load(f)
    for ps in (4, 8, 16):
        key = f"paged_attn/B4/kv2r2hd16/ps{ps}/cpu"
        assert key in disk, f"missing checked-in winner {key}"
        gh, qp = autotune.paged_attn_tiles((4, 2, 2, 16), ps,
                                           interpret=True)
        assert (gh, qp) == tuple(disk[key])
        assert 2 % gh == 0 and qp >= 2


def test_tuned_cell_matches_reference():
    """The autotuned (gh, qp) winner computes the same attention as the
    defaults (tiling is numerics-free)."""
    rng = np.random.default_rng(3)
    q, view, kt, vt = _rand_paged(rng, 4, 2, 2, 16, 4, 3)
    args = (q, view["k_pages"], view["v_pages"], view["k_scale"],
            view["v_scale"], kt, vt, view["page_table"], view["pos"])
    base = paged_attention_decode(*args, interpret=True)
    tuned = paged_attention_decode(*args, tune=True, interpret=True)
    np.testing.assert_allclose(np.asarray(tuned), np.asarray(base),
                               atol=1e-6)


def test_kernel_rejects_bad_cells():
    rng = np.random.default_rng(4)
    q, view, kt, vt = _rand_paged(rng, 2, 2, 2, 16, 4, 2)
    args = (q, view["k_pages"], view["v_pages"], view["k_scale"],
            view["v_scale"], kt, vt, view["page_table"], view["pos"])
    with pytest.raises(ValueError, match="must divide"):
        paged_attention_decode(*args, gh=3, interpret=True)
    with pytest.raises(ValueError, match="n_rep"):
        paged_attention_decode(*args, qp=1, interpret=True)
