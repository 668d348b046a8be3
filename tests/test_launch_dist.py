"""Distribution machinery tests on a small fake-device mesh (subprocess —
the main test process must keep 1 CPU device)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8, timeout: int = 420):
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=os.path.join(REPO, "src"))
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=timeout)


def test_mesh_and_sharding_rules():
    r = _run("""
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_debug_mesh, make_parallel_ctx
        from repro.launch.sharding import param_specs, cache_partition
        from repro.configs import ARCHS
        from repro.models import get_model
        from jax.sharding import PartitionSpec as P
        mesh = make_debug_mesh(2, 2, pod=2)
        par = make_parallel_ctx(mesh)
        assert par.dp_axes == ("pod", "data")
        cfg = ARCHS["qwen3-0.6b"]
        mod = get_model(cfg)
        ps = jax.eval_shape(lambda k: mod.init_params(cfg, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
        specs = param_specs(cfg, par, ps)
        assert specs["layers"]["attn"]["wq"] == P(None, "data", "model")
        assert specs["layers"]["attn"]["wo"] == P(None, "model", "data")
        assert specs["embed"] == P("model", "data")
        cs = mod.cache_specs(cfg, 8, 64)
        cp = cache_partition(cfg, par, cs)
        assert cp["k"][1] == ("pod", "data") and cp["k"][3] == "model"
        print("OK")
    """)
    assert "OK" in r.stdout, r.stderr[-3000:]


def test_tiny_distributed_train_step_compiles_and_runs():
    """A real (executed, not just lowered) distributed train step on a 2x2
    mesh with FSDP+TP shardings — validates the whole pjit path numerically
    against the single-device step."""
    r = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import ARCHS
        from repro.launch.mesh import make_debug_mesh, make_parallel_ctx
        from repro.launch.sharding import (param_specs, opt_state_specs,
                                           batch_specs, to_shardings)
        from repro.launch.steps import make_train_step
        from repro.models import get_model
        from repro.optim.adamw import AdamW
        cfg = ARCHS["olmo-1b"].reduced()
        mod = get_model(cfg)
        opt = AdamW(lr=1e-3)
        params = mod.init_params(cfg, jax.random.PRNGKey(0))
        ostate = opt.init(params)
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 16),
                                              0, cfg.vocab),
                 "labels": jax.random.randint(jax.random.PRNGKey(2), (4, 16),
                                              0, cfg.vocab)}
        # single-device reference
        ref_step = jax.jit(make_train_step(cfg, None, opt))
        _, _, m_ref = ref_step(params, ostate, batch)
        # distributed
        mesh = make_debug_mesh(2, 2)
        par = make_parallel_ctx(mesh)
        specs = param_specs(cfg, par, params)
        psh = to_shardings(mesh, specs)
        osh = to_shardings(mesh, opt_state_specs(specs))
        bsh = to_shardings(mesh, batch_specs(cfg, par, batch))
        pd = jax.device_put(params, psh)
        od = jax.device_put(ostate, osh)
        bd = jax.device_put(batch, bsh)
        dist_step = jax.jit(make_train_step(cfg, par, opt),
                            in_shardings=(psh, osh, bsh),
                            out_shardings=(psh, osh, None))
        _, _, m_dist = dist_step(pd, od, bd)
        np.testing.assert_allclose(float(m_ref["loss"]),
                                   float(m_dist["loss"]), rtol=2e-3)
        print("OK", float(m_ref["loss"]), float(m_dist["loss"]))
    """)
    assert "OK" in r.stdout, r.stderr[-3000:]


def test_moe_ep_shard_map_numerics():
    """shard_map EP MoE == local MoE on the same inputs (2-way EP)."""
    r = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.layers.moe import init_moe, moe, moe_local
        from repro.launch.mesh import make_debug_mesh
        mesh = make_debug_mesh(2, 2)
        key = jax.random.PRNGKey(0)
        p = init_moe(key, 16, 32, n_experts=4, top_k=2, n_shared=1)
        x = jax.random.normal(key, (4, 8, 16))
        ref, _ = moe_local(p, x, top_k=2, capacity_factor=8.0,
                           has_shared=True)
        def inner(p_, x_):
            out, aux = moe(p_, x_, top_k=2, capacity_factor=8.0,
                           ep_axis="model", has_shared=True)
            return out
        f = jax.shard_map(inner, mesh=mesh, check_vma=False,
            in_specs=({"router": P(None, None),
                       "experts": {"w_gate": P("model", None, None),
                                   "w_up": P("model", None, None),
                                   "w_down": P("model", None, None)},
                       "shared": {"w_gate": P(None, None),
                                  "w_up": P(None, None),
                                  "w_down": P(None, None)}},
                      P("data", None, None)),
            out_specs=P("data", None, None))
        got = f(p, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5)
        print("OK")
    """)
    assert "OK" in r.stdout, r.stderr[-3000:]


def test_hlo_cost_analyzer_loop_exactness():
    """Loop-aware analyzer reproduces analytic dot flops through a scan."""
    r = _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.launch.hlo_cost import analyze_hlo
        from repro.launch.mesh import make_debug_mesh
        mesh = make_debug_mesh(2, 2)
        D, F, L, B = 64, 128, 5, 16
        def f(w1, w2, x):
            def body(h, ws):
                a, b = ws
                return jax.nn.gelu(h @ a) @ b, None
            h, _ = jax.lax.scan(body, x, (w1, w2))
            return h.sum()
        import jax.numpy as jnp
        w1 = jax.ShapeDtypeStruct((L, D, F), jnp.float32)
        w2 = jax.ShapeDtypeStruct((L, F, D), jnp.float32)
        x = jax.ShapeDtypeStruct((B, D), jnp.float32)
        sh = (jax.NamedSharding(mesh, P(None, "data", "model")),
              jax.NamedSharding(mesh, P(None, "model", "data")),
              jax.NamedSharding(mesh, P("data", None)))
        c = jax.jit(f, in_shardings=sh).lower(w1, w2, x).compile()
        cost = analyze_hlo(c.as_text())
        analytic = 2 * (2.0 * B * D * F) * L / 4   # fwd only, per device
        assert abs(cost.flops / analytic - 1) < 0.05, (cost.flops, analytic)
        print("OK")
    """)
    assert "OK" in r.stdout, r.stderr[-3000:]


def test_pipeline_parallel_compiles():
    r = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
        from repro.launch.pipeline import pp_dryrun
        rec = pp_dryrun(d_model=256, d_ff=512, layers_per_stage=2,
                        microbatches=4, mb_size=1, seq=64)
        assert rec["ok"] and rec["collective_permutes"] > 0
        print("OK", rec)
    """, devices=512, timeout=560)
    assert "OK" in r.stdout, r.stderr[-3000:]


def test_dscim_nsharded_prepared_mvm_matches_single_device():
    """ROADMAP sharding item: the prepared weight's output columns tile over
    the 'model' axis (x broadcasts, windows stay local on K) — the sharded
    fused MVM must be bit-identical to the single-device prepared path."""
    r = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.qweights import prepare_linear_weight
        from repro.core.seed_search import calibrated_config
        from repro.kernels.dscim_fused import (dscim_fused_mvm_prepared,
                                               dscim_fused_mvm_sharded)
        from repro.launch.mesh import make_debug_mesh
        mesh = make_debug_mesh(2, 4)
        cfg = calibrated_config("dscim2", 64, "paper")
        rng = np.random.default_rng(0)
        for shape, gk in (((3, 130), 64), ((2, 5, 100), 128)):
            x = jnp.asarray(rng.normal(0, 1, (*shape,)), jnp.float32)
            w = jnp.asarray(rng.normal(0, 1, (shape[-1], 32)), jnp.float32)
            qw = prepare_linear_weight(w, gk)
            ref = dscim_fused_mvm_prepared(x, qw, cfg)
            got = dscim_fused_mvm_sharded(x, qw, cfg, mesh, axis="model")
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
            # batch additionally sharded over DP (or replicated when the
            # leading dim doesn't divide) — still bitwise
            got_b = dscim_fused_mvm_sharded(x, qw, cfg, mesh, axis="model",
                                            batch_axes=("data",))
            np.testing.assert_array_equal(np.asarray(got_b), np.asarray(ref))
        print("OK")
    """)
    assert "OK" in r.stdout, r.stderr[-3000:]


def test_param_specs_quantized_subtree():
    """Prepared params get the N-over-'model' rule: q (L, nw, g, N) and
    scale (L, nw, N) both shard their trailing dim; window dims stay local;
    to_shardings descends the QuantizedLinearWeight spec subtree."""
    r = _run("""
        import dataclasses
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.configs import ARCHS
        from repro.core.qweights import QuantizedLinearWeight
        from repro.launch.mesh import make_debug_mesh, make_parallel_ctx
        from repro.launch.sharding import param_specs, to_shardings
        from repro.launch.steps import prepare_serving_params
        from repro.models import get_model
        cfg = dataclasses.replace(ARCHS["qwen3-0.6b"].reduced(),
                                  dscim="kernel:dscim1:256")
        mod = get_model(cfg)
        params = mod.init_params(cfg, jax.random.PRNGKey(0))
        pp = prepare_serving_params(cfg, params)
        par = make_parallel_ctx(make_debug_mesh(2, 2))
        specs = param_specs(cfg, par, pp)
        up = specs["layers"]["mlp"]["w_up"]
        assert isinstance(up, QuantizedLinearWeight), type(up)
        assert up.q == P(None, None, None, "model"), up.q
        assert up.scale == P(None, None, "model"), up.scale
        head = specs["lm_head"]
        assert head.q == P(None, None, "model") and \
            head.scale == P(None, "model"), (head.q, head.scale)
        # float params keep their rules
        assert specs["layers"]["attn"]["wq"] == P(None, "data", "model")
        sh = to_shardings(par.mesh, specs)
        assert sh["layers"]["mlp"]["w_up"].q.spec == up.q
        jax.device_put(pp, sh)  # placement actually works
        print("OK")
    """)
    assert "OK" in r.stdout, r.stderr[-3000:]


def test_mesh_serve_scanned_parity():
    """ISSUE 3 acceptance: serve_batch under a 'model' mesh with prepared
    N-sharded qweights, whole scanned generation loop inside one jit —
    greedy tokens bit-identical to single-device serving and prefill logits
    equal to float tolerance (the DS-CIM MVMs themselves are bitwise — see
    test_dscim_nsharded_prepared_mvm_matches_single_device — but XLA's CPU
    dot blocking differs per shard width for the float attention matmuls,
    so full-stack logits land within reduction-order noise)."""
    r = _run("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_arch
        from repro.launch.mesh import parallel_ctx_from_spec
        from repro.launch.serve import serve_batch
        from repro.models import get_model
        cfg = dataclasses.replace(get_arch("qwen3-0.6b").reduced(),
                                  dscim="kernel:dscim1:256")
        model = get_model(cfg)
        params = model.init_params(cfg, jax.random.PRNGKey(0))
        prompts = np.random.default_rng(0).integers(
            0, cfg.vocab, (4, 8), dtype=np.int32)
        ref_t, ref_l = serve_batch(cfg, params, prompts, 6)
        par = parallel_ctx_from_spec("model=4")
        got_t, got_l = serve_batch(cfg, params, prompts, 6, par=par)
        np.testing.assert_array_equal(ref_t, got_t)
        np.testing.assert_allclose(np.asarray(ref_l[0]),
                                   np.asarray(got_l[0]), atol=1e-5)
        # data x model mesh too (batch shards over 'data')
        par2 = parallel_ctx_from_spec("data=2,model=4")
        got2_t, got2_l = serve_batch(cfg, params, prompts, 6, par=par2)
        np.testing.assert_array_equal(ref_t, got2_t)
        np.testing.assert_allclose(np.asarray(ref_l[0]),
                                   np.asarray(got2_l[0]), atol=1e-5)
        print("OK")
    """)
    assert "OK" in r.stdout, r.stderr[-3000:]


def test_moe_prepared_shared_expert_under_mesh():
    """Closes the ROADMAP guard note in models/lm.py: a prepared (resident
    int8) MoE shared expert now serves under a mesh — its planes replicate
    (launch/sharding.py) and the shard_map MoE body computes it locally via
    the DS-CIM linear, matching single-device serving."""
    r = _run("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.configs import get_arch
        from repro.core.qweights import QuantizedLinearWeight
        from repro.launch.mesh import parallel_ctx_from_spec
        from repro.launch.serve import serve_batch
        from repro.launch.sharding import param_specs
        from repro.launch.steps import prepare_serving_params
        from repro.models import get_model
        cfg = dataclasses.replace(get_arch("deepseek-moe-16b").reduced(),
                                  dscim="exact:dscim2:64")
        model = get_model(cfg)
        params = model.init_params(cfg, jax.random.PRNGKey(0))
        par = parallel_ctx_from_spec("data=2,model=4")
        pp = prepare_serving_params(cfg, params, par)
        sh = pp["layers"]["moe"]["shared"]["w_gate"]
        assert isinstance(sh, QuantizedLinearWeight), type(sh)
        # the prepared shared expert replicates; routed experts keep EP/FSDP
        specs = param_specs(cfg, par, pp)
        sspec = specs["layers"]["moe"]["shared"]["w_gate"]
        assert sspec.q == P(None, None, None, None), sspec.q
        assert specs["layers"]["moe"]["experts"]["w_gate"] == \\
            P(None, "model", None, "data")
        prompts = np.random.default_rng(0).integers(
            0, cfg.vocab, (4, 8), dtype=np.int32)
        ref_t, ref_l = serve_batch(cfg, params, prompts, 5)
        got_t, got_l = serve_batch(cfg, params, prompts, 5, par=par)
        np.testing.assert_array_equal(ref_t, got_t)
        np.testing.assert_allclose(np.asarray(ref_l[0]),
                                   np.asarray(got_l[0]), atol=1e-5)
        print("OK")
    """)
    assert "OK" in r.stdout, r.stderr[-3000:]


def test_paged_kv_serve_under_mesh():
    """ISSUE 4: the int8 block-paged KV cache serves under a mesh — the
    page pool / scales / tails / page table get DP-aligned specs from
    cache_partition (the pool shards over the DP axes like the request
    batch; slot-major allocation keeps a slot's pages on its own shard),
    and serve_batch(kv='int8') under model=4 and data=2,model=4 meshes
    reproduces single-device paged serving token for token (prefill
    logits to float tolerance, as in the dense mesh parity test)."""
    r = _run("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.configs import get_arch
        from repro.core.kvcache import paged_cache_specs
        from repro.launch.mesh import parallel_ctx_from_spec
        from repro.launch.serve import serve_batch
        from repro.launch.sharding import cache_partition
        from repro.models import get_model
        cfg = dataclasses.replace(get_arch("qwen3-0.6b").reduced(),
                                  dscim="kernel:dscim1:256")
        par2 = parallel_ctx_from_spec("data=2,model=4")
        cs = paged_cache_specs(cfg, 4, 32, 4)
        cp = cache_partition(cfg, par2, cs)
        assert cp["k_pages"] == P(None, "data", None, None, None), cp
        assert cp["k_scale"] == P(None, "data", None), cp
        assert cp["v_tail"] == P(None, "data", None, None, None), cp
        assert cp["page_table"] == P("data", None), cp
        assert cp["pos"] == P("data"), cp
        model = get_model(cfg)
        params = model.init_params(cfg, jax.random.PRNGKey(0))
        prompts = np.random.default_rng(0).integers(
            0, cfg.vocab, (4, 8), dtype=np.int32)
        ref_t, ref_l = serve_batch(cfg, params, prompts, 6, kv="int8",
                                   page_size=4)
        for spec in ("model=4", "data=2,model=4"):
            par = parallel_ctx_from_spec(spec)
            got_t, got_l = serve_batch(cfg, params, prompts, 6, kv="int8",
                                       page_size=4, par=par)
            np.testing.assert_array_equal(ref_t, got_t)
            np.testing.assert_allclose(np.asarray(ref_l[0]),
                                       np.asarray(got_l[0]), atol=1e-5)
        print("OK")
    """)
    assert "OK" in r.stdout, r.stderr[-3000:]


def test_paged_attention_kernel_under_mesh():
    """ISSUE 5 acceptance: the Pallas paged-attention read path serves
    under an 8-fake-device --mesh model=4 (shard_map placement,
    kernels/paged_attention.py ``paged_attention_decode_sharded``) with
    tokens bitwise-equal and the full logit trace within 1e-5 of the jnp
    gather reference under the same mesh; vs the single-device kernel
    path, tokens are bitwise-equal and prefill logits within 1e-5 (the
    full-trace cross-placement comparison is looser for the same reason
    as the dense mesh parity test — XLA CPU dot blocking differs per
    shard width in float attention, and fed-back steps accumulate it).
    The data=2,model=4 mesh additionally exercises the DP-sharded batch +
    gathered-pool in_specs."""
    r = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_arch
        from repro.launch.mesh import parallel_ctx_from_spec
        from repro.launch.serve import serve_batch
        from repro.models import get_model
        cfg = get_arch("qwen3-0.6b").reduced()
        model = get_model(cfg)
        params = model.init_params(cfg, jax.random.PRNGKey(0))
        prompts = np.random.default_rng(0).integers(
            0, cfg.vocab, (4, 8), dtype=np.int32)

        def run(path, par):
            return serve_batch(cfg, params, prompts, 6, kv="int8",
                               page_size=4, trace_logits=True,
                               prepare=False, par=par, paged_attn=path)

        ref_t, ref_l = run("kernel", None)
        for spec in ("model=4", "data=2,model=4"):
            par = parallel_ctx_from_spec(spec)
            kt, kl = run("kernel", par)
            jt, jl = run("jnp", par)
            np.testing.assert_array_equal(kt, ref_t)
            np.testing.assert_array_equal(kt, jt)
            np.testing.assert_allclose(np.stack(kl), np.stack(jl),
                                       atol=1e-5)
            np.testing.assert_allclose(np.asarray(kl[0]),
                                       np.asarray(ref_l[0]), atol=1e-5)
        print("OK")
    """)
    assert "OK" in r.stdout, r.stderr[-3000:]


def test_paged_chunk_kernel_under_mesh():
    """The multi-position one-launch path (kernels/paged_attention.py
    ``paged_attention_decode_chunk_sharded``) under model=4 and
    data=2,model=4 meshes: outputs and the flushed pool, scales and tails
    equal the single-device path's (the per-slot page walk is placement-
    invariant)."""
    r = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import parallel_ctx_from_spec
        from repro.layers.attention import _paged_multi_kernel
        B, KV, R, HD, ps, MP, T = 4, 2, 2, 16, 4, 6, 8
        P = B * MP + 2
        rng = np.random.default_rng(0)
        i8 = lambda: jnp.asarray(rng.integers(-127, 128, (P, ps, KV, HD)),
                                 jnp.int8)
        sc = lambda: jnp.asarray(rng.uniform(0.005, 0.02, (P, KV)),
                                 jnp.float32)
        bf = lambda *shape: jnp.asarray(rng.normal(0, 1, shape),
                                        jnp.bfloat16)
        view = {"k_pages": i8(), "v_pages": i8(), "k_scale": sc(),
                "v_scale": sc(), "k_tail": bf(B, ps, KV, HD),
                "v_tail": bf(B, ps, KV, HD),
                "page_table": jnp.asarray(
                    rng.permutation(P)[:B * MP].reshape(B, MP), jnp.int32),
                "pos": jnp.asarray([1, 6, 0, 9], jnp.int32)}
        qf = jnp.asarray(rng.normal(0, 1, (B, T, KV, R, HD)), jnp.float32)
        wk, wv = bf(B, T, KV, HD), bf(B, T, KV, HD)
        done = jnp.asarray([False, True, False, False])

        def run(par):
            return jax.jit(lambda *a: _paged_multi_kernel(*a, par))(
                qf, view, wk, wv, done)

        ref_o, ref_p = run(None)
        for spec in ("model=4", "data=2,model=4"):
            o, planes = run(parallel_ctx_from_spec(spec))
            np.testing.assert_allclose(np.asarray(o), np.asarray(ref_o),
                                       atol=1e-5)
            for a, b in zip(planes, ref_p):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("OK")
    """)
    assert "OK" in r.stdout, r.stderr[-3000:]


def test_elastic_mesh_from_env():
    r = _run("""
        import os
        os.environ["REPRO_MESH"] = "d2x4"
        from repro.runtime.elastic import mesh_from_env
        m = mesh_from_env()
        assert m.shape == {"data": 2, "model": 4}, m.shape
        print("OK")
    """)
    assert "OK" in r.stdout, r.stderr[-3000:]


def test_elastic_pod_spec_degrades_on_small_host():
    """REPRO_MESH=pod16x16 on an 8-device CI host must warn and fall back
    to the largest supported debug mesh instead of raising (ISSUE 6)."""
    r = _run("""
        import os, warnings
        os.environ["REPRO_MESH"] = "pod16x16"
        from repro.runtime.elastic import mesh_from_env
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            m = mesh_from_env()
        assert m.shape == {"data": 1, "model": 8}, m.shape
        msgs = [str(x.message) for x in w
                if issubclass(x.category, RuntimeWarning)]
        assert any("pod16x16" in s and "degrading" in s for s in msgs), msgs

        # the pod default (no env var) degrades the same way
        del os.environ["REPRO_MESH"]
        with warnings.catch_warnings(record=True) as w2:
            warnings.simplefilter("always")
            m2 = mesh_from_env()
        assert m2.shape == {"data": 1, "model": 8}, m2.shape

        # explicit debug specs still raise when oversubscribed
        os.environ["REPRO_MESH"] = "d4x4"
        try:
            mesh_from_env()
        except Exception:
            pass
        else:
            raise AssertionError("d4x4 on 8 devices should raise")
        print("OK")
    """)
    assert "OK" in r.stdout, r.stderr[-3000:]
