"""Compile-only guards: both Pallas kernels of the serving path lower for a
TPU v5e (Mosaic, ``interpret=False``) at Qwen3-0.6B widths, and the
chunked-prefill program calls the paged kernel's chunk entry.

The chip is *described*, not attached (``jax.experimental.topologies``), so
these run on a CPU-only machine: nothing executes, but the TPU compiler
refuses what the chip would refuse — illegal block shapes, unsupported
in-kernel ops, too much VMEM.  Numerics are covered by the interpret-mode
parity tests (tests/test_kernels_fused.py, tests/test_paged_kernel.py).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.core.qweights import QuantizedLinearWeight
from repro.core.seed_search import calibrated_config
from repro.kernels import dscim_fused, paged_attention

QWEN = get_arch("qwen3-0.6b")          # published widths (not reduced)
M_DECODE = 8                           # decode rows: 8 serving slots
PAGE_SIZES = (8, 16)                   # serving default + a larger page


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compilation
    cache off (a compile for a described chip cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("variant,L", [("dscim1", 256), ("dscim2", 64)])
@pytest.mark.parametrize("K,N", [(QWEN.d_model, QWEN.d_ff),      # up/gate
                                 (QWEN.d_ff, QWEN.d_model),      # down
                                 (QWEN.d_model, QWEN.vocab)])    # tied head
def test_fused_mvm_compiles_for_v5e(one_chip, variant, L, K, N):
    cfg = calibrated_config(variant, L, "paper")
    g = 128
    nw = -(-K // g)

    def mvm(x, q, s):
        qw = QuantizedLinearWeight(q, s, K, g)
        return dscim_fused.dscim_fused_mvm_prepared(x, qw, cfg,
                                                    interpret=False)

    args = (jax.ShapeDtypeStruct((M_DECODE, K), jnp.bfloat16,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((nw, g, N), jnp.int8, sharding=one_chip),
            jax.ShapeDtypeStruct((nw, N), jnp.float32, sharding=one_chip))
    text = _compiled_text(mvm, args)
    assert "tpu_custom_call" in text
    assert dscim_fused.KERNEL_NAME in text


@pytest.mark.parametrize("ps", PAGE_SIZES)
def test_paged_attention_compiles_for_v5e(one_chip, ps):
    B, KV, HD = 8, QWEN.n_kv, QWEN.head_dim
    R = QWEN.n_heads // KV
    MP = 512 // ps                      # a 512-token page walk per slot
    P = B * MP + 1

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def attend(q, kp, vp, ks, vs, kt, vt, table, pos):
        return paged_attention.paged_attention_decode(
            q, kp, vp, ks, vs, kt, vt, table, pos, interpret=False)

    args = (spec((B, KV, R, HD), jnp.float32),
            spec((P, ps, KV, HD), jnp.int8), spec((P, ps, KV, HD), jnp.int8),
            spec((P, KV), jnp.float32), spec((P, KV), jnp.float32),
            spec((B, ps, KV, HD), jnp.bfloat16),
            spec((B, ps, KV, HD), jnp.bfloat16),
            spec((B, MP), jnp.int32), spec((B,), jnp.int32))
    text = _compiled_text(attend, args)
    assert "tpu_custom_call" in text
    assert paged_attention.KERNEL_NAME in text


def test_paged_attention_chunk_compiles_for_v5e(one_chip):
    """The chunk entry at the serving shape of a 128-token prefill chunk:
    8 slots, page size 8, a 192-page walk per slot."""
    T, B, KV, HD, ps, MP = 128, 8, QWEN.n_kv, QWEN.head_dim, 8, 192
    R = QWEN.n_heads // KV
    NC = (T + 2 * ps - 2) // ps
    P = B * MP + 1

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def attend(*args):
        return paged_attention.paged_attention_decode_chunk(
            *args, interpret=False)

    args = (spec((B, T, KV, R, HD), jnp.float32),
            spec((P, ps, KV, HD), jnp.int8), spec((P, ps, KV, HD), jnp.int8),
            spec((P, KV), jnp.float32), spec((P, KV), jnp.float32),
            spec((B, NC, ps, KV, HD), jnp.bfloat16),
            spec((B, NC, ps, KV, HD), jnp.bfloat16),
            spec((B, MP), jnp.int32), spec((B,), jnp.int32),
            spec((B,), jnp.bool_))
    text = _compiled_text(attend, args)
    assert "tpu_custom_call" in text
    assert paged_attention.CHUNK_KERNEL_NAME in text


@pytest.mark.parametrize("chunk_len", [1, 4])
def test_extend_engages_chunk_entry(one_chip, monkeypatch, chunk_len):
    """The chunked-prefill program, lowered for the chip at reduced
    widths, holds the chunk entry's kernel when a chunk has more than one
    position, and only the per-position decode entry when it has one."""
    from repro.launch.steps import init_serve_state, make_extend_fn
    from repro.models import get_model

    monkeypatch.setattr(paged_attention, "ON_TPU", True)
    cfg = get_arch("qwen3-0.6b").reduced()

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = place(jax.eval_shape(
        lambda: get_model(cfg).init_params(cfg, jax.random.PRNGKey(0))))
    state = place(jax.eval_shape(
        lambda: init_serve_state(cfg, 2, 24, kv="int8", page_size=4)))
    extend = make_extend_fn(cfg, None, chunk_len, eos_id=-1,
                            paged_attn="kernel", jit=False)
    scalar = lambda dt: jax.ShapeDtypeStruct((), dt, sharding=one_chip)  # noqa: E731
    text = jax.jit(extend).lower(
        params, state,
        jax.ShapeDtypeStruct((1, chunk_len), jnp.int32, sharding=one_chip),
        scalar(jnp.int32), scalar(jnp.int32), scalar(jnp.bool_),
        scalar(jnp.int32)).as_text()
    chunk = f'kernel_name = "{paged_attention.CHUNK_KERNEL_NAME}"'
    decode = f'kernel_name = "{paged_attention.KERNEL_NAME}"'
    assert (chunk in text) == (chunk_len > 1)
    assert (decode in text) == (chunk_len == 1)


@pytest.mark.parametrize("arch", ["olmo-1b", "codeqwen1.5-7b",
                                  "musicgen-large", "zamba2-7b"])
def test_paged_attention_default_group_compiles_for_v5e(one_chip, arch):
    """Configs with 16 or 32 kv heads: the default cell takes the smallest
    chip-legal kv-head group (8), not the whole kv axis."""
    cfg = get_arch(arch)
    B, KV, HD, ps = 8, cfg.n_kv, cfg.head_dim, PAGE_SIZES[0]
    R = cfg.n_heads // KV
    MP = 512 // ps
    P = B * MP + 1
    assert paged_attention.chip_legal_ghs(KV)[0] == 8

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def attend(q, kp, vp, ks, vs, kt, vt, table, pos):
        return paged_attention.paged_attention_decode(
            q, kp, vp, ks, vs, kt, vt, table, pos, interpret=False)

    args = (spec((B, KV, R, HD), jnp.float32),
            spec((P, ps, KV, HD), jnp.int8), spec((P, ps, KV, HD), jnp.int8),
            spec((P, KV), jnp.float32), spec((P, KV), jnp.float32),
            spec((B, ps, KV, HD), jnp.bfloat16),
            spec((B, ps, KV, HD), jnp.bfloat16),
            spec((B, MP), jnp.int32), spec((B,), jnp.int32))
    text = _compiled_text(attend, args)
    assert "tpu_custom_call" in text
    assert paged_attention.KERNEL_NAME in text
