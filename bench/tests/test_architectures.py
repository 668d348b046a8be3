"""A configuration's parameter layout and work counts (``architectures/``)
and the checks that read them: the dense layout makes the arrays it made
before it moved, bit for bit; the per-layer readers give the values of
the formulas they had before the work came from the layout; the config
check holds every key the program shares; the counter readers."""
from __future__ import annotations

import hashlib
import json
import os
import types

import jax
import numpy as np
import pytest

import cells
import conftest
import run

ARCH = cells.load_module(os.path.join(cells.HERE, "architectures",
                                      "dense_tied.py"), "arch_dense_tied")
with open(os.path.join(cells.HERE, "configs", "qwen3-0.6b.dscim1.json")) as f:
    QWEN3 = json.load(f)
PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9}

# sha256 over every leaf (path, dtype, shape, bytes) of
# ``weights.make(conftest.TINY_MODEL, QWEN3["init"], seed)`` on the commit
# before the dense layout moved into architectures/dense_tied.py
PARENT_DIGESTS = {
    0: "c12cf2bd5ec2e17e9a276e69f28019acd911eec8bf907cd2938f1bd583498cc9",
    7: "87b7bf2e21d100617fea89e454337582531c52432e16b009090a5751fae8ef89",
    2**33 + 5:
        "71efb23c67bc749c184a8e2f1cdf8de5ff4c72598ba3f683f7f9ac0844b60156",
}


def _digest(params) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed", sorted(PARENT_DIGESTS))
def test_dense_tied_weights_bitwise_as_before(seed):
    got = ARCH.make(conftest.TINY_MODEL, QWEN3["init"], seed)
    assert _digest(got) == PARENT_DIGESTS[seed]


def test_cell_serves_its_architecture(tiny_cell):
    assert tiny_cell.architecture().__name__ == \
        "bench_architecture_dense_tied"
    a = tiny_cell.weights(3)
    b = ARCH.make(conftest.TINY_MODEL, QWEN3["init"], 3)
    assert _digest(a) == _digest(b)


# -- the readers against the formulas they had before --------------------

def _old_mfu(ctx):
    m, w = ctx.model, ctx.work
    n = w["prefill_rows"] + w["decoded"]
    if n <= 0 or ctx.window_s <= 0:
        return None
    d, H, KV, D = m["d_model"], m["n_heads"], m["n_kv"], m["head_dim"]
    per_layer = d * H * D + 2 * d * KV * D + H * D * d + 3 * d * m["d_ff"]
    params = m["n_layers"] * per_layer + d * m["vocab_padded"]
    attn = sum(c * k for c, k in w["contexts"]) * 4 * H * D * m["n_layers"]
    ops = 2 * params * n + attn
    return 100.0 * ops / (ctx.window_s * ctx.peaks["bf16_flops"])


def _old_dscim(ctx):
    kt = ctx.summary.op_seconds("dscim_fused_mvm")
    if kt <= 0:
        return None
    m, w = ctx.model, ctx.work
    work = ctx.kernel_work("dscim_fused_mvm")
    peak, bw = ctx.peaks[work.PEAK], ctx.peaks["hbm_bytes_per_s"]
    d, f, v = m["d_model"], m["d_ff"], m["vocab_padded"]
    mlp = ((d, f), (d, f), (f, d))

    def call(M, K, N):
        ops, moved = work.work(M, K, N)
        return max(ops / peak, moved / bw)

    def layers(M):
        return m["n_layers"] * sum(call(M, K, N) for K, N in mlp)

    t = 0.0
    if w["decode_steps"] > 0 and w["decoded"] > 0:
        M = w["decoded"] / w["decode_steps"]
        t += w["decode_steps"] * (layers(M) + call(M, d, v))
    if w["extend_calls"] > 0 and w["prefill_rows"] > 0:
        t += w["extend_calls"] * layers(w["prefill_rows"]
                                        / w["extend_calls"])
        t += w["emits"] * call(1, d, v)
    return 100.0 * t / kt


def _old_paged(ctx):
    kt = ctx.summary.op_seconds("paged_attention_decode")
    if kt <= 0:
        return None
    m, w = ctx.model, ctx.work
    work = ctx.kernel_work("paged_attention_decode")
    peak, bw = ctx.peaks[work.PEAK], ctx.peaks["hbm_bytes_per_s"]
    t = 0.0
    for context, count in w["contexts"]:
        ops, moved = work.work(context, m["n_heads"], m["n_kv"],
                               m["head_dim"], ctx.serve["page_size"])
        t += count * max(ops / peak, moved / bw)
    return 100.0 * m["n_layers"] * t / kt


def _work(seed: int) -> dict:
    """Window work as ``run.window_work`` gives it: ``decoded`` from the
    Router's occupancy, a float."""
    if seed == 0:                   # PR 15's traced span, about
        return {"decode_steps": 60, "decoded": 0.6833 * 60 * 8,
                "prefill_rows": 1913, "emits": 11, "extend_calls": 17,
                "contexts": [(c, 1 + c % 3) for c in range(1, 1400)]}
    rng = np.random.default_rng(seed)
    steps = int(rng.integers(0, 80)) * 4
    total = steps * 8
    occ = float(rng.integers(0, total + 1)) / max(total, 1)
    calls = int(rng.integers(0, 20))
    return {"decode_steps": steps, "decoded": occ * total,
            "prefill_rows": int(rng.integers(0, 128 * calls + 1)),
            "emits": int(rng.integers(0, calls + 1)),
            "extend_calls": calls,
            "contexts": sorted((int(c), int(k)) for c, k in zip(
                rng.integers(1, 1409, 50), rng.integers(1, 9, 50)))}


def _ctx(model, work, kernel_s: float):
    summary = types.SimpleNamespace(op_seconds=lambda name: kernel_s)
    ctx = types.SimpleNamespace(
        summary=summary, window_s=14.43, work=work, peaks=PEAKS,
        model=model, serve=QWEN3["serve"], architecture=ARCH,
        kernel_work=lambda k: cells.load_module(
            os.path.join(cells.HERE, "work", f"{k}.py"), f"w_{k}"))
    return ctx


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("model", [QWEN3["model"], conftest.TINY_MODEL],
                         ids=["qwen3", "tiny"])
@pytest.mark.parametrize("name,old", [("mfu.step", _old_mfu),
                                      ("dscim_fused_mvm_roofline",
                                       _old_dscim),
                                      ("paged_attention_decode_roofline",
                                       _old_paged)],
                         ids=["mfu", "dscim", "paged"])
def test_readers_give_old_values(tiny_cell, name, old, model, seed):
    ctx = _ctx(model, _work(seed), kernel_s=0.5 + seed)
    want = old(ctx)
    assert tiny_cell.metric_reader(name).read(ctx) == want
    if seed == 0:
        assert want is not None and want > 0


# -- the config check ----------------------------------------------------

def test_program_config_holds_every_shared_key():
    import copy
    run.program_config(QWEN3)
    for key, value in (("moe_topk", 2), ("moe_experts", 64),
                       ("tie_embeddings", False), ("vocab", 151937),
                       ("norm", "layernorm")):
        cfg = copy.deepcopy(QWEN3)
        cfg["model"][key] = value
        with pytest.raises(ValueError, match=key):
            run.program_config(cfg)
    # a key the program does not have is the file's own
    cfg = copy.deepcopy(QWEN3)
    cfg["model"]["norm_eps"] = 1e-5
    run.program_config(cfg)


# -- the counter readers -------------------------------------------------

def _stats(slot_steps, empty, prefill, admitted, wait_s):
    return {"slot_steps": slot_steps, "empty_slot_steps": empty,
            "prefill_slot_steps": prefill, "admitted": admitted,
            "queue_wait_s": wait_s}


@pytest.mark.parametrize("name,want", [
    ("router.empty_slot_share", 100.0 * 52 / 288),
    ("router.prefill_slot_share", 100.0 * 12 / 288),
    ("router.queue_wait_ms", 1e3 * (0.25 - 0.125) / 4)])
def test_counter_readers(tiny_cell, name, want):
    reader = tiny_cell.metric_reader(name)
    ctx = types.SimpleNamespace(stats0=_stats(960, 100, 40, 30, 0.125),
                                stats1=_stats(1248, 152, 52, 34, 0.25))
    assert reader.read(ctx) == pytest.approx(want, rel=1e-12)
    # nothing ran in the span: nothing to read
    idle = types.SimpleNamespace(stats0=ctx.stats0, stats1=ctx.stats0)
    assert reader.read(idle) is None
    # a program that does not count it
    bare = {"slot_steps": 1248}
    assert reader.read(types.SimpleNamespace(stats0=bare,
                                             stats1=bare)) is None
