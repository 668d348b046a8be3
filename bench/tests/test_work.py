"""Work counts of the two kernels at Qwen3-0.6B widths, against numbers
worked out by hand, and the table of peaks."""
from __future__ import annotations

import os

import pytest

import cells

dscim = cells.load_module(os.path.join(cells.HERE, "work",
                                       "dscim_fused_mvm.py"), "w_dscim")
paged = cells.load_module(os.path.join(cells.HERE, "work",
                                       "paged_attention_decode.py"),
                          "w_paged")


def test_dscim_decode_call():
    # one decode step of 16 live slots through the gate projection
    # 1024 -> 3072: 2*16*1024*3072 ops; bytes: 3072*1024 int8 weights,
    # 8 windows * 3072 f32 weight scales, 16*1024 int8 activations,
    # 16*8 f32 activation scales, 16*3072 f32 outputs
    ops, moved = dscim.work(16, 1024, 3072)
    assert ops == 100_663_296
    assert moved == 3_145_728 + 98_304 + 16_384 + 512 + 196_608


def test_dscim_prefill_chunk():
    # one 128-row chunk through the down projection 3072 -> 1024
    # (24 windows): 2*128*3072*1024 ops
    ops, moved = dscim.work(128, 3072, 1024)
    assert ops == 805_306_368
    assert moved == 3_145_728 + 98_304 + 393_216 + 12_288 + 524_288


def test_paged_decode_read():
    # one token at context 1000, 16 query heads over 8 kv heads of 128:
    # 4*16*128*1000 ops; int8 K and V 2*1000*8*128 bytes, 125 pages of
    # f32 scales for K and V, the f32 query in and output out
    ops, moved = paged.work(1000, 16, 8, 128, 8)
    assert ops == 8_192_000
    assert moved == 2_048_000 + 8_000 + 16_384


def test_peaks_refuse_unknown_device(tmp_path):
    import conftest
    conftest.install(str(tmp_path), "tiny", conftest.tiny_config(),
                     conftest.TINY_MIX, conftest.TINY_CHECK)
    cell = cells.Cell("tiny.load", root=str(tmp_path),
                      bench_dir=os.path.join(str(tmp_path), "bench"))
    assert cell.peaks("TPU v5 lite")["int8_ops"] == 393e12
    with pytest.raises(KeyError):
        cell.peaks("TPU v9 imaginary")
