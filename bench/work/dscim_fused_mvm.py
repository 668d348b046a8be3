"""Work of one DS-CIM matrix product, counted as the exact int8 product the
estimator stands for: 2*M*K*N operations; int8 weights with one float32
scale per 128-row window and column, int8 activations with one float32
scale per row and window, and the float32 output moved once."""
from __future__ import annotations

PEAK = "int8_ops"


def work(M: int, K: int, N: int, window: int = 128) -> tuple:
    nw = -(-K // window)
    ops = 2 * M * K * N
    moved = K * N + 4 * nw * N + M * K + 4 * M * nw + 4 * M * N
    return ops, moved
