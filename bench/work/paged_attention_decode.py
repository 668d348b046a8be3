"""Work of one decode-attention read for one token and one layer: the
query against the ``context`` cached positions of its sequence (not the
cache's capacity): int8 keys and values with one float32 scale per page
and kv head, the float32 query in and output out; 2*H*D*context
operations for the scores and as many for the weighted sum."""
from __future__ import annotations

PEAK = "bf16_flops"


def work(context: int, n_heads: int, n_kv: int, head_dim: int,
         page_size: int) -> tuple:
    pages = -(-context // page_size)
    ops = 4 * n_heads * head_dim * context
    moved = 2 * context * n_kv * head_dim + 2 * pages * n_kv * 4 \
        + 2 * n_heads * head_dim * 4
    return ops, moved
