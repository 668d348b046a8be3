"""The paged-attention kernel's share of its roofline: the least time of
reading, for every position the window computed, its sequence's cached
keys and values up to that position (work/paged_attention_decode.py:
operations at the bf16 peak or bytes at HBM bandwidth, whichever is
longer), in every layer, over the kernel's device time in the trace.
Decoded tokens and chunked-prefill rows both read the cache through the
kernel; the contexts are those of the requests the window served."""


def read(ctx):
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
