"""The fused DS-CIM kernel's share of its roofline: the least time of the
exact int8 products it stands for (work/dscim_fused_mvm.py: operations at
the int8 peak or bytes at HBM bandwidth, whichever is longer, per call)
over the kernel's device time in the trace.

Calls in the window: every decode step runs the three MLP matrices of
each layer and the head with M = live rows (the mean over the steps); a
chunked-prefill call runs the MLP matrices with M = the chunk's real
prompt rows, and the head only for the row that emits the first token."""


def read(ctx):
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
