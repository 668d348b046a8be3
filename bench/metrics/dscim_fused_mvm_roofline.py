"""The fused DS-CIM kernel's share of its roofline: the least time of the
exact int8 products it stands for (work/dscim_fused_mvm.py: operations at
the int8 peak or bytes at HBM bandwidth, whichever is longer, per call)
over the kernel's device time in the trace.

The products are those the architecture's ``calls`` marks as DS-CIM, at
the rows each call of the window gave them."""


def read(ctx):
    kt = ctx.summary.op_seconds("dscim_fused_mvm")
    if kt <= 0:
        return None
    work = ctx.kernel_work("dscim_fused_mvm")
    peak, bw = ctx.peaks[work.PEAK], ctx.peaks["hbm_bytes_per_s"]

    def call(M, K, N):
        ops, moved = work.work(M, K, N)
        return max(ops / peak, moved / bw)

    t = 0.0
    for count, blocks in ctx.architecture.calls(ctx):
        t += count * sum(times * sum(call(M, K, N)
                                     for M, K, N, dscim in prods if dscim)
                         for times, prods in blocks)
    return 100.0 * t / kt
