"""Least time of the ``paged_mixed_attention`` calls over their device
time, in %, summed over the engine steps in the traced window. The
kernel serves both the fused mixed steps and the decode-only steps. A
call's least time is the larger of its FLOPs over the bf16 peak and its
K/V bytes (``head_dim`` rows) over HBM bandwidth (the architecture
module's ``attn_least_time``), at the peaks of all the cell's chips
(``chips`` x one chip's): under tensor parallelism each chip computes
its share of the heads, and the device time is already per chip (the
trace's, averaged over the chips). The segments come from the request
states before and after each step, the device time from the trace's ops
inside that step's host span. Layer: kernels. Moves ``tokens_per_s``."""

KERNEL = "paged_mixed_attention"


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    import xtrace
    f, m, pk, n = ctx["flops"], ctx["m"], ctx["peaks"], ctx["chips"]
    steps = {s.n: s for s in ctx["rec"].engine_steps}
    least = spent = 0.0
    for stats, dev_s in xtrace.kernel_time_in_spans(tr, "engine.step",
                                                    KERNEL):
        s = steps.get(int(stats.get("n", -1)))
        if s is None or dev_s <= 0:
            continue
        least += f.attn_least_time(m, s.decode_ctx,
                                   [(c0, cl) for c0, cl, _ in s.chunks],
                                   n * pk["bf16_flops"],
                                   n * pk["hbm_bytes_per_s"])
        spent += dev_s
    return 100.0 * least / spent if spent > 0 else None
