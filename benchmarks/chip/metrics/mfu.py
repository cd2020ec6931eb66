"""Model FLOPs of the work the engine steps of the window did (prompt
tokens prefilled, tokens decoded, each over its actual context; the LM
head for every decoded token and once per completed prompt) over the
window's length times the bf16 peak of the cell's chips (``chips`` x
one chip's), in %: the share of each chip's peak where the chips share
the work evenly, as tensor parallelism shares it. Layer: model step.
Moves ``tokens_per_s``."""


def read(ctx):
    f, m = ctx["flops"], ctx["m"]
    t0, t1 = ctx["t_open"], ctx["t_close"]
    total = 0
    for s in ctx["rec"].engine_steps:
        if not t0 <= s.t0 < t1:
            continue
        total += sum(f.token_flops(m, c, True) for c in s.decode_ctx)
        total += sum(f.chunk_flops(m, c0, cl, done)
                     for c0, cl, done in s.chunks)
    if not total:
        return None
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops"]
    return 100.0 * total / ((t1 - t0) * peak)
