"""KV blocks in use over the pool's blocks after each engine step,
averaged over the engine steps that start in the window, in %. Layer:
KV memory (block allocator). Moves ``tokens_per_s``: a full pool holds
requests in the queue."""


def read(ctx):
    t0, t1 = ctx["t_open"], ctx["t_close"]
    steps = [s for s in ctx["rec"].engine_steps if t0 <= s.t0 < t1]
    if not steps:
        return None
    return 100.0 * sum(s.kv_used for s in steps) / len(steps)
