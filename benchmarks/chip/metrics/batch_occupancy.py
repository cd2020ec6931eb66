"""Live decode rows per engine step over the engine's ``max_slots``,
averaged over the engine steps that start in the window, in %. Layer:
engine scheduler. Moves ``tokens_per_s``."""


def read(ctx):
    t0, t1 = ctx["t_open"], ctx["t_close"]
    steps = [s for s in ctx["rec"].engine_steps if t0 <= s.t0 < t1]
    if not steps:
        return None
    return 100.0 * sum(len(s.decode_ctx) / s.max_slots
                       for s in steps) / len(steps)
