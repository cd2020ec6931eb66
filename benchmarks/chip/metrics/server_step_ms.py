"""Mean host-clock length of ``MILSServer.step()`` over the steps that
start in the window (each ends in its engines' blocking device-to-host
copies), in ms. Layer: server loop. Moves ``tokens_per_s``."""


def read(ctx):
    t0, t1 = ctx["t_open"], ctx["t_close"]
    steps = [(a, b) for a, b in ctx["rec"].server_steps if t0 <= a < t1]
    if not steps:
        return None
    return sum(b - a for a, b in steps) / len(steps) * 1e3
