"""Control-plane time per server step, in ms: the ``plane.tick`` spans of
the traced window (liveness, growth handover, balance, refinement, the
deferred offers, and the KV export and import of each ``plane.migrate``
inside them) over the window's server steps. Layer: router and control
plane. Moves ``tokens_per_s``: the engines wait while it runs."""
import program_spans as ps


def read(ctx):
    spans = ps.window_spans(ctx)
    if spans is None:
        return None
    ticks = ps.named(spans, "plane.tick")
    if not ticks:
        return None
    return ps.total_ns(ticks) / len(ps.named(spans, "server.step")) / 1e6
