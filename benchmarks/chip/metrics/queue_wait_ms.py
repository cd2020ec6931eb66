"""Mean queue wait of the requests admitted in the traced window, in ms:
the ``wait_us`` stats of the window's ``engine.plan`` spans over their
``admitted`` stats. A request's wait runs on the host clock from
``MILSServer.submit`` to the engine step that first admits it. Layer:
engine scheduler (admission). Moves ``tokens_per_s``: a request that
waits holds its client's next request back."""
import program_spans as ps


def read(ctx):
    spans = ps.window_spans(ctx)
    if spans is None:
        return None
    plans = ps.named(spans, "engine.plan")
    n = ps.stat_sum(plans, "admitted")
    if not n:
        return None
    return ps.stat_sum(plans, "wait_us") / n / 1e3
