"""Host time per engine step outside the device-to-host wait, in ms: the
summed durations of the step's ``engine.plan``, ``engine.tables``,
``engine.stage``, ``engine.launch``, ``engine.commit`` and
``engine.flush`` spans (admission and chunk planning, block-table growth,
staging the chunk arrays, dispatching the program, appending tokens and
releasing slots, the demote flush), averaged over the engine steps
(``engine.step`` spans) of the traced window. Layer: engine scheduler.
Moves ``tokens_per_s``: while the host works here the chip waits."""
import program_spans as ps

PHASES = ("engine.plan", "engine.tables", "engine.stage", "engine.launch",
          "engine.commit", "engine.flush")


def read(ctx):
    spans = ps.window_spans(ctx)
    if spans is None:
        return None
    steps = ps.named(spans, "engine.step")
    host = ps.named(spans, *PHASES)
    if not steps or not host:
        return None
    inside = [h for h in host
              if any(s <= h[1] and h[2] <= e for _, s, e, _ in steps)]
    return ps.total_ns(inside) / len(steps) / 1e6
