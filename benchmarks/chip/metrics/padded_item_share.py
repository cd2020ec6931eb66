"""Share of the paged kernel's work items that hold no KV block, in %:
100 x (1 - real_items / items) summed over the ``engine.launch`` spans of
the traced window. The work list is bucketed to a power of two (decode
and chunk halves apart on a mixed step), and every item past the real
ones is padding the kernel still steps through. Layer: kernels (work
list). Moves ``tokens_per_s``."""
import program_spans as ps


def read(ctx):
    spans = ps.window_spans(ctx)
    if spans is None:
        return None
    launches = ps.named(spans, "engine.launch")
    items = ps.stat_sum(launches, "items")
    if not items:
        return None
    return 100.0 * (1.0 - ps.stat_sum(launches, "real_items") / items)
