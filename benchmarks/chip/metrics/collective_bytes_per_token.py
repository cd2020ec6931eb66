"""Bytes the chips put into the tensor-parallel collectives per output
token, in the traced window, in B/token: the ``collective_bytes`` stats of
the window's ``engine.launch`` spans (the operand bytes, on each chip, of
the psums after the embedding, ``wo`` and ``w_down`` and of the logits
all-gather in each launched program) over the tokens those server steps
gave the clients (the harness's token stamps inside each traced
``server.step``, found by its ``step_num``). Padded rows count: they pass
the collectives too. A one-chip cell's spans carry 0, and a program whose
spans carry no such stat gives nothing to read. Layer: tensor-parallel
collectives. Moves ``tokens_per_s``; read beside ``collective_share``,
the device time those bytes take."""
import program_spans as ps


def read(ctx):
    spans = ps.window_spans(ctx)
    if spans is None:
        return None
    coll = ps.stat_sum(ps.named(spans, "engine.launch"), "collective_bytes")
    if not coll:
        return None
    rec = ctx["rec"]
    stamps = [t for st in rec.stamps.values() for t in st]
    tokens = 0
    for _, _, _, stats in ps.named(spans, "server.step"):
        t0, t1 = rec.server_steps[int(stats["step_num"])]
        tokens += sum(1 for t in stamps if t0 <= t <= t1)
    if not tokens:
        return None
    return coll / tokens
