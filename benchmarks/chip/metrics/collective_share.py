"""Device time of the collective ops over the device's busy time, in the
traced window (the traced server steps), per chip and averaged over the
chips, in %. Busy time is the union of all device-op intervals; the
collectives' time is the union of theirs, so an op nested in another
counts once.

Collective ops are named by their HLO op: ``all-reduce``, ``all-gather``,
``reduce-scatter``, ``collective-permute``, ``all-to-all``, and the async
``-start`` and ``-done`` halves of each (``all-reduce-start.3``). The
name is the op's own, the text before its first space, so an op that
only consumes a collective's result does not count. Under tensor
parallelism these are the ``psum`` after ``wo`` and after ``w_down`` in
every layer (``common.psum_if_tp``) and the logits gather. A cell whose
trace holds no collective (one chip) reads nothing. Layer:
tensor-parallel collectives. Moves ``tokens_per_s``."""

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def is_collective(name: str) -> bool:
    return name.split(" ", 1)[0].lstrip("%").startswith(COLLECTIVES)


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    import xtrace
    steps = [s for s in tr.spans if s[0] == "server.step"]
    if not steps:
        return None
    w0, w1 = min(s[1] for s in steps), max(s[2] for s in steps)
    shares, total = [], 0.0
    for chip in range(tr.chips):
        ops = [(n, max(s, w0), min(e, w1)) for n, s, e, c in tr.ops
               if c == chip and e > w0 and s < w1]
        busy = sum(e - s for s, e in xtrace._union(
            [(s, e) for _, s, e in ops]))
        coll = sum(e - s for s, e in xtrace._union(
            [(s, e) for n, s, e in ops if is_collective(n)]))
        if busy > 0:
            shares.append(coll / busy)
            total += coll
    if not total:
        return None
    return 100.0 * sum(shares) / len(shares)
