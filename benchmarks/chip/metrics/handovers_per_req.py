"""Length-stage handovers (``MILSServer.migrations``) made in the window
per request finished in it. Layer: router / control plane. Moves
``tokens_per_s``: each handover exports and imports a KV piece between
two engine steps."""


def read(ctx):
    n = ctx["finished_in_window"]
    return ctx["handovers"] / n if n else None
