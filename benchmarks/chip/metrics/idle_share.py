"""1 - (union of the device-op intervals) / traced window, in %, the
window being the traced server steps. Layer: device. Moves
``tokens_per_s``: an idle chip waits on the host between steps."""


def read(ctx):
    red = ctx.get("trace_reduced")
    if not red or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
