"""Device: 1 - (union of device-op intervals) / (traced steady window)."""
from trace_reduce import idle_share


def read(ctx):
    if ctx["rehearsal"] or not ctx["busy_s"] or not ctx["window_s"]:
        return None
    return 100.0 * idle_share(ctx["busy_s"], ctx["window_s"])
