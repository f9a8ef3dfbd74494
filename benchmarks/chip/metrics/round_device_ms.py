"""Round-program layer: device busy milliseconds per fleet round, from the
profiler trace of the window (busy over the rounds the window ran)."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not ctx["rounds"] or tr["busy_s"] <= 0:
        return None
    return tr["busy_s"] / ctx["rounds"] * 1e3
