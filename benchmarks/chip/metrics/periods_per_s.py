"""Node sampling periods committed and read out per second of the window:
all read-outs' periods over all of their time.  Host clock."""


def read(ctx):
    work = ctx["work"].get("periods")
    return None if work is None else work / ctx["window_s"]
