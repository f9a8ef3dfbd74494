"""Node ANN inferences committed and read out per second of the window:
all read-outs' inferences over all of their time.  Host clock."""


def read(ctx):
    work = ctx["work"].get("inferences")
    return None if work is None else work / ctx["window_s"]
