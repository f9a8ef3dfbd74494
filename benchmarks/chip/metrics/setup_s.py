"""Set-up seconds: building the nodes from the seed, compiling each
firmware once, and the warm-up read-outs (trace, lowering, compile or
cache load of every program the window runs).  Host clock."""


def read(ctx):
    return ctx["setup_s"]
