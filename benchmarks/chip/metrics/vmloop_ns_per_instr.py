"""vmloop kernel layer: nanoseconds of ``vmloop`` device time per
instruction retired inside the kernel (``FleetVM.pallas_stats()``'s
``kernel_steps`` over the same window).  Instructions retired are the
same under every executor, so this does not depend on how the kernel
moves its state."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not ctx["kernel_steps"]:
        return None
    kernel_s = tr["kernel_s"].get("vmloop", 0.0)
    if kernel_s <= 0:
        return None
    return kernel_s / ctx["kernel_steps"] * 1e9
