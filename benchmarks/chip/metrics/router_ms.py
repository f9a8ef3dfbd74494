"""Round-program layer: device milliseconds per round under the
``fleet.router`` scope (the virtual clock and the mailbox router), from
the profiler trace of the window."""

import fleet_trace


def read(ctx):
    return fleet_trace.phase_per_round(ctx, __file__, "fleet.router")
