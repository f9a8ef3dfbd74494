"""Round-program layer: device milliseconds per round under the
``vmloop.pack`` and ``vmloop.unpack`` scopes (building the vmloop kernel's
rows from the fleet state and cutting them apart), from the profiler
trace of the window."""

import fleet_trace


def read(ctx):
    return fleet_trace.phase_per_round(ctx, __file__, "vmloop.pack",
                                       "vmloop.unpack")
