"""Host read-out layer: mean milliseconds of the ``fleet.sync`` span per
read-out: at the end of ``FleetVM.run()`` copying the fleet's state to the
host and into every node's frontend.  The program's own span."""

import fleet_trace


def read(ctx):
    return fleet_trace.span_per_readout(ctx, __file__, "sync")
