"""Host read-out layer: mean milliseconds of the ``fleet.decode`` span per
read-out: at the end of ``FleetVM.run()`` every node's steps, status and
printed output.  The program's own span."""

import fleet_trace


def read(ctx):
    return fleet_trace.span_per_readout(ctx, __file__, "decode")
