"""Host read-out layer: mean milliseconds of the ``fleet.restack`` span per
read-out: in ``FleetVM.start()`` stacking every node's host state and
copying it to the device.  The program's own span."""

import fleet_trace


def read(ctx):
    return fleet_trace.span_per_readout(ctx, __file__, "restack")
