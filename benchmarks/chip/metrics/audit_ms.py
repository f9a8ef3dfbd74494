"""Host read-out layer: mean milliseconds of the ``fleet.audit`` span per
read-out: in ``FleetVM.start()`` the Auditor verifying every node's
programs and choosing the executor.  The program's own span."""

import fleet_trace


def read(ctx):
    return fleet_trace.span_per_readout(ctx, __file__, "audit")
