"""Host read-out layer: nodes the Auditor verified per ``FleetVM.start()``
(the ``nodes`` count of the ``fleet.audit`` span, per read-out).  The
Auditor's work count, which one verdict per distinct program would cut."""

import fleet_trace


def read(ctx):
    return fleet_trace.span_per_readout(ctx, __file__, "audit", "nodes")
