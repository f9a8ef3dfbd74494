"""Host read-out layer: mean milliseconds of ``FleetVM.start()`` per
read-out: under ``auto`` the Auditor's verification of every node, then
restacking every node's host state onto the device.  The harness's own
host span around the call, which covers both."""


def read(ctx):
    spans = ctx["spans"].get("start")
    return sum(spans) / len(spans) * 1e3 if spans else None
