"""Sensor-network measuring jobs on the Executive fleet (paper §7.1/§7.4).

A virtual GUW monitoring network: every sensor node is one REXAVM whose
*entire* measuring logic — stimulus, wait on conversion, hull envelope, peak
detection — arrives as a text code frame over the (simulated) NFC link.
Since PR 9 each node is genuinely multi-task: one task table carries

  * slot 0 — the **measuring daemon** (the boot task): ping, sample,
    envelope, peak, report — the paper's long-running measuring job;
  * a **shell job** spawned by the host :class:`~repro.exec.Executive`
    at priority 2: a watchdog that periodically stamps the node's virtual
    clock onto the UART service (the paper's incremental shell session
    riding alongside the job);
  * a **one-shot** spawned at priority 1: a single boot banner over UART.

The preemptive priority scheduler time-slices the three inside the fleet
round (``ExecutiveConfig.quantum`` instructions per micro-slice), and all
host IO — the ADC/DAC FIOS calls *and* the shared ``uart.write`` service —
is executed through the vectorized syscall plane: one batched handler call
per syscall wave for the whole fleet, not one Python callback per node.

The nodes run as one device-resident :class:`FleetVM`; each node reports
its peak to a collector node through the on-device ``send``/``receive``
mailbox rings.  The example ends by re-running the measuring job
single-task (the pre-Executive configuration) and asserting the
multi-task fleet did not regress the full-state transfer counts — the
Executive adds tasks, not host round trips.

    PYTHONPATH=src python examples/sensor_node.py
"""

import jax
import numpy as np

from repro.config import VMConfig
from repro.core.vm import FleetVM, REXAVM
from repro.exec import Executive, ExecutiveConfig, install_services
from repro.launch.mesh import make_node_mesh
from repro.launch.cache import enable_compile_cache

CFG = VMConfig(cs_size=8192, steps_per_slice=2048)
ECFG = ExecutiveConfig(quantum=256, slices=8)

# The measuring daemon (slot 0, per sensor node): ping, sample, envelope,
# peak — then report (peak_idx, peak_amp) and send the peak to the collector.
MEASURE_JOB = """
( measuring daemon: active GUW ping + envelope + peak report )
0 1 800 100 dac          ( hamming sine burst on the actuator )
10 1 1 100 adc           ( start sampling: free trigger, 1kS, gain 1 )
1000 1 sampled await     ( suspend until conversion done or 1s timeout )
0< if ." timeout!" cr end endif
samples 0 64 400 hull    ( rectify + low-pass envelope, k=0.4 )
samples vecmax           ( peak index = time of flight )
dup out                  ( report peak position to the host stream )
dup samples get out      ( report peak amplitude )
{collector} send         ( and route the peak to the collector node )
"""

# The shell job (spawned, prio 2): a watchdog stamping the virtual clock
# onto the UART a few times while the daemon measures.  Heartbeats are
# tagged into the 8000+ band so they are separable from the daemon's
# peak reports in the shared streams.
SHELL_JOB = """
( shell watchdog: 3 heartbeats, 2 ms apart, over the UART service )
3 0 do 2 sleep ms 8000 + uart.write loop
"""

# The one-shot (spawned, prio 1): a single boot banner (9500+ band).
ONE_SHOT = "{banner} uart.write"

# The collector node: gather one peak per sensor over the mailbox ring.
COLLECT_JOB = """
( collector: receive n peaks, print "src peak" pairs )
{n} 0 do receive swap . . cr loop halt
"""


def wire_sensor(vm: REXAVM, defect_pos: float) -> None:
    """Attach the virtual ADC/DAC whose echo depends on the defect distance."""
    n = 64
    vm.dios_add("samples", np.zeros(n, np.int32))
    vm.dios_add("sampled", np.array([0], np.int32))

    def dac(wave, interval, ampl, freq):
        pass  # the actuator fires; physics happens below in adc

    def adc(trig, depth, gain, freq):
        t = np.arange(n)
        center = 10 + defect_pos * 40
        echo = np.sin(t / 1.5) * np.exp(-((t - center) ** 2) / 30.0) * 900
        noise = np.random.default_rng(int(defect_pos * 100)).normal(0, 30, n)
        vm.dios_write("samples", (echo + noise).astype(np.int32))
        vm.dios_write("sampled", [1])

    vm.svc_add("dac", dac, args=4, ret=0)
    vm.svc_add("adc", adc, args=4, ret=0)


def build_fleet(defects, mesh, executive=None):
    """The measuring fleet; with ``executive`` each sensor also gets the
    shell job + one-shot in its task table."""
    n_sensors = len(defects)
    collector = n_sensors                      # last fleet index
    fleet = FleetVM(CFG, n=n_sensors + 1, mesh=mesh, executive=executive)
    svcs = install_services(fleet.nodes) if executive is not None else None
    ex = Executive(fleet) if executive is not None else None
    for i, defect in enumerate(defects):
        node = fleet.nodes[i]
        wire_sensor(node, defect)
        node.launch(node.load(MEASURE_JOB.format(collector=collector)))
        if ex is not None:
            ex.spawn(i, SHELL_JOB, prio=2)
            ex.spawn(i, ONE_SHOT.format(banner=9500 + i), prio=1)
    fleet.nodes[collector].launch(
        fleet.nodes[collector].load(COLLECT_JOB.format(n=n_sensors))
    )
    return fleet, ex, svcs


def main():
    enable_compile_cache()
    defects = [0.1, 0.35, 0.6, 0.85]
    n_sensors = len(defects)
    collector = n_sensors

    # On a multi-chip host (a four-chip v5e, say) the node axis shards
    # across the chips; on one device the same code runs unsharded.
    # Non-divisible fleets replicate.
    mesh = make_node_mesh() if len(jax.devices()) > 1 else None

    fleet, ex, svcs = build_fleet(defects, mesh, executive=ECFG)
    res = fleet.run(max_rounds=500)
    assert all(s in ("done", "halt") for s in res.statuses), res.statuses

    def daemon_reports(vm):
        # uart.write tees into out_stream too; the shell/one-shot traffic
        # sits in the tagged 8000+ band, the daemon's peak reports below it.
        return [v for v in vm.out_stream if v < 8000]

    print("node  defect_pos  peak_idx  peak_amp  est_distance")
    for i, defect in enumerate(defects):
        peak_idx, peak_amp = daemon_reports(fleet.nodes[i])[:2]
        est = (peak_idx - 10) / 40
        print(f"n{int(defect*100):03d}  {defect:10.2f}  {peak_idx:8d}  "
              f"{peak_amp:8d}  {est:12.2f}")
    print(f"\ncollector (node {collector}) received via on-device routing:")
    print(res.outputs[collector])

    banners = [v for _, v in svcs.uart.stream if v >= 9500]
    beats = [(node, v) for node, v in svcs.uart.stream if v < 9500]
    estats = fleet.executive_stats()
    print(f"[exec] task table per sensor: measuring daemon (slot 0) + "
          f"shell watchdog (prio 2) + boot one-shot (prio 1)")
    print(f"[exec] {estats['task_switches']} task switches, "
          f"{estats['preemptions']} preemptions over {res.rounds} rounds "
          f"(quantum {ECFG.quantum} x {ECFG.slices} slices)")
    print(f"[uart] {len(banners)} boot banners, {len(beats)} heartbeats in "
          f"{svcs.uart.batches} vectorized batches "
          f"({estats['syscalls']} SVC rows, {estats['svc_batches']} batched "
          f"handler calls; {estats['svc_scalar_calls']} scalar callbacks "
          f"for the per-node ADC/DAC devices)")
    assert len(banners) == n_sensors
    assert len(beats) == 3 * n_sensors
    # All shared-service traffic is batched; only the per-node virtual
    # ADC/DAC devices (one dac + one adc call per sensor) stay scalar.
    assert svcs.uart.batches < svcs.uart.writes, "UART never batched"
    assert estats["svc_scalar_calls"] == 2 * n_sensors

    from repro.core.vm.vmstate import state_nbytes
    stats = fleet.transfer_stats()
    full_state = state_nbytes(fleet.nodes[0].state) * fleet.n
    print(f"[fleet] {res.rounds} rounds, "
          f"{fleet.h2d} h2d / {fleet.d2h} d2h full-state syncs "
          f"(vs {2 * res.rounds * (n_sensors + 1)} for per-slice host loops)")
    print(f"[fleet] vector IO service: {stats['io_services']} services, "
          f"{stats['io_nodes_serviced']} node-slices, "
          f"{stats['io_d2h_bytes'] + stats['io_h2d_bytes']} B moved "
          f"(full-state sync would move "
          f"{stats['io_services'] * 2 * full_state} B)")

    # Non-regression: the multi-task fleet must not pay more full-state
    # host<->device syncs than the pre-Executive single-task configuration.
    single, _, _ = build_fleet(defects, mesh, executive=None)
    sres = single.run(max_rounds=500)
    assert all(s in ("done", "halt") for s in sres.statuses), sres.statuses
    assert fleet.h2d <= single.h2d and fleet.d2h <= single.d2h, (
        "Executive fleet regressed full-state transfers: "
        f"{fleet.h2d}/{fleet.d2h} vs single-task {single.h2d}/{single.d2h}"
    )
    for i in range(n_sensors):
        assert daemon_reports(fleet.nodes[i])[:2] == single.nodes[i].out_stream, i
    print(f"[fleet] transfer non-regression vs single-task: "
          f"{fleet.h2d}/{fleet.d2h} <= {single.h2d}/{single.d2h} "
          f"full-state syncs (h2d/d2h)")


if __name__ == "__main__":
    main()
