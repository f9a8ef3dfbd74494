"""Mesh-sharded fleet tests.

The acceptance case: a 64-node ring partitioned 8-ways over a forced-host-
device CPU mesh (``--xla_force_host_platform_device_count=8``) must stay
byte-exact with ``reference_round``, and the partial-state IO service must
move only the suspended nodes' slices.  The multi-device run lives in a
subprocess (same idiom as test_sharding.py) so the forced device count
cannot leak into the rest of the suite; the single-device mesh path is
covered in-process.
"""

import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.config import VMConfig
from repro.core.vm import FleetVM, REXAVM, reference_round
from repro.core.vm.vmstate import VMState
from repro.launch.mesh import make_node_mesh

CFG = VMConfig(cs_size=2048, steps_per_slice=64, mbox_size=4)


class TestSingleDeviceMesh:
    def test_mesh_fleet_matches_unsharded(self):
        """A 1-device node mesh exercises the constraint-wired kernels; the
        result must equal the meshless fleet byte-for-byte."""
        progs = ["1 1 send receive swap . . halt",
                 "receive swap . 1+ 0 send halt"]

        def build(mesh):
            fleet = FleetVM(CFG, n=len(progs), mesh=mesh)
            for node, prog in zip(fleet.nodes, progs):
                node.launch(node.load(prog))
            return fleet

        meshed, plain = build(make_node_mesh(1)), build(None)
        assert meshed.kernels is not plain.kernels  # separate (cfg, mesh) key
        r1 = meshed.run(max_rounds=20)
        r2 = plain.run(max_rounds=20)
        assert r1.outputs == r2.outputs
        assert r1.statuses == r2.statuses == ["halt", "halt"]
        for a, b in zip(meshed.nodes, plain.nodes):
            for f in VMState._fields:
                assert np.array_equal(
                    np.asarray(getattr(a.state, f)),
                    np.asarray(getattr(b.state, f)),
                ), f

    def test_divisible_fleet_gets_node_spec(self):
        """A divisible fleet shards its leading axis over "node" (the
        non-divisible replication fallback needs >1 device and is asserted
        in the subprocess test below)."""
        from jax.sharding import PartitionSpec

        fleet = FleetVM(CFG, n=3, mesh=make_node_mesh(1))
        assert fleet._sharding.spec == PartitionSpec("node")
        for node in fleet.nodes:
            node.launch(node.load("1 . halt"))
        res = fleet.run(max_rounds=10)
        assert res.outputs == ["1 "] * 3


@pytest.mark.slow
def test_sharded_64_ring_subprocess():
    """Own process so the forced 8-device count can't leak into other tests.

    Asserts (1) the stacked state is genuinely 8-way sharded on the node
    axis, (2) the 64-node ring is byte-exact vs the host-routed
    ``reference_round``, (3) the partial IO service moves exactly the
    suspended fraction of the fleet state."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import sys; sys.path.insert(0, "src")
        import numpy as np
        import jax
        from repro.config import VMConfig
        from repro.core.vm import FleetVM, REXAVM, reference_round
        from repro.core.vm.vmstate import VMState, state_nbytes
        from repro.launch.mesh import make_node_mesh

        assert len(jax.devices()) == 8
        mesh = make_node_mesh()
        CFG = VMConfig(cs_size=2048, steps_per_slice=64, mbox_size=4)
        n = 64

        def prog(i):
            if i == 0:
                return f"1 {1 % n} send receive swap . . halt"
            return f"receive swap . 1+ {(i + 1) % n} send halt"

        fleet = FleetVM(CFG, n=n, mesh=mesh)
        for i, node in enumerate(fleet.nodes):
            node.launch(node.load(prog(i)))
        fleet.start()
        sh = fleet._S.pc.sharding
        assert len(sh.device_set) == 8, sh
        shapes = {s.data.shape for s in fleet._S.pc.addressable_shards}
        assert shapes == {(n // 8, CFG.max_tasks)}, shapes
        res = fleet.run(max_rounds=300)
        assert res.statuses == ["halt"] * n
        assert res.outputs[0] == f"{n - 1} {n} "
        assert fleet.h2d == 1 and fleet.d2h == 1
        print("SHARDED_RUN_OK")

        ref = [REXAVM(CFG, backend="jit", seed=1 + i) for i in range(n)]
        for i, node in enumerate(ref):
            node.launch(node.load(prog(i)))
        for _ in range(res.rounds):
            reference_round(ref, CFG.steps_per_slice)
        for i in range(n):
            for f in VMState._fields:
                if f in ("out", "outp"):   # fleet.run() drained its rings
                    continue
                av = np.asarray(getattr(fleet.nodes[i].state, f))
                bv = np.asarray(getattr(ref[i].state, f))
                assert np.array_equal(av, bv), (i, f)
        assert res.outputs == [vm.output() for vm in ref]
        print("BYTE_EXACT_OK")

        # Partial IO under sharding: 2-of-8 nodes suspend on a FIOS call;
        # the service gathers/scatters exactly those slices cross-shard.
        fl = FleetVM(CFG, n=8, mesh=mesh)
        for i, node in enumerate(fl.nodes):
            if i < 2:
                node.dios_add("ready", np.array([0], np.int32))
                node.fios_add(
                    "ping", lambda node=node: node.dios_write("ready", [1])
                )
                node.launch(node.load("ping 1000 1 ready await drop 5 . halt"))
            else:
                node.launch(node.load("0 50 0 do 1+ loop . halt"))
        r = fl.run(max_rounds=60)
        assert r.statuses == ["halt"] * 8, r.statuses
        svc = fl.io_service
        assert svc.services >= 1 and svc.nodes_serviced >= 2
        per_node = state_nbytes(fl.nodes[0].state)
        assert fl.io_d2h_bytes == svc.nodes_serviced * per_node
        assert fl.io_d2h_bytes < svc.services * 8 * per_node  # < full syncs
        print("PARTIAL_IO_SHARDED_OK")

        # Non-divisible fleet (6 nodes, 8 devices) replicates but still runs.
        from jax.sharding import PartitionSpec
        fl6 = FleetVM(CFG, n=6, mesh=mesh)
        assert fl6._sharding.spec == PartitionSpec(), fl6._sharding
        for node in fl6.nodes:
            node.launch(node.load("1 . halt"))
        r6 = fl6.run(max_rounds=10)
        assert r6.outputs == ["1 "] * 6
        print("REPLICATE_FALLBACK_OK")
    """)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=900, cwd=".",
    )
    for marker in ("SHARDED_RUN_OK", "BYTE_EXACT_OK", "PARTIAL_IO_SHARDED_OK",
                   "REPLICATE_FALLBACK_OK"):
        assert marker in out.stdout, (out.stdout[-2000:], out.stderr[-4000:])
