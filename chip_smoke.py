"""Bring-up check: the REXAVM fleet's main path on one TPU chip.

Runs a 128 x 128 torus of sensor nodes (16,384 REXAVMs at the default
``VMConfig``, about 0.82 GB of device state) through ``FleetVM`` the way
the README and ``examples/sensor_node.py`` drive it: every node gets
``load`` and ``launch``, then the fleet ``run``s.  Each node sends a value
derived from its index to its right and its lower neighbour, receives
twice, runs a counted compute loop and two TinyML words (``dotprod`` and
``vecfold``), and prints.  Every node's output is checked against values
computed here in numpy, and an 8 x 8 torus is checked byte for byte
against the host reference (``reference_round``).

Phases, in one process:

1. device — fail unless JAX's first device is a TPU;
2. batched — the default executor, plus the 8 x 8 reference check;
3. pallas — the compiled ``kernels/vmloop`` kernel (never the
   interpreter);
4. auto — the Auditor picks the executor; its choice and reasons print.

``--four-chips`` runs only the sharded phase instead: the same fleet on a
4-chip node mesh (4,096 nodes per chip) under the pallas and the batched
executors, each compared byte for byte with the fleet run unsharded on
the first chip.

Each phase prints its set-up, compile and run seconds, node count, device
bytes and the device's ``peak_bytes_in_use``.  The last line of standard
output is ``{"ok": true, "device": {...}}``; any failure raises and exits
non-zero.

    python chip_smoke.py              # one chip
    python chip_smoke.py --four-chips # a four-chip host

The compile cache is ``JAX_COMPILATION_CACHE_DIR`` when set, else
``.jax_cache`` in the checkout.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.config import VMConfig  # noqa: E402
from repro.core.vm import FleetVM, REXAVM, reference_round  # noqa: E402
from repro.core.vm.vmstate import VMState, state_nbytes  # noqa: E402

SIDE = 128                 # 128 x 128 torus = 16,384 nodes
LOOP = 4                   # iterations of the counted compute loop
MAX_ROUNDS = 64


# --- the program and its independent expectation -----------------------------

def neighbours(i: int, side: int) -> tuple[int, int, int, int]:
    """(right, down, left, up) of node i on a side x side torus."""
    r, c = divmod(i, side)
    return (
        r * side + (c + 1) % side,
        ((r + 1) % side) * side + c,
        r * side + (c - 1) % side,
        ((r - 1) % side) * side + c,
    )


def sent_right(i: int) -> int:
    return 7 * i + 3


def sent_down(i: int) -> int:
    return 13 * i + 5


def vec_x(i: int) -> list[int]:
    return [(i + k) % 97 - 48 for k in range(4)]


def mat_w(i: int) -> list[int]:
    """A 4 x 2 weight matrix, row-major."""
    return [(3 * i + 5 * k) % 31 - 15 for k in range(8)]


def program(i: int, side: int) -> str:
    right, down, _, _ = neighbours(i, side)
    x = " ".join(map(str, vec_x(i)))
    w = " ".join(map(str, mat_w(i)))
    return (
        f"array x {{ {x} }} array w {{ {w} }} array y 2 "
        f"{sent_right(i)} {right} send {sent_down(i)} {down} send "
        # ( src1 v1 src2 v2 ) -> v1 + v2 through the loop, then src1 + src2
        f"receive receive rot + {LOOP} 0 do 3 * 7 + 65535 and loop . + . "
        f"x w dotprod . x w y 0 vecfold y vecprint halt"
    )


def expected_output(i: int, side: int) -> str:
    """Node i's printed output, computed without the VM."""
    _, _, left, up = neighbours(i, side)
    acc = np.int64(sent_right(left) + sent_down(up))
    for _ in range(LOOP):
        acc = (acc * 3 + 7) & 65535
    x = np.array(vec_x(i), np.int64)
    w = np.array(mat_w(i), np.int64)
    # dotprod reads w over x's length; vecfold folds x through w as 4 x 2.
    vals = [int(acc), left + up, int(x @ w[:4]), *(x @ w.reshape(4, 2))]
    return "".join(f"{v} " for v in vals)


# --- phases -------------------------------------------------------------------

class CompileClock:
    """Sums the XLA backend-compile seconds JAX's monitoring reports."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.total = 0.0

        def listen(name, secs, **_):
            if name == self.EVENT:
                self.total += secs
        jax.monitoring.register_event_duration_secs_listener(listen)

    def since(self, start: float) -> float:
        return self.total - start


def build_nodes(side: int, cfg: VMConfig) -> tuple[FleetVM, list[dict]]:
    """A batched fleet with every node loaded and launched, plus each
    node's checkpoint so later phases start from the same state."""
    fleet = FleetVM(cfg, n=side * side)
    for i, node in enumerate(fleet.nodes):
        node.launch(node.load(program(i, side)))
    return fleet, [node.checkpoint() for node in fleet.nodes]


def refleet(nodes, ckpts, **kw) -> FleetVM:
    for node, ck in zip(nodes, ckpts):
        node.restore(ck)
    return FleetVM.from_nodes(nodes, **kw)


def check_outputs(res, side: int) -> None:
    n = side * side
    assert res.statuses == ["halt"] * n, sorted(set(res.statuses))
    bad = [
        i for i in range(n) if res.outputs[i] != expected_output(i, side)
    ]
    assert not bad, (
        f"{len(bad)} nodes disagree with numpy, first {bad[0]}: "
        f"{res.outputs[bad[0]]!r} != {expected_output(bad[0], side)!r}"
    )


def device_peak() -> int:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def run_phase(name: str, fleet: FleetVM, side: int, clock: CompileClock,
              setup_s: float) -> dict:
    c0, t0 = clock.total, time.perf_counter()
    res = fleet.run(max_rounds=MAX_ROUNDS)
    run_s = time.perf_counter() - t0
    check_outputs(res, side)
    line = {
        "phase": name,
        "executor": fleet.executor_kind,
        "n": fleet.n,
        "rounds": res.rounds,
        "setup_s": setup_s,
        "compile_s": clock.since(c0),
        "run_s": run_s,
        "device_bytes": fleet.n * state_nbytes(fleet.nodes[0].state),
        "peak_bytes_in_use": device_peak(),
    }
    print(json.dumps(line), flush=True)
    return line


def reference_check(side: int, cfg: VMConfig) -> None:
    """The batched fleet is byte-exact against reference_round."""
    fleet, _ = build_nodes(side, cfg)
    res = fleet.run(max_rounds=MAX_ROUNDS)
    ref = [REXAVM(cfg, backend="jit", seed=1 + i) for i in range(side * side)]
    for i, node in enumerate(ref):
        node.launch(node.load(program(i, side)))
    for _ in range(res.rounds):
        reference_round(ref, cfg.steps_per_slice)
    for i, (a, b) in enumerate(zip(fleet.nodes, ref)):
        for f in VMState._fields:
            if f in ("out", "outp"):       # run() drained the fleet's rings
                continue
            assert np.array_equal(
                np.asarray(getattr(a.state, f)), np.asarray(getattr(b.state, f))
            ), f"node {i} field {f} differs from reference_round"
    assert res.outputs == [vm.output() for vm in ref]
    check_outputs(res, side)
    print(json.dumps({"phase": "reference", "n": side * side,
                      "rounds": res.rounds, "byte_exact": True}), flush=True)


def one_chip(side: int, cfg: VMConfig, ref_side: int) -> None:
    import jax

    clock = CompileClock()
    t0 = time.perf_counter()
    fleet, ckpts = build_nodes(side, cfg)
    setup_s = time.perf_counter() - t0
    print(json.dumps({"phase": "setup", "n": fleet.n, "setup_s": setup_s}),
          flush=True)
    run_phase("batched", fleet, side, clock, setup_s)
    reference_check(ref_side, cfg)

    t0 = time.perf_counter()
    pallas = refleet(fleet.nodes, ckpts, executor="pallas")
    # Compiled on a TPU; only a CPU rehearsal runs the Pallas interpreter.
    on_tpu = jax.default_backend() == "tpu"
    assert pallas.kernels.executor.interpret is not on_tpu, "vmloop mode"
    run_phase("pallas", pallas, side, clock, time.perf_counter() - t0)

    t0 = time.perf_counter()
    auto = refleet(fleet.nodes, ckpts, executor="auto")
    line = run_phase("auto", auto, side, clock, time.perf_counter() - t0)
    stats = auto.analysis_stats()
    print(json.dumps({"phase": "auto_plan", "executor": line["executor"],
                      "elide_checks": stats["elide_checks"],
                      "reasons": stats["reasons"]}), flush=True)


def four_chips(side: int, cfg: VMConfig, n_dev: int = 4) -> None:
    """The sharded fleet against the same fleet run on the first chip.

    The one-chip run uses the pallas executor (the batched one takes
    minutes per round at this size on a v5e); the sharded runs use the
    pallas and the batched executors, and each must match it byte for
    byte (every executor is byte-exact, so one reference serves both)."""
    from repro.launch.mesh import make_node_mesh

    clock = CompileClock()
    t0 = time.perf_counter()
    fleet, ckpts = build_nodes(side, cfg)
    setup_s = time.perf_counter() - t0
    n = fleet.n
    per = n // n_dev
    # Rows cross shard boundaries: some node's lower neighbour lives on
    # another chip, so the router moves messages between chips.
    crossing = sum(
        i // per != neighbours(i, side)[1] // per for i in range(n)
    )
    assert crossing > 0, "no message crosses a shard boundary"
    single = refleet(fleet.nodes, ckpts, executor="pallas")
    run_phase("pallas_one_chip", single, side, clock, setup_s)
    want = [nd.checkpoint()["state"] for nd in single.nodes]
    mesh = make_node_mesh(n_dev)
    for executor in ("pallas", "batched"):
        t0 = time.perf_counter()
        sharded = refleet(fleet.nodes, ckpts, executor=executor, mesh=mesh)
        sharded.start()
        S = sharded._S
        assert len(S.cs.sharding.device_set) == n_dev
        assert [s.data.shape[0] for s in S.cs.addressable_shards] == [per] * n_dev
        line = run_phase(f"{executor}_sharded", sharded, side, clock,
                         time.perf_counter() - t0)
        for i, nd in enumerate(sharded.nodes):
            for f in VMState._fields:
                assert np.array_equal(
                    np.asarray(getattr(nd.state, f)), getattr(want[i], f)
                ), f"node {i} field {f}: sharded run differs from one chip"
        print(json.dumps({"phase": f"{executor}_compare", "n": n,
                          "shards": n_dev, "nodes_per_shard": per,
                          "crossing_links": crossing,
                          "rounds": line["rounds"], "byte_exact": True}),
              flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded phase")
    args = ap.parse_args(argv)

    import jax

    from repro.launch.cache import enable_compile_cache

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX's first device is {dev.platform})",
              file=sys.stderr)
        return 1
    enable_compile_cache()
    cfg = VMConfig()
    if args.four_chips:
        if len(devs) < 4:
            print(f"chip_smoke: --four-chips needs 4 devices, found "
                  f"{len(devs)}", file=sys.stderr)
            return 1
        four_chips(SIDE, cfg)
    else:
        one_chip(SIDE, cfg, ref_side=8)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devs),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
