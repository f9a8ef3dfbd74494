"""Each node program's Forth, run through ``FleetVM`` on the CPU, agrees
with its numpy reference on counters, checksums and every printed value,
and the lower-precision control does not."""

import ast
import json

import pytest

import harness
import manifest
from conftest import CHIP, ROOT, real_traffic

SEED = 2**31 + 977          # wider than 32 signed bits
CASES = {
    "sensor_grid_128": {"fleet": {"nodes": 64},
                        "params": {"side": 8, "window": 16, "pole_k": 700}},
    "tinyml_tab10_16k": {"fleet": {"nodes": 18}},
}


def small(config: str) -> dict:
    cfg = json.loads((CHIP / "configs" / f"{config}.json").read_text())
    cfg.update(CASES[config])
    return cfg


@pytest.fixture(scope="module", params=sorted(CASES))
def ran(request):
    cfg = small(request.param)
    params = harness.cell_params(cfg, real_traffic(request.param))
    prog = manifest.program(ROOT, cfg["program"])
    fleet = harness.build_fleet(cfg, params, prog, SEED, "oracle")
    reads = [harness.readout(fleet, 16, prog.STATE) for _ in range(3)]
    return params, prog, reads


def test_program_agrees_with_reference(ran):
    params, prog, reads = ran
    counts = reads[-1].state[:, 0]
    assert (counts > 0).all()
    assert harness.check(prog, params, SEED, reads) == {
        k: 0 for k in harness.LIMITS
    }
    printed, checksums = prog.reference(params, SEED, len(counts),
                                        int(counts.max()))
    for i, c in enumerate(counts):
        got = [v for r in reads for v in harness.parse(r.outputs[i])]
        assert len(got) in (c, c - 1)
        assert got == list(printed[i, :len(got)])
        assert reads[-1].state[i, 1] == checksums[i, c]


def test_control_fails(ran):
    params, prog, reads = ran
    numbers = harness.check(prog, params, SEED, reads, control=True)
    assert numbers["wrong_outputs"] + numbers["wrong_checksums"] > 0


@pytest.mark.parametrize("name", ["sensor_grid", "tinyml_ann"])
def test_reference_imports_nothing_of_the_program(name):
    tree = ast.parse((CHIP / "programs" / f"{name}.py").read_text())
    imported = {
        alias.name.split(".")[0]
        for node in ast.walk(tree) if isinstance(node, ast.Import)
        for alias in node.names
    } | {
        node.module.split(".")[0]
        for node in ast.walk(tree) if isinstance(node, ast.ImportFrom)
        if node.module
    }
    assert imported <= {"__future__", "math", "numpy"}


def test_sigmoid_reference_at_paper_points():
    prog = manifest.program(ROOT, "tinyml_ann")
    xs = [0, 1000, -1000, 2000, 5000, 9999, 10000, -10000, 20000]
    ys = prog.sigmoid(xs)
    assert ys[0] == 500 and ys[6] == 1000 and ys[7] == 0 and ys[8] == 1000
    assert ys[1] + ys[2] == 1000
    assert all(abs(int(y) - 1000 / (1 + 2.718281828 ** (-x / 1000))) < 25
               for x, y in zip(xs, ys))


def test_grid_thresholds_are_one_multiset_for_every_seed():
    prog = manifest.program(ROOT, "sensor_grid")
    params = harness.cell_params(small("sensor_grid_128"),
                                 real_traffic("sensor_grid_128"))
    a = prog.thresholds(params, SEED, 64)
    b = prog.thresholds(params, SEED + 1, 64)
    assert sorted(a) == sorted(b) and list(a) != list(b)
    shares = dict(params["thresholds"])
    assert {d: int((a == d).sum()) for d in shares} == {
        d: 64 * s // sum(shares.values()) for d, s in shares.items()}


def test_grid_sends_are_skewed_and_out_of_lockstep():
    cfg = small("sensor_grid_128")
    params = harness.cell_params(cfg, real_traffic("sensor_grid_128"))
    prog = manifest.program(ROOT, "sensor_grid")
    fleet = harness.build_fleet(cfg, params, prog, SEED, "oracle")
    reads = [harness.readout(fleet, 16, prog.STATE) for _ in range(3)]
    counts = reads[-1].state[:, 0]
    assert counts.min() < counts.max()        # nodes drift apart
    n = len(counts)
    events = 0
    delta = prog.thresholds(params, SEED, n)
    printed, _ = prog.reference(params, SEED, n, int(counts.max()))
    ref = 0
    for p in range(printed.shape[1]):
        ev = abs(printed[:, p] - ref) > delta
        ref = printed[:, p] * ev + ref * ~ev
        events += ev
    burst_share = events / printed.shape[1]
    low = delta == min(delta)
    assert burst_share[low].mean() > 2 * burst_share[~low].mean()


@pytest.mark.parametrize("burst", [0, 5])
def test_grid_refuses_a_burst_its_mailboxes_cannot_hold(burst):
    prog = manifest.program(ROOT, "sensor_grid")
    params = harness.cell_params(small("sensor_grid_128"),
                                 real_traffic("sensor_grid_128"))
    with pytest.raises(ValueError, match="burst"):
        prog.firmwares(dict(params, burst=burst), SEED)
