"""The harness, rehearsed on the CPU with the Python reference executor.

Cells that exist only as new files in a temporary root are found and run;
the command refuses a platform that is not a TPU; and with the timed path
broken underneath, ``correct`` comes out false.
"""

import os
import shutil
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import harness
from conftest import CHIP, ROOT
from repro.core.vm.fleet import FleetVM
from repro.core.vm.vmstate import VMState

SEED = 2**31 + 4093


def run_cell(root, cell, trace=False):
    run = harness.measure(root, cell, SEED, 0.0, trace, executor="oracle")
    return harness.result(root, run, trace)


@pytest.mark.parametrize("cell,trace", [("grid4.tiny", True),
                                        ("tab9.tiny", False)])
def test_cell_from_new_files_runs(tiny_root, cell, trace):
    out = run_cell(tiny_root, cell, trace)
    assert out["correct"] and out["attempted"] > 0 and out["failed"] == 0
    names = set(out["metrics"])
    if trace:
        assert {"start_ms.grid", "round_device_ms.grid",
                "idle_share.grid"} <= names
        assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
        assert out["breakdown"]["device_ops"]
    else:
        assert names == {"inferences_per_s", "setup_s"}
    assert list(out)[-1] == "check"
    assert all(v == {"value": 0, "limit": 0} for v in out["check"].values())


def test_refuses_without_a_tpu(capsys):
    import run
    args = ["--workload", "grid128.bulk", "--seed", "1", "--seconds", "1"]
    assert run.main(args) == 1
    assert capsys.readouterr().out == ""


def test_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip")
    shutil.copytree(ROOT / "tests" / "bench", tmp_path / "tests" / "bench")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ""}
    out = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "grid128.bulk", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode != 0 and out.stdout == ""
    assert "repro" in out.stderr


# --- faults planted under the timed path ---------------------------------------

class _Broken:
    """The fleet's kernels with ``round`` replaced (the rest delegated)."""

    round_aux = rounds_aux = round_exec = None

    def __init__(self, inner, fault):
        self._inner = inner
        self.round = fault(inner.round)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def _unchanged(inner):
    return lambda S, steps: S


def _half(inner):
    def rnd(S, steps):
        new, n = inner(S, steps), S.pc.shape[0]
        keep = np.arange(n) < n // 2
        return VMState(*[
            jnp.where(keep.reshape((n,) + (1,) * (a.ndim - 1)), a, b)
            for a, b in zip(new, S)
        ])
    return rnd


def _no_exchange(inner):
    def rnd(S, steps):
        new = inner(S, steps)
        return new._replace(mbox=S.mbox, mbox_rd=S.mbox_rd, mbox_wr=S.mbox_wr)
    return rnd


def _altered(inner):
    def rnd(S, steps):
        new = inner(S, steps)
        return new._replace(out=new.out.at[1, 1].add(1))
    return rnd


FAULTS = {"unchanged": _unchanged, "half": _half,
          "no_exchange": _no_exchange, "altered": _altered}


@pytest.mark.parametrize("cell,fault", [
    ("grid4.tiny", "unchanged"), ("grid4.tiny", "half"),
    ("grid4.tiny", "no_exchange"), ("grid4.tiny", "altered"),
    ("tab9.tiny", "unchanged"), ("tab9.tiny", "half"),
    ("tab9.tiny", "altered"),
])
def test_fault_under_the_timed_path_is_not_correct(tiny_root, monkeypatch,
                                                   cell, fault):
    start = FleetVM.start

    def broken_start(self):
        start(self)
        if not isinstance(self.kernels, _Broken):
            self.kernels = _Broken(self.kernels, FAULTS[fault])

    monkeypatch.setattr(FleetVM, "start", broken_start)
    out = run_cell(tiny_root, cell)
    assert not out["correct"]


def test_control_in_place_of_the_program_is_not_correct(tiny_root):
    run = harness.measure(tiny_root, "grid4.tiny", SEED, 0.0, False,
                          executor="oracle")
    numbers = harness.check(run["prog"], run["params"], SEED, run["reads"],
                            control=True)
    assert any(numbers[k] > harness.LIMITS[k] for k in harness.LIMITS)
