"""``chip_smoke.py`` rehearsed on the CPU at a tiny size.

The script's own entry point refuses every platform but ``tpu``; these
tests import its phase functions and drive them on an 8 x 8 torus (64
nodes, interpret-mode kernel), and the four-chip phase on a 4 x 4 torus
over four forced host devices, so tier-1 keeps guarding the script.
"""

import importlib.util
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.config import VMConfig

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_expected_output_is_independent_of_the_vm(smoke):
    # Node 0 of a 2 x 2 torus: left = 1, up = 2.
    acc = smoke.sent_right(1) + smoke.sent_down(2)
    for _ in range(smoke.LOOP):
        acc = (acc * 3 + 7) & 65535
    x, w = smoke.vec_x(0), smoke.mat_w(0)
    fold = [sum(x[i] * w[2 * i + j] for i in range(4)) for j in range(2)]
    want = [acc, 3, sum(a * b for a, b in zip(x, w[:4])), *fold]
    assert smoke.expected_output(0, 2) == "".join(f"{v} " for v in want)


def test_refuses_without_a_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    assert capsys.readouterr().out == ""


def test_fails_without_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ""}
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode != 0
    assert out.stdout == ""


def test_one_chip_phases(smoke, capsys):
    smoke.one_chip(8, VMConfig(), ref_side=8)
    phases = capsys.readouterr().out
    for name in ("setup", "batched", "reference", "pallas", "auto", "auto_plan"):
        assert f'"phase": "{name}"' in phases


def test_four_chip_phase_on_forced_devices():
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import sys
        sys.path.insert(0, {str(ROOT)!r})
        import chip_smoke
        from repro.config import VMConfig
        chip_smoke.four_chips(4, VMConfig())
        print("FOUR_CHIP_OK")
    """)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=900, cwd=ROOT,
    )
    assert "FOUR_CHIP_OK" in out.stdout, (out.stdout[-2000:], out.stderr[-4000:])
    for executor in ("batched", "pallas"):
        assert f'"phase": "{executor}_compare"' in out.stdout
