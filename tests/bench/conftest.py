"""Fixtures for the chip benchmark's CPU tests.

``tiny_root`` is a checkout-like directory holding a copy of the
benchmark's files plus cells that exist only as new files there: small
configurations of both node programs (a 4 x 4 torus, 9 Tab. 10 nodes),
traffic mixes of their own (the real cells' mixes at 16 rounds a
read-out) and ``BENCHMARK.json`` entries for them.  The
harness finds them by name, as it finds the real cells.
"""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CHIP = ROOT / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

TINY = {
    # cell: (base config, config name, changes, traffic)
    "grid4.tiny": ("sensor_grid_128", "sensor_grid_4",
                   {"fleet": {"nodes": 16},
                    "params": {"side": 4, "window": 16, "pole_k": 700}},
                   "tiny_grid_r16"),
    "tab9.tiny": ("tinyml_tab10_16k", "tinyml_tab10_9",
                  {"fleet": {"nodes": 9}}, "tiny_tab_r16"),
}


def real_traffic(config: str) -> dict:
    """The traffic mix of the real cell that runs ``config``."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["config"] == config)
    return json.loads(
        (CHIP / "traffic" / f"{cell['traffic']}.json").read_text())


def make_root(dest: Path, cells=TINY) -> Path:
    """A benchmark root at ``dest`` with ``cells`` added as new files."""
    shutil.copytree(CHIP, dest / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    chip = dest / "benchmarks" / "chip"
    for cell, (base, name, changes, mix) in cells.items():
        (chip / "traffic" / f"{mix}.json").write_text(json.dumps(
            dict(real_traffic(base), rounds_per_readout=16)))
        cfg = json.loads((chip / "configs" / f"{base}.json").read_text())
        cfg.update(changes, name=name)
        (chip / "configs" / f"{name}.json").write_text(json.dumps(cfg))
        entry = next(c for c in bench["configs"] if c["name"] == base)
        bench["configs"].append(
            dict(entry, name=name, file=f"benchmarks/chip/configs/{name}.json"))
        real = next(w for w in bench["workloads"] if w["config"] == base)
        bench["workloads"].append(
            dict(real, name=cell, config=name, traffic=mix))
        for m in bench["end_to_end"] + bench["per_layer"]:
            if real["name"] in m.get("workloads", ()):
                m["workloads"].append(cell)
    (dest / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return dest


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))
