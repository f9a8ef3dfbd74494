"""The manifest loader: the repo's ``BENCHMARK.json`` and what it refuses."""

import copy
import json

import pytest

import manifest
from conftest import CHIP, ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_repo_manifest_and_every_file_it_names():
    bench = manifest.load(ROOT)
    for c in bench["configs"]:
        cfg = manifest.config(ROOT, bench, c["name"])
        assert (CHIP / "programs" / f"{cfg['program']}.py").exists()
    for w in bench["workloads"]:
        assert manifest.traffic(ROOT, w["traffic"])["rounds_per_readout"] > 0
        e2e = manifest.cell_metrics(bench, w["name"], trace=False)
        assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
        assert manifest.cell_metrics(bench, w["name"], trace=True)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(manifest.reader(ROOT, m["name"]).read)


def broken(path, value):
    bench = copy.deepcopy(BENCH)
    obj = bench
    for key in path[:-1]:
        obj = obj[key]
    obj[path[-1]] = value
    return bench


@pytest.mark.parametrize("path,value", [
    (("workloads", 0, "name"), "grid 128"),
    (("workloads", 0, "name"), "grid/128"),
    (("configs", 0, "reduced"), ["side,nodes"]),
    (("end_to_end", 0, "unit"), "periods per s"),
    (("per_layer", 0, "unit"), "µs"),
    (("per_layer", 0, "name"), "x" * 65),
    (("per_layer", 0, "better"), "faster"),
    (("end_to_end", 0, "source"), "program_counter"),
])
def test_rejects_names_and_units(path, value):
    with pytest.raises(ValueError):
        manifest.validate(broken(path, value))


def test_rejects_a_metric_whose_cells_do_not_report_what_it_moves():
    bench = copy.deepcopy(BENCH)
    grid = next(m for m in bench["per_layer"] if m["name"] == "start_ms.grid")
    grid["workloads"] = ["grid128.bulk", "tab10.bulk"]
    with pytest.raises(ValueError, match="do not report periods_per_s"):
        manifest.validate(bench)
    del grid["workloads"]         # then it is reported by every cell
    with pytest.raises(ValueError, match="do not report periods_per_s"):
        manifest.validate(bench)


def test_unknown_device_kind_is_an_error():
    import run
    assert run.device_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        run.device_peaks("TPU v9 imaginary")
