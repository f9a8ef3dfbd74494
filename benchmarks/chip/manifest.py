"""Load ``BENCHMARK.json`` and find each cell's files by name.

Everything that belongs to one configuration, traffic mix, node program or
metric is a file of its own under ``benchmarks/chip/``:

- ``configs/<config>.json`` (the path ``BENCHMARK.json`` gives): fleet
  shape, ``VMConfig``, the node program's name and its parameters;
- ``traffic/<traffic>.json``: rounds per read-out and warm-up read-outs;
- ``programs/<program>.py``: the Forth firmware and its numpy reference;
- ``metrics/<metric>.py``, or ``metrics/<base>.py`` for a metric named
  ``<base>.<cell group>``: a reader ``read(ctx) -> float | None``.

So a cell is added by adding files and a ``workloads`` entry.
"""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
E2E_SOURCES = {"device_trace", "host_clock"}


def chip_dir(root: Path) -> Path:
    return Path(root) / "benchmarks" / "chip"


def _name(value, what: str) -> str:
    if not isinstance(value, str) or not NAME.fullmatch(value):
        raise ValueError(f"{what}: {value!r} is not a valid name")
    return value


def _unique(items, what: str) -> None:
    names = [it["name"] for it in items]
    if len(names) != len(set(names)):
        raise ValueError(f"duplicate {what} names: {names}")


def reports(metric: dict, workloads: list[dict]) -> list[str]:
    """The cells that report ``metric``."""
    if "workloads" in metric:
        return list(metric["workloads"])
    return [w["name"] for w in workloads]


def validate(bench: dict) -> dict:
    """Raise ``ValueError`` on a manifest that breaks the naming rules or
    whose per-layer metrics point at cells that do not report the
    end-to-end metric they move; return the manifest."""
    configs, workloads = bench["configs"], bench["workloads"]
    e2e, layer = bench["end_to_end"], bench["per_layer"]
    for what, items in (("config", configs), ("workload", workloads),
                        ("metric", e2e + layer)):
        _unique(items, what)
        for it in items:
            _name(it["name"], what)
    config_names = {c["name"] for c in configs}
    for c in configs:
        for key in c["reduced"]:
            _name(key, f"config {c['name']} reduced key")
    cells = {w["name"] for w in workloads}
    for w in workloads:
        _name(w["traffic"], f"workload {w['name']} traffic")
        if w["config"] not in config_names:
            raise ValueError(f"workload {w['name']}: unknown config {w['config']!r}")
        if w["chips"] not in (1, 4):
            raise ValueError(f"workload {w['name']}: chips must be 1 or 4")
    for m in e2e + layer:
        if not UNIT.fullmatch(m["unit"]):
            raise ValueError(f"metric {m['name']}: unit {m['unit']!r} is not valid")
        if m["better"] not in ("lower", "higher"):
            raise ValueError(f"metric {m['name']}: better must be lower or higher")
        allowed = E2E_SOURCES if m in e2e else SOURCES
        if m["source"] not in allowed:
            raise ValueError(f"metric {m['name']}: source {m['source']!r}")
        unknown = set(reports(m, workloads)) - cells
        if unknown:
            raise ValueError(f"metric {m['name']}: unknown cells {sorted(unknown)}")
    by_e2e = {m["name"]: set(reports(m, workloads)) for m in e2e}
    for m in layer:
        if m["moves"] not in by_e2e:
            raise ValueError(f"metric {m['name']}: moves unknown {m['moves']!r}")
        missing = set(reports(m, workloads)) - by_e2e[m["moves"]]
        if missing:
            raise ValueError(
                f"metric {m['name']}: cells {sorted(missing)} do not report "
                f"{m['moves']}, the metric it moves"
            )
    return bench


def load(root: Path) -> dict:
    with open(Path(root) / "BENCHMARK.json") as f:
        return validate(json.load(f))


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(root: Path, bench: dict, name: str) -> dict:
    entry = next(c for c in bench["configs"] if c["name"] == name)
    with open(Path(root) / entry["file"]) as f:
        return json.load(f)


def traffic(root: Path, name: str) -> dict:
    with open(chip_dir(root) / "traffic" / f"{name}.json") as f:
        return json.load(f)


def _module(path: Path, prefix: str):
    spec = importlib.util.spec_from_file_location(
        f"{prefix}_{path.stem.replace('.', '_')}", path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def program(root: Path, name: str):
    return _module(chip_dir(root) / "programs" / f"{_name(name, 'program')}.py",
                   "bench_program")


def reader(root: Path, metric: str):
    """The reader module of ``metric``: ``metrics/<metric>.py``, else the
    one of its base name (the part before the first dot)."""
    d = chip_dir(root) / "metrics"
    path = d / f"{metric}.py"
    if not path.exists():
        path = d / f"{metric.split('.')[0]}.py"
    return _module(path, "bench_metric")


def cell_metrics(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a cell reports: end-to-end ones untraced, per-layer
    ones traced."""
    pool = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in pool if cell in reports(m, bench["workloads"])]
