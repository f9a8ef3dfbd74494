"""Benchmark harness — one module per paper table (see DESIGN.md index).
Prints ``name,us_per_call,derived`` CSV rows per the assignment contract,
and writes one ``BENCH_<module>.json`` per module (rows + any structured
``METRICS`` the module filled, e.g. the VM fleet's steps/s, transfer and
byte counters) so the perf trajectory is tracked across PRs.

    PYTHONPATH=src python -m benchmarks.run [--only vm,ann,...] [--json-dir .]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

MODULES = ["lut", "resources", "efficiency", "vm", "ann", "kernels", "roofline"]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma list of modules")
    ap.add_argument("--json-dir", default=".",
                    help="directory for BENCH_<module>.json (\"\" disables)")
    args = ap.parse_args(argv)
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    names = args.only.split(",") if args.only else MODULES

    print("name,us_per_call,derived")
    failures = []
    for name in names:
        try:
            mod = __import__(f"benchmarks.bench_{name}", fromlist=["run"])
            rows = mod.run()
            for row_name, us, derived in rows:
                print(f"{row_name},{us:.2f},{derived}")
            sys.stdout.flush()
            if args.json_dir:
                payload = {
                    "module": name,
                    "rows": [
                        {"name": rn, "us_per_call": us, "derived": d}
                        for rn, us, d in rows
                    ],
                    "metrics": getattr(mod, "METRICS", {}),
                }
                path = os.path.join(args.json_dir, f"BENCH_{name}.json")
                with open(path, "w") as f:
                    json.dump(payload, f, indent=2)
        except Exception:
            traceback.print_exc()
            failures.append(name)
    if failures:
        print(f"# FAILED modules: {failures}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
