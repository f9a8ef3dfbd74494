"""The chip benchmark's one command.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the TPU this process holds and
prints, as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``check``, the numbers compared with the
reference beside their limits (also the last lines of standard error).

It refuses to measure on anything but a TPU, and on fewer chips than the
cell asks for: it then exits 1 and prints nothing on standard output.
The compile cache is ``JAX_COMPILATION_CACHE_DIR`` when set, else
``.jax_cache`` in the checkout (``repro.launch.cache``).
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))


def device_peaks(kind: str) -> dict:
    """The published peaks of ``kind`` from ``devices.json``."""
    with open(HERE / "devices.json") as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in devices.json")
    return table[kind]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    import harness
    import manifest
    from repro.launch.cache import enable_compile_cache

    cell = manifest.workload(manifest.load(ROOT), args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"run.py: {args.workload} needs {cell['chips']} TPU chip(s); "
              f"JAX has {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 1
    device_peaks(devs[0].device_kind)

    enable_compile_cache()
    run = harness.measure(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace))
    print(json.dumps(harness.result(ROOT, run, bool(args.trace))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
