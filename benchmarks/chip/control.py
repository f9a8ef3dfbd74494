"""Read the comparison's numbers for the program and for its control.

    python3 benchmarks/chip/control.py --workload <cell> --seconds <s> \\
        --seeds <a,b,c,...>

Runs the cell once at its own size and load on the TPU (set-up, warm-up
and a window of ``--seconds``) with the first seed, and prints the
numbers the program's answers give against the reference.  Then, for
every seed, it prints the numbers that the control gives when it stands
in for the program: the reference computed with one of the
configuration's guarantees broken (float arithmetic where the
configuration states 32-bit fixed point), over the same work (per node,
as many results as the program committed and printed; the work does not
depend on the seed).  Each line is one JSON object.  The benchmark's own
runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys

import run as cli


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]

    import jax

    import harness
    from repro.launch.cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("control.py: no TPU", file=sys.stderr)
        return 1
    enable_compile_cache()
    run = harness.measure(cli.ROOT, args.workload, seeds[0], args.seconds,
                          False)
    params = run["params"]
    program = harness.check(run["prog"], params, seeds[0], run["reads"])
    print(json.dumps({"workload": args.workload, "seed": seeds[0],
                      "side": "program", "numbers": program}), flush=True)
    for seed in seeds:
        numbers = harness.check(run["prog"], params, seed, run["reads"],
                                control=True)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "side": "control", "numbers": numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
