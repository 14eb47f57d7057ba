"""The benchmark's one command.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints one JSON object as the last line of standard output. Exits with
another code than 0, and prints no result, where JAX finds no TPU or
fewer chips than the cell asks for, or where the program is not there.
``--rehearse 1`` is the CPU rehearsal: the cell's kind at a tiny size
(tests/tiny/), marked as such, with no device metric.
"""

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from perfbench import harness

    manifest = harness.load_manifest()
    kw = {}
    if args.rehearse:
        import jax

        cell, _ = harness.find_cell(manifest, args.workload)
        kw = {"devices": jax.devices()[:cell["chips"]], "rehearsal": True}
    try:
        result = harness.run_cell(manifest, args.workload, args.seed,
                                  args.seconds, bool(args.trace),
                                  t_start=T_START, **kw)
    except harness.NoChipError as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 3
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
