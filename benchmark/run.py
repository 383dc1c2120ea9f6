#!/usr/bin/env python3
"""The benchmark's command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 benchmark/run.py --check-manifest

One process holds the chip: it starts the node behind its HTTP server,
builds or reopens the stored base index of (configuration, seed), warms
the cell's own shapes, and lets a load generator in a process of its own
drive the window over HTTP. The last line of standard output is the
result; the numbers compared, each beside its limit, are its last key
and the last lines of standard error. No TPU, or fewer chips than the
cell asks for: no result and a non-zero exit.
"""

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload")
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--check-manifest", action="store_true",
                   help="check BENCHMARK.json and the files it names; "
                        "no chip, no JAX")
    p.add_argument("--control", default=None, metavar="NAME",
                   help="put a control in the program's place in the "
                        "comparison: the run must come out not correct. "
                        "One the cell's reference states (references/"
                        "<name>.py, controls), or lost_ack where the cell "
                        "writes. Never set by the driver")
    p.add_argument("--keep-trace", default=None, metavar="FILE",
                   help="with --trace 1: write the first events of every "
                        "line of the trace there, as JSON")
    return p.parse_args(argv)


def main(argv=None) -> int:
    from harness import manifest_check

    args = parse_args(argv)
    faults = manifest_check.check(ROOT)
    if faults:
        print("BENCHMARK.json is not to the contract:\n  "
              + "\n  ".join(faults), file=sys.stderr, flush=True)
        return 2
    if args.check_manifest:
        print("manifest: sound", flush=True)
        return 0
    manifest = manifest_check.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    if args.workload is None:
        print("--workload is required", file=sys.stderr)
        return 2
    if args.seconds is None:
        args.seconds = float(manifest["run_seconds"])
    from harness import run_cell, server

    try:
        result = run_cell.run(args, manifest, T_PROCESS)
    except server.HarnessFailure as e:
        print(f"benchmark FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    for name, n in result["compared"].items():
        print(f"compared {name}: {n['value']!r} limit {n['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # the program's threads and native handles are not the result's to
    # wait for: every process this run started has been waited for
    os._exit(code)
