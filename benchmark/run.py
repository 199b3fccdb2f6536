"""Run one benchmark cell on the chips this machine holds.

    python benchmark/run.py --workload raft5.sweep --seed 7 --seconds 10 \
        --trace 0

The last line of standard output is the result: {"correct", "attempted",
"failed", "metrics", "device", ["breakdown"], "checks"}. With no TPU, or
fewer chips than the cell asks for, it exits 2 and prints no result.
`--control NAME` puts the configuration's control (a planted break of
one of its guarantees) in the program's place; the benchmark's own runs
never pass it.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.lib import harness  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", default=None)
    args = p.parse_args(argv)
    harness.prepare_env()
    harness.configure_jax()
    try:
        res = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), control=args.control,
                               t_start=T_START)
    except harness.NoChip as e:
        harness.say(f"benchmark: {e}")
        return 2
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
