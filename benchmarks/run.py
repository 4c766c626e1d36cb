#!/usr/bin/env python3
"""Run ONE cell of the benchmark ONCE.

    python3 benchmarks/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The last line of stdout is the result (see README.md). Without a TPU
holding the chips the cell asks for, the exit code is not 0 and no
result is printed - unless BENCH_REHEARSAL=1, which runs tiny widths on
the CPU to rehearse the control flow (its numbers mean nothing).
"""

import argparse
import os
import sys
import time

T_PROC0 = time.monotonic()
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
# the program (ray_tpu) and the harness; workers of the runtime inherit
# the driver's sys.path, so the replica can import ``harness`` too
for p in (BENCH_DIR, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "ray_tpu")):
        print("benchmark: the program (ray_tpu/) is not in this checkout",
              file=sys.stderr)
        return 4
    from harness import spec
    cell = spec.cell(a.workload)
    kind = cell["model"]["deployment"]["kind"]
    if kind == "serve":
        from harness import serve_cell as runner
    elif kind == "train":
        from harness import train_cell as runner
    else:
        raise SystemExit(f"configuration kind {kind!r}")
    return runner.run(cell, a.seed, a.seconds, bool(a.trace), T_PROC0)


if __name__ == "__main__":
    sys.exit(main())
