#!/usr/bin/env python3
"""Run ONE cell of the benchmark ONCE.

    python3 benchmarks/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The last line of stdout is the result (see README.md), printed after
every process the run started has ended; the line before it says what
that took. Without a TPU holding the chips the cell asks for, the exit
code is not 0 and no result is printed - unless BENCH_REHEARSAL=1, which
runs tiny widths on the CPU to rehearse the control flow (its numbers
mean nothing).
"""

import argparse
import importlib
import os
import signal
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


def _cut(signum, frame):
    """A run cut by ``timeout`` tears down like any other: Python's
    default SIGTERM would skip every ``finally``."""
    raise SystemExit(128 + signum)


CUTS = (signal.SIGTERM, signal.SIGINT, signal.SIGHUP)


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
    from harness import procs, result, spec
    cell = spec.cell(a.workload)
    kind = cell["model"]["deployment"]["kind"]
    mark = procs.begin()    # before anything of the run starts a process
    for sig in CUTS:
        signal.signal(sig, _cut)
    try:
        # harness/<kind>_cell.py: serve_cell, train_cell
        try:
            runner = importlib.import_module(f"harness.{kind}_cell")
        except ModuleNotFoundError as e:
            if e.name != f"harness.{kind}_cell":
                raise
            raise SystemExit(f"configuration kind {kind!r}") from None
        code = runner.run(cell, a.seed, a.seconds, bool(a.trace), T_PROC0)
    finally:
        # the runner's own shutdown has run; now nothing may cut the
        # teardown short, and nothing of the run may outlive it
        for sig in CUTS:
            signal.signal(sig, signal.SIG_IGN)
        t = time.monotonic()
        outlived = procs.end_all()
        result.note(note="teardown", mark=mark, outlived=outlived,
                    seconds=time.monotonic() - t)
    row = result.take()
    if any(p["how"] == "alive" for p in outlived):
        print("benchmark: a process of this run could not be ended. "
              "No result.", file=sys.stderr)
        return 5
    if code == 0 and row is not None:
        result.note(**row)
    return code


if __name__ == "__main__":
    sys.exit(main())
