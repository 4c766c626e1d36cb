"""The lines a run prints. The LAST line of stdout is the result the
driver reads; everything before it is information, one JSON object a
line. The result is kept, not printed, by the runner: ``run.py`` prints
it as its very last act, after every process of the run has ended."""

from __future__ import annotations

import json
import sys


def note(**row) -> None:
    print(json.dumps(row), flush=True)


_kept = []


def final(*, correct: bool, attempted: int, failed: int, metrics: dict,
          device: dict, breakdown: dict | None = None) -> None:
    """Keep the result's row for ``take()``."""
    row = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown:
        row["breakdown"] = breakdown
    _kept[:] = [row]


def take() -> dict | None:
    """The row the runner kept, once."""
    return _kept.pop() if _kept else None


def finish(cell: dict, trace: bool, ctx: dict, *, correct: bool,
           attempted: int, failed: int) -> None:
    """Read the cell's metrics out of ``ctx`` (end-to-end ones, or the
    per-layer ones of a traced run) and print the last line."""
    from harness import spec
    info = ctx["info"]
    device = {**{k: info["device"][k] for k in ("platform", "kind", "count")},
              "memory_peak_bytes": info["memory_peak_bytes"]}
    breakdown = None
    tr = ctx.get("trace") if trace else None
    if tr:
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        breakdown = {"device_ops": tr["device_ops"],
                     "idle_gaps": tr["idle_gaps"]}
    metrics = spec.read_metrics(
        cell["per_layer"] if trace else cell["end_to_end"], ctx)
    final(correct=correct, attempted=attempted, failed=failed,
          metrics=metrics, device=device, breakdown=breakdown)


def require_tpu(device: dict, chips: int) -> None:
    from harness.model import REHEARSAL
    if not REHEARSAL and (device["platform"] != "tpu"
                          or device["count"] < chips):
        raise NoAccelerator(device)


class NoAccelerator(SystemExit):
    def __init__(self, device):
        print(f"benchmark: JAX reports {device}; this cell needs a TPU "
              f"with enough chips. No result.", file=sys.stderr)
        super().__init__(3)
