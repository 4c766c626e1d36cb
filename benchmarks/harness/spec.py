"""Find a cell's files by the names in BENCHMARK.json.

A cell is (configuration, traffic mix). Everything that belongs to one
configuration, one traffic mix or one per-layer metric is a file of its
own; nothing in the harness names a cell, so a later PR adds one by
adding files and entries.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _load(os.path.join(root, "BENCHMARK.json"))


def cell(name: str, bench: dict | None = None, root: str = ROOT) -> dict:
    """Everything one run needs: the workload entry, its configuration
    file, its traffic file, and the metric definitions it reports."""
    bench = bench or benchmark(root)
    wl = next((w for w in bench["workloads"] if w["name"] == name), None)
    if wl is None:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{[w['name'] for w in bench['workloads']]}")
    cfg = next(c for c in bench["configs"] if c["name"] == wl["config"])
    out = dict(wl)
    out["model"] = _load(os.path.join(root, cfg["file"]))
    out["family"] = out["model"]["deployment"].get("family") \
        or _module("families", "__init__").DEFAULT
    out["traffic_params"] = _load(
        os.path.join(BENCH_DIR, "traffic", wl["traffic"] + ".json"))

    def mine(m):
        return "workloads" not in m or name in m["workloads"]
    out["end_to_end"] = [m for m in bench["end_to_end"] if mine(m)]
    out["per_layer"] = [m for m in bench["per_layer"] if mine(m)]
    return out


def metric_file(name: str) -> dict:
    return _load(os.path.join(BENCH_DIR, "metrics", name + ".json"))


def _module(kind: str, name: str):
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str):
    """readers/<name>.py, loaded by path; its ``read(ctx, **args)``
    returns a number, or None when there is nothing to read."""
    return _module("readers", name).read


def family(name: str):
    """families/<name>.py, loaded by path, once a process: everything
    the harness asks of a model by its name (``config``, ``module``,
    ``forward``, ``logits_and_loss``, ``train_required_flops_per_token``
    and, for a family that can be served, ``serve_parity``)."""
    key = f"bench_families_{name}"
    if key not in sys.modules:
        path = os.path.join(BENCH_DIR, "families", name + ".py")
        if not os.path.isfile(path):
            raise SystemExit(f"the configuration names family {name!r}; "
                             f"there is no {path}")
        sys.modules[key] = _module("families", name)
    return sys.modules[key]


def read_metrics(defs: list, ctx: dict) -> dict:
    """{name: {"value", "unit"}} for every metric whose reader found
    something."""
    out = {}
    for m in defs:
        mf = metric_file(m["name"])
        value = reader(mf["reader"])(ctx, **mf.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
