"""CPU rehearsal of the harness: tiny widths, JAX_PLATFORMS=cpu, kernels
interpreted (BENCH_REHEARSAL=1). Every cell's command runs end to end
and prints a last line with the contract's keys; without the rehearsal
flag the same command refuses to produce a result off the TPU. Numbers
from these runs mean nothing and are not looked at.

Slow (a few minutes): run as
    python -m pytest benchmarks/tests/test_rehearsal.py -q
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

from harness import procs, spec

KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def run_cell(root, workload, *, rehearsal=True, trace=0, chips=1,
             seconds=3, extra_env=None):
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}",
               BENCH_RUN="7")       # the driver sets it; it is ignored
    env.pop("BENCH_REHEARSAL", None)
    if rehearsal:
        env["BENCH_REHEARSAL"] = "1"
    env.update(extra_env or {})
    cmd = [sys.executable if c == "python3" else c
           for c in bench["command"]]
    return subprocess.run(
        cmd + ["--workload", workload, "--seed", "3000000001",
               "--seconds", str(seconds), "--trace", str(trace)],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)


def teardown_note(proc, at):
    """The run's teardown note, which is line ``at`` of its stdout;
    nothing that carries the run's mark is alive any more."""
    note = json.loads(proc.stdout.strip().splitlines()[at])
    assert note["note"] == "teardown", note
    assert all(p["how"] in ("exited", "sigterm", "sigkill")
               for p in note["outlived"])
    marked = [pid for pid in procs.table() if procs.has_env(
        pid, f"{procs.MARK_ENV}={note['mark']}")]
    assert not marked, [procs.cmdline(pid) for pid in marked]
    return note


def last_line(proc):
    """The result, which is the last line; the teardown note is the one
    before it."""
    assert proc.returncode == 0, proc.stderr[-3000:]
    teardown_note(proc, -2)
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", [w["name"] for w in
                                      spec.benchmark()["workloads"]])
def test_cell_runs_end_to_end(workload, trace):
    cell = spec.cell(workload)
    out = last_line(run_cell(spec.ROOT, workload, trace=trace,
                             chips=cell["chips"]))
    assert set(out) - {"breakdown"} == KEYS | {"compared"}
    # what ``correct`` was decided from, each beside its limit, last
    assert list(out)[-1] == "compared" and out["compared"]
    assert all(set(c) == {"value", "limit"}
               for c in out["compared"].values())
    assert set(out["device"]) >= DEVICE_KEYS
    assert out["device"]["platform"] == "cpu"       # and says so
    assert out["attempted"] > 0 and out["failed"] == 0
    allowed = {m["name"] for m in
               (cell["per_layer"] if trace else cell["end_to_end"])}
    assert set(out["metrics"]) <= allowed
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"}
    if not trace:       # every end-to-end metric of the cell is there
        assert set(out["metrics"]) == allowed
    # a trace from a CPU run has no TPU plane: no device-trace metric
    # may appear from it
    traced = {m["name"] for m in cell["per_layer"]
              if m["source"] == "device_trace"}
    assert not traced & set(out["metrics"])


@pytest.mark.parametrize("workload", ["train-dense-1chip",
                                      "serve-chat-open"])
def test_no_tpu_no_result(workload):
    proc = run_cell(spec.ROOT, workload, rehearsal=False)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    teardown_note(proc, -1)     # a refused run tears down all the same


def test_nothing_but_the_benchmark_no_result(tmp_path):
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(spec.BENCH_DIR, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = run_cell(str(tmp_path), "train-dense-1chip")
    assert proc.returncode != 0 and '"correct"' not in proc.stdout


# a family file of a later PR: here the accepted one under another name
REEXPORT = (
    "from harness import spec\n"
    "globals().update({k: v for k, v in vars(spec.family('llama')).items()\n"
    "                  if not k.startswith('__') and k not in %r})\n")


def scratch_checkout(root):
    shutil.copytree(spec.BENCH_DIR, root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(spec.ROOT, "ray_tpu"), root / "ray_tpu")
    return root / "benchmarks", spec.benchmark()


def add_cell(b, bench, name, like, family):
    """A configuration like ``like`` but of ``family``, and a cell on it
    with the traffic and end-to-end metrics of ``like``'s cell."""
    cfg = json.load(open(b / "configs" / f"{like}.json"))
    cfg["deployment"]["family"] = family
    (b / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    was = next(w for w in bench["workloads"] if w["config"] == like)
    bench["configs"].append({
        "name": name, "source": cfg["source"], "why": "test",
        "file": f"benchmarks/configs/{name}.json",
        "reduced": ["num_hidden_layers"]})
    bench["workloads"].append(dict(was, name=name, config=name))
    for m in bench["end_to_end"]:
        if was["name"] in m.get("workloads", ()):
            m["workloads"].append(name)


def harness_files(b):
    return {p: p.read_bytes() for p in sorted((b / "harness").iterdir())
            if p.is_file()}


def test_a_later_pr_adds_files_only(tmp_path):
    """A configuration, a traffic mix, a cell and a per-layer metric
    (with a reader of its own) are added by new files and new entries,
    and so is a model family with a train cell on it; no file that was
    there is touched."""
    root = tmp_path
    b, bench = scratch_checkout(root)
    before = harness_files(b)
    (b / "families" / "throwaway.py").write_text(REEXPORT % ((),))
    add_cell(b, bench, "throwaway-train", "mistral-7b-v0.3-train",
             "throwaway")

    cfg = json.load(open(b / "configs" / "mistral-7b-v0.3-serve.json"))
    cfg["rehearsal"]["num_hidden_layers"] = 1
    (b / "configs" / "throwaway-serve.json").write_text(json.dumps(cfg))
    mix = json.load(open(b / "traffic" / "chat-open.json"))
    mix["rehearsal"]["rate_per_s"] = 3.0
    (b / "traffic" / "throwaway-chat.json").write_text(json.dumps(mix))
    (b / "readers" / "throwaway_reader.py").write_text(
        "def read(ctx, scale):\n"
        "    return scale * len(ctx['requests'])\n")
    metric = {"name": "throwaway_requests", "unit": "requests",
              "better": "higher", "source": "program_counter",
              "layer": "load generator", "moves": "tpot_p50_ms",
              "workloads": ["throwaway-cell"]}
    (b / "metrics" / "throwaway_requests.json").write_text(json.dumps(
        dict(metric, reader="throwaway_reader", args={"scale": 2.0})))

    bench["configs"].append({
        "name": "throwaway-serve", "source": cfg["source"],
        "file": "benchmarks/configs/throwaway-serve.json",
        "reduced": ["num_hidden_layers"], "why": "test"})
    bench["workloads"].append({
        "name": "throwaway-cell", "config": "throwaway-serve",
        "traffic": "throwaway-chat", "chips": 1, "why": "test"})
    bench["per_layer"].append(metric)
    # what an entry already reads with the same reader and arguments,
    # the cell JOINS (PR 58): its name in the entry's list, as with an
    # end-to-end metric; the metric file is not touched
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] == "decode_batch_mean.serve" or (
                "bound" in m and "serve-chat-open" in m.get("workloads", ())):
            m["workloads"].append("throwaway-cell")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    env = {"PYTHONPATH": spec.ROOT}
    out = last_line(run_cell(str(root), "throwaway-cell", trace=1,
                             extra_env=env))
    assert out["metrics"]["throwaway_requests"]["value"] > 0
    assert set(out["metrics"]) == {"throwaway_requests",
                                   "decode_batch_mean.serve"}
    out = last_line(run_cell(str(root), "throwaway-cell", trace=0,
                             extra_env=env))
    assert {"tpot_p50_ms", "setup_s"} <= set(out["metrics"])
    # the new family's train cell, through make_train_step(model=...)
    assert spec.cell("throwaway-train", bench,
                     root=str(root))["family"] == "throwaway"
    out = last_line(run_cell(str(root), "throwaway-train", trace=0,
                             extra_env=env))
    assert out["correct"] and out["attempted"] > 0
    assert harness_files(b) == before


def test_a_family_that_cannot_be_served_is_refused_early(tmp_path):
    """A serve configuration on a family without ``serve_parity`` exits
    non-zero before the runtime is started: nothing but the teardown
    note is printed, and that says no process had to be ended."""
    root = tmp_path
    b, bench = scratch_checkout(root)
    (b / "families" / "noserve.py").write_text(
        REEXPORT % (("serve_parity",),))
    add_cell(b, bench, "noserve-serve", "mistral-7b-v0.3-serve", "noserve")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    proc = run_cell(str(root), "noserve-serve",
                    extra_env={"PYTHONPATH": spec.ROOT})
    assert proc.returncode != 0 and "serve_parity" in proc.stderr
    assert len(proc.stdout.strip().splitlines()) == 1
    assert teardown_note(proc, -1)["outlived"] == []


def test_the_prepared_docs_cell_needs_entries_only(tmp_path):
    """serve-docs-batch is not a cell yet (PERF.md, Open questions), but
    its traffic file, metric files and readers are here: entries in
    BENCHMARK.json alone make it run, closed loop and prefill readers
    included."""
    root = tmp_path
    os.symlink(spec.BENCH_DIR, root / "benchmarks")
    os.symlink(os.path.join(spec.ROOT, "ray_tpu"), root / "ray_tpu")
    bench = spec.benchmark()
    bench["workloads"].append({
        "name": "serve-docs-batch", "config": "mistral-7b-v0.3-serve",
        "traffic": "docs-batch", "chips": 1, "why": "test"})
    mdir = os.path.join(spec.BENCH_DIR, "metrics")
    for name in sorted(os.listdir(mdir)):
        m = json.load(open(os.path.join(mdir, name)))
        if m.get("workloads") != ["serve-docs-batch"]:
            continue
        m = {k: v for k, v in m.items() if k not in ("reader", "args")}
        bench["end_to_end" if "bound" in m else "per_layer"].append(m)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    env = {"PYTHONPATH": spec.ROOT}
    out = last_line(run_cell(str(root), "serve-docs-batch", trace=0,
                             extra_env=env))
    assert out["metrics"]["serve_tok_s"]["value"] > 0
    out = last_line(run_cell(str(root), "serve-docs-batch", trace=1,
                             extra_env=env))
    assert {"decode_batch_mean.docs", "decode_steps_per_block.docs",
            "engine_queue_mean_ms.docs", "kv_pool_used_peak.docs",
            "hbm_peak.docs"} <= set(out["metrics"]) | {"hbm_peak.docs"}
    assert out["metrics"]["decode_steps_per_block.docs"]["value"] >= 1
