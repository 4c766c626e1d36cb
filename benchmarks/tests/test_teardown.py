"""A run ends every process it started, and only then prints its
result: ``harness/procs.py`` against a process tree built for the
purpose, and ``run.py``'s ordering with a stub runner. CPU, no JAX; a
few seconds each."""
import json
import os
import shutil
import signal
import subprocess
import sys
import textwrap
import time

import pytest

from harness import procs, spec

SLEEPER = "import time; time.sleep(60)"


def alive(pid: int) -> bool:
    return procs.table().get(pid, (0, "Z"))[1] != "Z"


# --- procs.end_all ---------------------------------------------------------

DRIVER = """
import json, subprocess, sys
sys.path.insert(0, {bench!r})
from harness import procs
procs.begin()
py, PIPE = sys.executable, subprocess.PIPE
# a child that ignores SIGTERM
stubborn = subprocess.Popen([py, "-c",
    "import signal, time; signal.signal(signal.SIGTERM, signal.SIG_IGN);"
    "print('up', flush=True); time.sleep(60)"], stdout=PIPE)
# a grandchild whose parent exits at once: the orphan
mid = subprocess.Popen([py, "-c",
    "import subprocess, sys; p = subprocess.Popen([sys.executable, '-c',"
    "'import time; time.sleep(60)']); print(p.pid, flush=True)"],
    stdout=PIPE)
orphan = int(mid.stdout.readline())
mid.wait()
# a child in a session of its own
own = subprocess.Popen([py, "-c", {sleeper!r}], start_new_session=True)
stubborn.stdout.readline()
print(json.dumps({{"stubborn": stubborn.pid, "orphan": orphan,
                  "own": own.pid, "mark": procs.os.environ[procs.MARK_ENV],
                  "records": procs.end_all(grace_s=2)}}))
"""


def test_end_all_ends_the_tree_and_nothing_else(tmp_path):
    script = tmp_path / "driver.py"
    script.write_text(DRIVER.format(bench=spec.BENCH_DIR, sleeper=SLEEPER))
    env = {k: v for k, v in os.environ.items() if k != procs.MARK_ENV}
    # not a descendant of the run, and without its mark
    bystander = subprocess.Popen([sys.executable, "-c", SLEEPER], env=env)
    try:
        t0 = time.monotonic()
        proc = subprocess.run([sys.executable, str(script)], env=env,
                              capture_output=True, text=True, timeout=60)
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert time.monotonic() - t0 < 20
        out = json.loads(proc.stdout.strip().splitlines()[-1])
        how = {r["pid"]: r["how"] for r in out["records"]}
        for name in ("stubborn", "orphan", "own"):
            assert not alive(out[name]), name
            assert not procs.has_env(
                out[name], f"{procs.MARK_ENV}={out['mark']}")
        assert how[out["stubborn"]] == "sigkill"
        assert how[out["orphan"]] in ("sigterm", "exited")
        assert how[out["own"]] in ("sigterm", "exited")
        for r in out["records"]:
            assert set(r) == {"pid", "cmd", "outlived_s", "how"}
            assert len(r["cmd"]) <= 80
        # a SIGKILL is never silent
        assert f"sigkill: pid {out['stubborn']}" in proc.stderr
        assert bystander.poll() is None
    finally:
        bystander.kill()
        bystander.wait()


def test_of_run_finds_a_reparented_process_by_its_mark():
    """The mark alone finds a process that is nobody's descendant here
    (what is left where no subreaper can be had)."""
    mark = "test-" + str(time.monotonic_ns())
    p = subprocess.Popen([sys.executable, "-c", SLEEPER],
                         env={**os.environ, procs.MARK_ENV: mark})
    try:
        for _ in range(100):    # the environment is there after exec
            if procs.has_env(p.pid, f"{procs.MARK_ENV}={mark}"):
                break
            time.sleep(0.02)
        assert p.pid in procs.of_run(mark, root=1, tab={p.pid: (0, "S")})
        assert p.pid not in procs.of_run("another", root=1,
                                         tab={p.pid: (0, "S")})
    finally:
        p.kill()
        p.wait()


# --- run.py's ordering -----------------------------------------------------

STUB = """
import os, subprocess, sys, time
from harness import result


def run(cell, seed, seconds, trace, t_proc0):
    child = subprocess.Popen([sys.executable, "-c", {sleeper!r}])
    result.note(note="child", pid=child.pid)
    mode = os.environ.get("STUB_MODE")
    if mode == "raise":
        raise RuntimeError("the stub failed")
    if mode == "hang":
        time.sleep(60)
    result.final(correct=True, attempted=1, failed=0,
                 metrics={{"setup_s": {{"value": 1.5, "unit": "s"}}}},
                 device={{"platform": "none", "kind": "stub", "count": 1,
                         "memory_peak_bytes": 0}})
    return 0
"""
ROW = {"correct": True, "attempted": 1, "failed": 0,
       "metrics": {"setup_s": {"value": 1.5, "unit": "s"}},
       "device": {"platform": "none", "kind": "stub", "count": 1,
                  "memory_peak_bytes": 0}}


@pytest.fixture(scope="module")
def stub_root(tmp_path_factory):
    """A checkout whose one extra cell is run by harness/stub_cell.py:
    the kind of a configuration names its runner's file."""
    root = tmp_path_factory.mktemp("stub_root")
    shutil.copytree(spec.BENCH_DIR, root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "ray_tpu").mkdir()      # the stub needs none of the program
    b = root / "benchmarks"
    (b / "harness" / "stub_cell.py").write_text(
        textwrap.dedent(STUB).format(sleeper=SLEEPER))
    (b / "configs" / "stub.json").write_text(json.dumps(
        {"deployment": {"kind": "stub"}}))
    bench = spec.benchmark()
    bench["configs"].append({
        "name": "stub", "source": "test", "reduced": [], "why": "test",
        "file": "benchmarks/configs/stub.json"})
    bench["workloads"].append({
        "name": "stub-cell", "config": "stub", "traffic": "pretrain-4k",
        "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def start(root, mode):
    return subprocess.Popen(
        [sys.executable, "benchmarks/run.py", "--workload", "stub-cell",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=root, env={**os.environ, "STUB_MODE": mode},
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)


def teardown_names_the_child(lines):
    child = next(json.loads(ln) for ln in lines
                 if '"note": "child"' in ln)["pid"]
    note = next(json.loads(ln) for ln in lines
                if '"note": "teardown"' in ln)
    assert [p["pid"] for p in note["outlived"]] == [child]
    assert note["outlived"][0]["how"] == "sigterm"
    assert note["seconds"] < 5
    assert not alive(child)
    return note


def test_the_result_is_the_last_line_after_the_teardown(stub_root):
    proc = start(stub_root, "ok")
    out, err = proc.communicate(timeout=60)
    assert proc.returncode == 0, err[-2000:]
    lines = out.strip().splitlines()
    teardown_names_the_child(lines)
    assert '"note": "teardown"' in lines[-2]
    # byte for byte the row the runner kept
    assert lines[-1] == json.dumps(ROW)


def test_a_failed_run_tears_down_and_prints_no_result(stub_root):
    proc = start(stub_root, "raise")
    out, err = proc.communicate(timeout=60)
    assert proc.returncode != 0 and "the stub failed" in err
    lines = out.strip().splitlines()
    teardown_names_the_child(lines)
    assert '"note": "teardown"' in lines[-1]
    assert '"correct"' not in out


@pytest.mark.parametrize("sig", [signal.SIGTERM, signal.SIGHUP])
def test_a_run_cut_by_a_signal_tears_down(stub_root, sig):
    proc = start(stub_root, "hang")
    first = proc.stdout.readline()      # the child is up, the run hangs
    assert '"note": "child"' in first
    proc.send_signal(sig)
    out, err = proc.communicate(timeout=60)
    assert proc.returncode == 128 + sig, err[-2000:]
    lines = [first] + out.strip().splitlines()
    teardown_names_the_child(lines)
    assert '"note": "teardown"' in lines[-1]
    assert '"correct"' not in out


def test_an_unknown_kind_is_refused(stub_root):
    cfg = stub_root / "benchmarks" / "configs" / "stub.json"
    keep = cfg.read_text()
    cfg.write_text(json.dumps({"deployment": {"kind": "nosuch"}}))
    try:
        proc = start(stub_root, "ok")
        out, err = proc.communicate(timeout=60)
    finally:
        cfg.write_text(keep)
    assert proc.returncode != 0 and "nosuch" in err
    assert '"correct"' not in out
