#!/usr/bin/env python3
"""What outlives a run? Watch from outside, as the driver's check does.

    python3 benchmarks/tools/leftovers.py --plan plan.json --out DIR

Not part of any run: a one-off for the chip (PERF.md records what it
found). ``plan.json`` is a list of ``{"label", "cmd": [...], "watch_s",
"overlap"}``. Each command is started with a tag of this tool's own in
its environment, its process tree is followed while it runs, and from
the instant it exits every process of that tree or with that tag, and
every process that holds an accelerator device file, is sampled every
100 ms for ``watch_s`` seconds: pid, how long it stayed, every change of
its parent and state, and once what it was waiting in. Who held a device
file while the command ran is recorded too (it says which worker is the
replica, and that holders can be seen at all). With ``overlap`` the next
command is started at once, as the driver starts its runs, while the
watch goes on in a thread. This process never imports JAX.
"""

import argparse
import json
import os
import re
import subprocess
import sys
import threading
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

from harness import procs  # noqa: E402 - after the path is set

DEVICE_FILE = re.compile(r"^/dev/(accel|vfio/\d)")
TAG_ENV = "BENCH_LEFTOVERS_TAG"


def device_holders() -> dict:
    """{pid: [device files]} over every process whose fds can be read."""
    out = {}
    for name in os.listdir("/proc"):
        if not name.isdigit():
            continue
        try:
            fds = os.listdir(f"/proc/{name}/fd")
        except OSError:
            continue
        held = set()
        for fd in fds:
            try:
                target = os.readlink(f"/proc/{name}/fd/{fd}")
            except OSError:
                continue
            if DEVICE_FILE.match(target):
                held.add(target)
        if held:
            out[int(name)] = sorted(held)
    return out


def _read(path: str) -> str:
    try:
        with open(path) as f:
            return f.read().strip()
    except OSError as e:
        return f"<{type(e).__name__}>"


def waiting_in(pid: int) -> dict:
    """Where the process and its threads sleep, as far as /proc says."""
    threads = {}
    try:
        for tid in os.listdir(f"/proc/{pid}/task"):
            key = (_read(f"/proc/{pid}/task/{tid}/comm"),
                   _read(f"/proc/{pid}/task/{tid}/wchan"))
            threads[key] = threads.get(key, 0) + 1
    except OSError:
        pass
    return {"wchan": _read(f"/proc/{pid}/wchan"),
            "stack": _read(f"/proc/{pid}/stack")[:600],
            "threads": [[c, w, n] for (c, w), n in sorted(threads.items())]}


def watch(run: dict, tag: str, tree: dict, watch_s: float) -> None:
    """Sample what is left of ``run`` from its exit on."""
    t_exit = run["t_exit"]
    left, samples = {}, 0
    while True:
        t = time.monotonic() - t_exit
        tab = procs.table()
        holders = device_holders()
        for pid in tab:
            if pid not in tree and not procs.has_env(pid, tag):
                continue
            ppid, state = tab[pid]
            rec = left.setdefault(pid, {
                "pid": pid, "cmd": procs.cmdline(pid)[:160] or tree.get(pid),
                "pgid_sid": _read(f"/proc/{pid}/stat").rsplit(")", 1)[-1]
                .split()[2:4], "waiting_in": waiting_in(pid),
                "held_device_in_run": pid in run["device_holders_during"],
                "changes": []})     # [seconds after the exit, ppid, state]
            if not rec["changes"] or rec["changes"][-1][1:] != [ppid, state]:
                rec["changes"].append([round(t, 3), ppid, state])
            rec["last_seen_s"] = round(t, 3)
            if pid in holders:
                rec["held_device_until_s"] = round(t, 3)
        samples += 1
        if t >= watch_s:
            break
        time.sleep(0.1)
    run.update(samples=samples, left=sorted(left.values(),
                                            key=lambda r: r["pid"]))


def one(step: dict, out_dir: str) -> tuple:
    label = step["label"]
    tag = f"{TAG_ENV}={os.getpid()}-{time.monotonic_ns()}"
    name, value = tag.split("=")
    run = {"label": label, "cmd": step["cmd"],
           "device_holders_before": device_holders()}
    tree = {}           # every descendant seen while it ran: pid -> cmd
    t0 = time.monotonic()
    with open(os.path.join(out_dir, label + ".stdout"), "wb") as so, \
            open(os.path.join(out_dir, label + ".stderr"), "wb") as se:
        child = subprocess.Popen(step["cmd"], stdout=so, stderr=se,
                                 env={**os.environ, name: value})
        tree[child.pid] = " ".join(step["cmd"])[:160]
        last_scan, scans, during = 0.0, 0, {}
        while child.poll() is None:
            if time.monotonic() - last_scan > 0.25:
                last_scan, scans = time.monotonic(), scans + 1
                for pid in procs.descendants(procs.table(), child.pid):
                    if pid not in tree:
                        tree[pid] = procs.cmdline(pid)[:160]
                if scans % 8 == 0:
                    during.update(device_holders())
            time.sleep(0.01)
    run["device_holders_during"] = during
    run["t_exit"] = time.monotonic()
    run.update(rc=child.returncode, wall_s=round(run["t_exit"] - t0, 3),
               processes_seen=len(tree))
    return run, tag, tree


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--plan", required=True)
    ap.add_argument("--out", required=True)
    a = ap.parse_args()
    with open(a.plan) as f:
        plan = json.load(f)
    os.makedirs(a.out, exist_ok=True)
    runs, watchers = [], []
    for step in plan:
        run, tag, tree = one(step, a.out)
        runs.append(run)
        th = threading.Thread(target=watch, args=(
            run, tag, tree, float(step.get("watch_s", 15.0))))
        th.start()
        watchers.append(th)
        with open(os.path.join(a.out, step["label"] + ".stdout"),
                  errors="replace") as f:
            run["last_lines"] = [ln[:400]
                                 for ln in f.read().splitlines()[-2:]]
        if not step.get("overlap"):
            th.join()
    for th in watchers:
        th.join()
    for run in runs:
        run.pop("t_exit")
        print(json.dumps(run), flush=True)
        print(json.dumps({
            "label": run["label"], "rc": run["rc"], "wall_s": run["wall_s"],
            "left": [{k: r.get(k) for k in (
                "pid", "cmd", "changes", "last_seen_s", "held_device_in_run",
                "held_device_until_s")} for r in run["left"]]}),
            file=sys.stderr, flush=True)
    with open(os.path.join(a.out, "leftovers.json"), "w") as f:
        json.dump(runs, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
