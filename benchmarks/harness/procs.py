"""A run ends every process it started before it prints its result.

The program's own shutdown signals its workers and does not wait for
them (``runtime/agent.py stop()`` -> ``_kill_worker`` ->
``proc.terminate()``), so a replica that holds the chip can outlive the
process that started it. ``begin()`` makes every process of the run
findable whatever its parent or session becomes; ``end_all()`` waits
for them, signals what has not been signalled, kills what will not go,
and says what it met. ``run.py`` prints the result only after that, so
a reader of the last line can rely on nothing of the run being alive.

Linux only (``/proc``, ``prctl``), which the chip machines are.
"""

from __future__ import annotations

import ctypes
import os
import signal
import sys
import time

MARK_ENV = "BENCH_RUN_MARK"
# How long a process may take to go after SIGTERM before it is killed:
# a worker's own backstop is os._exit 3 s after it handles the signal,
# after a final metrics push bounded at 2 s (runtime/worker.py
# _graceful_term); twice that.
GRACE_S = 10.0
# How long after SIGKILL a process may still be listed. A killed process
# goes within milliseconds; one that does not is stuck in the kernel
# (a hung device) and no signal will move it.
KILL_WAIT_S = 5.0
_PR_SET_CHILD_SUBREAPER = 36


def begin() -> str:
    """Mark this run in the environment its processes inherit (the
    runtime hands workers ``dict(os.environ)``), and adopt orphans: a
    worker whose parent exits becomes this process's child, so it can be
    waited for. Returns the mark."""
    mark = f"{os.getpid()}-{time.monotonic_ns()}"
    os.environ[MARK_ENV] = mark
    if not _subreaper():
        print("benchmark: no child subreaper on this platform; processes "
              "of the run are found by their mark alone", file=sys.stderr)
    return mark


def _subreaper() -> bool:
    try:
        libc = ctypes.CDLL(None, use_errno=True)
        prctl = libc.prctl
    except (OSError, AttributeError):
        return False
    prctl.argtypes = [ctypes.c_int] + [ctypes.c_ulong] * 4
    prctl.restype = ctypes.c_int
    return prctl(_PR_SET_CHILD_SUBREAPER, 1, 0, 0, 0) == 0


def table() -> dict:
    """{pid: (ppid, state)} of every process there is now."""
    out = {}
    for name in os.listdir("/proc"):
        if not name.isdigit():
            continue
        try:
            with open(f"/proc/{name}/stat") as f:
                stat = f.read()
        except OSError:         # gone between the listing and the read
            continue
        # "pid (comm) state ppid ..."; comm may hold spaces and brackets
        state, ppid = stat[stat.rfind(")") + 2:].split()[:2]
        out[int(name)] = (int(ppid), state)
    return out


def descendants(tab: dict, root: int) -> set:
    kids = {}
    for pid, (ppid, _) in tab.items():
        kids.setdefault(ppid, []).append(pid)
    out, todo = set(), [root]
    while todo:
        for pid in kids.get(todo.pop(), ()):
            if pid not in out:
                out.add(pid)
                todo.append(pid)
    return out


def has_env(pid: int, assignment: str) -> bool:
    """Was ``pid`` started with ``NAME=value`` in its environment?
    (``/proc/<pid>/environ`` is the environment at exec, so a process
    that only forked is found as a descendant instead.)"""
    try:
        with open(f"/proc/{pid}/environ", "rb") as f:
            env = f.read()
    except OSError:             # gone, or another user's
        return False
    return assignment.encode() in env.split(b"\0")


def cmdline(pid: int) -> str:
    """The command line, or ``[name]`` of a zombie, which has none."""
    try:
        with open(f"/proc/{pid}/cmdline", "rb") as f:
            cmd = f.read().replace(b"\0", b" ").decode(
                errors="replace").strip()
        if not cmd:
            with open(f"/proc/{pid}/comm") as f:
                cmd = f"[{f.read().strip()}]"
        return cmd
    except OSError:
        return ""


def of_run(mark: str, root: int, tab: dict | None = None) -> set:
    """Every process of the run but ``root`` itself: its descendants,
    and whatever carries the mark wherever it has been re-parented."""
    tab = table() if tab is None else tab
    found = descendants(tab, root)
    found |= {p for p in tab if p not in found and p != root
              and has_env(p, f"{MARK_ENV}={mark}")}
    return found


def end_all(grace_s: float = GRACE_S, mark: str | None = None) -> list:
    """End every process of this run and wait until each is gone.

    Call it after the runner's own shutdown has stopped the runtime's
    event loop, whose child watchers wait on the same pids. Returns one
    record per process that was still there when it was called:
    ``{"pid", "cmd", "outlived_s", "how"}`` with ``how`` what it took:
    ``exited`` (no signal from here), ``sigterm``, ``sigkill``, or
    ``alive`` for one that SIGKILL did not remove in ``KILL_WAIT_S``.
    """
    mark = os.environ.get(MARK_ENV, "") if mark is None else mark
    me, t0 = os.getpid(), time.monotonic()
    seen, sent = {}, {}
    while True:
        tab = table()
        now = time.monotonic() - t0
        live = of_run(mark, me, tab)
        for pid in sorted(live):
            seen.setdefault(pid, {"pid": pid, "cmd": cmdline(pid)[:80]})
            ppid, state = tab[pid]
            if state == "Z":
                # ours to reap once it is our child (its parent's death
                # brings it here); another parent's zombie stays in the
                # set until that parent has reaped it
                if ppid == me:
                    _reap(pid)
                continue
            sig = signal.SIGTERM if now < grace_s else signal.SIGKILL
            if sent.get(pid) not in (sig, signal.SIGKILL):
                try:
                    os.kill(pid, sig)
                    sent[pid] = sig
                except (ProcessLookupError, PermissionError):
                    pass
        for pid, rec in seen.items():
            if pid not in live and "how" not in rec:
                rec["outlived_s"] = round(now, 3)
                rec["how"] = {signal.SIGTERM: "sigterm",
                              signal.SIGKILL: "sigkill"}.get(
                                  sent.get(pid), "exited")
        if not live:
            break
        if now > grace_s + KILL_WAIT_S:
            for pid in live:
                seen[pid].update(outlived_s=round(now, 3), how="alive")
            break
        time.sleep(0.02)
    out = list(seen.values())
    for rec in out:
        if rec["how"] in ("sigkill", "alive"):
            print(f"benchmark: teardown {rec['how']}: pid {rec['pid']} "
                  f"after {rec['outlived_s']} s: {rec['cmd']}",
                  file=sys.stderr)
    return out


def _reap(pid: int) -> None:
    try:
        os.waitpid(pid, os.WNOHANG)
    except ChildProcessError:   # a child watcher of the runtime had it
        pass
