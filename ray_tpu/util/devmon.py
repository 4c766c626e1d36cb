"""Device-plane observability: XLA compile tracing, HBM accounting,
and device duty cycle.

The observability plane sees hosts (util/profiling.py), wires
(dag/ring.py collective traces), and requests (util/tracing.py request
layer) — this module adds the ACCELERATOR itself, the layer where the
JAX-production failure modes live:

- **Compile tracing**: every backend XLA compile in this process is
  recorded as a span into the budget-capped "device" event category
  (function name, duration, persistent-cache hit vs miss) via the
  ``jax.monitoring`` duration/event listeners. The ambient request
  trace context (util/tracing.py) is stamped onto each compile span,
  so "this request was slow because it compiled" shows up as a
  ``dev:compile`` lane in ``ray-tpu trace <id>`` waterfalls. A
  recompile-STORM detector flags a function compiled >=
  ``Config.devmon_recompile_threshold`` times inside
  ``Config.devmon_recompile_window_s`` — the silent multi-second
  mid-serving recompile (a new sequence-length bucket, a dtype drift)
  that no host profiler can see.
- **HBM accounting**: periodic per-device snapshots via
  ``device.memory_stats()`` (TPU/GPU), falling back to a
  ``jax.live_arrays()`` aggregation on backends without memory stats
  (CPU), exported as ``device_hbm_used_bytes`` /
  ``device_hbm_limit_bytes`` / ``device_hbm_peak_bytes{device}``
  gauges (worker processes push them to the head through the existing
  util/metrics.py push_loop) and recorded as "device"/"hbm" events so
  the `/devices` dashboard page and ``ray-tpu devices`` render them
  cluster-wide off collect_timeline.
- **Duty cycle**: components that bracket device work with
  block_until_ready (engine prefill/decode blocks, train steps) wrap
  it in :func:`device_window`; the estimator reports the fraction of
  wall time inside such windows over ``Config.devmon_duty_horizon_s``
  as ``device_duty_cycle{device}`` and the windows render as a
  per-node ``dev:<device>`` lane in chrome timelines.

``RAY_TPU_DEVMON=0`` disables the whole plane at process start (the
listeners are never registered, every record path no-ops) — the same
master-switch idiom as RAY_TPU_TRACE_REQUESTS. The function NAME on a
compile span comes from correlating jax's own "Finished XLA
compilation of <name> ..." debug log line (emitted inside the same
``log_elapsed_time`` context that fires the monitoring event, on the
same thread, immediately before it) — the monitoring callback alone
carries no name. Private-API drift there degrades names to "?", never
breaks recording.
"""

from __future__ import annotations

import contextlib
import logging
import math
import os
import re
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from ray_tpu.util import events

logger = logging.getLogger("ray_tpu.devmon")

_OFF = ("0", "false", "off")
_ENABLED = os.environ.get("RAY_TPU_DEVMON", "1").lower() not in _OFF

# jax.monitoring event names this module acts on (jax._src/dispatch.py
# BACKEND_COMPILE_EVENT and jax._src/compiler.py's persistent-cache
# retrieval timer).
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

_COMPILE_LOG_RE = re.compile(
    r"Finished XLA compilation of (.+?) in [0-9.eE+-]+ sec")

_LOCK = threading.Lock()
_INSTALLED = False
# thread-local carrying the fun_name parsed from jax's compile log
# line until the monitoring duration event (same thread, right after)
# consumes it
_TLS = threading.local()

# per-function compile timestamps inside the storm window, the last
# time a storm was flagged for that function (one flag per window),
# and whether the function ever compiled (compile #2+ is a RECOMPILE)
_COMPILE_HIST: Dict[str, deque] = {}
_STORM_FLAGGED: Dict[str, float] = {}
_EVER_COMPILED: Dict[str, bool] = {}

# duty-cycle windows: (t0, t1) wall-clock intervals of device work in
# this process, bounded (old windows age past any plausible horizon)
_WINDOWS: deque = deque(maxlen=4096)

# live_arrays-fallback peak tracking (memory_stats backends report
# their own peak): device label -> max used bytes ever snapshotted
_PEAK: Dict[str, int] = {}

_DEVICE_LABEL: Optional[str] = None

# Whether THIS process has initialised a JAX backend. A chip belongs
# to one process at a time, and asking jax for its devices initialises
# the backend — a worker that merely imported jax would claim the chip
# (or block on the process that holds it) just to be observable. So
# nothing here touches a device until the process has gone live on its
# own: the engine and the mesh builder say so (mark_backend_live), and
# so does the first backend compile the listener sees.
_BACKEND_LIVE = False


def enabled() -> bool:
    return _ENABLED


def mark_backend_live() -> None:
    """Called by code that has just put work on a device (LLMEngine,
    make_mesh): from here on the monitor may query the backend."""
    global _BACKEND_LIVE
    _BACKEND_LIVE = True


def backend_live() -> bool:
    return _BACKEND_LIVE


def devmon_metrics() -> dict:
    """Get-or-create the device-plane metrics (shared process registry,
    pushed to the head by util/metrics.push_loop like every other
    worker-side series). Catalog:

      xla_compiles_total{fn}          backend XLA compiles (cache misses)
      xla_recompiles_total{fn}        compiles BEYOND the first per fn —
                                      the recompile signal the storm
                                      detector integrates
      xla_recompile_storms_total{fn}  storm flags (threshold compiles
                                      inside the window)
      xla_cache_hits_total            persistent-compilation-cache hits
                                      (suppressed from recompile counts)
      xla_compile_s                   compile duration distribution,
                                      exemplar-linked to the request
                                      trace that triggered it
      device_hbm_used_bytes{device}   HBM in use per local device
      device_hbm_limit_bytes{device}  HBM capacity (0 = unknown backend)
      device_hbm_peak_bytes{device}   high watermark
      device_duty_cycle{device}       fraction of wall time inside
                                      device_window()s over the horizon
    """
    from ray_tpu.util import metrics as m
    return {
        "compiles": m.Counter(
            "xla_compiles_total", "Backend XLA compiles in this process",
            tag_keys=("fn",)),
        "recompiles": m.Counter(
            "xla_recompiles_total",
            "XLA compiles beyond the first per function (recompile "
            "signal; persistent-cache hits are suppressed)",
            tag_keys=("fn",)),
        "storms": m.Counter(
            "xla_recompile_storms_total",
            "Recompile storms flagged (devmon_recompile_threshold "
            "compiles of one function inside "
            "devmon_recompile_window_s)", tag_keys=("fn",)),
        "cache_hits": m.Counter(
            "xla_cache_hits_total",
            "Persistent compilation cache hits"),
        "compile_s": m.Histogram(
            "xla_compile_s", "Backend XLA compile duration",
            boundaries=(.01, .05, .1, .25, .5, 1, 2.5, 5, 10, 30, 60)),
        "hbm_used": m.Gauge(
            "device_hbm_used_bytes", "Device HBM in use",
            tag_keys=("device",)),
        "hbm_limit": m.Gauge(
            "device_hbm_limit_bytes",
            "Device HBM capacity (0 when the backend reports none)",
            tag_keys=("device",)),
        "hbm_peak": m.Gauge(
            "device_hbm_peak_bytes", "Device HBM high watermark",
            tag_keys=("device",)),
        "duty": m.Gauge(
            "device_duty_cycle",
            "Fraction of wall time inside device-compute windows over "
            "devmon_duty_horizon_s", tag_keys=("device",)),
    }


# --- compile tracing ---------------------------------------------------


class _CompileLogHandler(logging.Handler):
    """Captures jax's per-compile log line for the function name; the
    duration listener (fired right after, same thread) consumes it."""

    def emit(self, record: logging.LogRecord) -> None:
        try:
            m = _COMPILE_LOG_RE.search(record.getMessage())
            if m is not None:
                _TLS.pending_fn = m.group(1)
        except Exception:  # noqa: BLE001 — observability must not raise
            pass


class _ForwardHandler(logging.Handler):
    """Re-emits records to the root logger. install() drops the jax
    dispatch logger to DEBUG (so the compile lines reach the name
    correlator) with ``propagate`` off (so that DEBUG enablement
    doesn't spray jax's own debug lines through the user's root
    handlers); this handler, levelled at the logger's PRE-install
    effective level, keeps the records the user would have seen —
    e.g. jax_log_compiles WARNINGs — flowing to root as before."""

    def emit(self, record: logging.LogRecord) -> None:
        try:
            logging.getLogger().handle(record)
        except Exception:  # noqa: BLE001
            pass


def _take_pending_fn() -> str:
    fn = getattr(_TLS, "pending_fn", None)
    _TLS.pending_fn = None
    return fn or "?"


def _ambient_trace() -> str:
    from ray_tpu.util import tracing
    return tracing.current_trace_id()


def record_compile(fn: str, dur_s: float, *,
                   cache_hit: bool = False) -> None:
    """One compile (or persistent-cache retrieval) as a "device" span +
    metrics + storm check. Public so tests and non-jax.monitoring
    callers can drive it deterministically."""
    if not _ENABLED:
        return
    now = time.time()
    trace = _ambient_trace()
    events.record("device", "compile", fn=fn, ts=now - dur_s, dur=dur_s,
                  cache_hit=cache_hit, pid=os.getpid(),
                  **({"trace": trace} if trace else {}))
    m = devmon_metrics()
    if cache_hit:
        # a persistent-cache hit is NOT a recompile: the storm
        # detector must not fire on a cold process warming from cache
        m["cache_hits"].inc()
        return
    m["compiles"].inc(tags={"fn": fn})
    m["compile_s"].observe(dur_s, exemplar=trace or None)
    try:
        # a real compile (not a cache retrieval) stalls the step that
        # triggered it — the goodput ledger's compile category
        from ray_tpu.util import goodput
        goodput.add("compile", dur_s)
    except Exception:   # noqa: BLE001
        pass
    _note_compile(fn, now, m)


def _note_compile(fn: str, now: float, m: dict) -> None:
    """Recompile bookkeeping + the storm gate. Deterministic: with
    threshold T and window W, the Nth compile of ``fn`` increments
    ``xla_recompiles_total`` for N >= 2, and a storm is flagged exactly
    once per window the moment the in-window count reaches T."""
    from ray_tpu.config import get_config
    cfg = get_config()
    thr = int(getattr(cfg, "devmon_recompile_threshold", 3))
    win = float(getattr(cfg, "devmon_recompile_window_s", 60.0))
    with _LOCK:
        dq = _COMPILE_HIST.setdefault(fn, deque(maxlen=1024))
        ever = _EVER_COMPILED.get(fn, False)
        _EVER_COMPILED[fn] = True
        dq.append(now)
        while dq and dq[0] < now - win:
            dq.popleft()
        in_window = len(dq)
        storm = (thr > 0 and in_window >= thr
                 and now - _STORM_FLAGGED.get(fn, -math.inf) >= win)
        if storm:
            _STORM_FLAGGED[fn] = now
    if ever:
        m["recompiles"].inc(tags={"fn": fn})
    if storm:
        m["storms"].inc(tags={"fn": fn})
        events.record("device", "recompile_storm", fn=fn,
                      count=in_window, window_s=win, pid=os.getpid())
        logger.warning(
            "devmon: recompile storm: %r compiled %d times in the last "
            "%.0fs (threshold %d) — look for an unbucketed shape/dtype "
            "reaching a jit boundary (`ray-tpu devices`, or `ray-tpu "
            "trace <id>` for the dev:compile lane of a slow request)",
            fn, in_window, win, thr)


def _on_duration(name: str, dur: float, **_kw) -> None:
    if name == CACHE_RETRIEVAL_EVENT:
        # fires INSIDE the backend-compile timing context when the
        # persistent cache hits; the BACKEND_COMPILE event still fires
        # at that context's exit (it times compile_or_get_cached, hit
        # or miss) — flag the thread so that one span is recorded as
        # a hit instead of double-recording a phantom recompile
        _TLS.cache_hit = True
    elif name == BACKEND_COMPILE_EVENT:
        mark_backend_live()     # only a live backend compiles
        hit = getattr(_TLS, "cache_hit", False)
        _TLS.cache_hit = False
        record_compile(_take_pending_fn(), dur, cache_hit=hit)


def install() -> bool:
    """Register the jax.monitoring listeners + the compile-log name
    correlator in THIS process. Idempotent; returns True when the
    hooks are (already) live. No-ops — without importing jax — when
    the plane is disabled or jax isn't loaded yet (call again later,
    or let monitor_loop() pick it up on its next tick)."""
    global _INSTALLED
    if not _ENABLED:
        return False
    import sys
    if "jax" not in sys.modules:
        return False
    with _LOCK:
        if _INSTALLED:
            return True
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(_on_duration)
        try:
            # jax logs "Finished XLA compilation of {fun_name} ..." at
            # DEBUG from jax._src.dispatch right before recording the
            # monitoring event; DEBUG-enable that one logger and parse
            # the name out, forwarding only records at the logger's
            # previous level on to root (see _ForwardHandler).
            dlog = logging.getLogger("jax._src.dispatch")
            prev = dlog.getEffectiveLevel()
            dlog.addHandler(_CompileLogHandler())
            if prev > logging.DEBUG:
                fwd = _ForwardHandler()
                fwd.setLevel(prev)
                dlog.addHandler(fwd)
                dlog.setLevel(logging.DEBUG)
                dlog.propagate = False
        except Exception:  # noqa: BLE001 — names degrade to "?"
            pass
        _INSTALLED = True
    return True


# --- HBM accounting ----------------------------------------------------


def _device_label(d) -> str:
    return f"{getattr(d, 'platform', 'dev')}:{getattr(d, 'id', 0)}"


def _live_array_bytes() -> Dict[str, int]:
    """Fallback HBM estimate for backends whose memory_stats() is None
    (CPU): per-device bytes of all live jax arrays, sharded arrays
    attributed shard-by-shard."""
    import jax
    out: Dict[str, int] = {}
    for arr in jax.live_arrays():
        try:
            for shard in arr.addressable_shards:
                label = _device_label(shard.device)
                out[label] = out.get(label, 0) + int(
                    getattr(shard.data, "nbytes", 0))
        except Exception:  # noqa: BLE001 — deleted/donated mid-scan
            continue
    return out


def hbm_snapshot(record: bool = True) -> List[dict]:
    """One snapshot of every local device's HBM occupancy: sets the
    device_hbm_* gauges and (by default) records a "device"/"hbm"
    event per device so the head-aggregated timeline carries them to
    `/devices` and ``ray-tpu devices``. Returns the rows. Safe to call
    on any backend; no-op (empty) when devmon is off or this process
    has not initialised a backend itself (see _BACKEND_LIVE)."""
    if not _ENABLED or not _BACKEND_LIVE:
        return []
    import jax
    m = devmon_metrics()
    duty = duty_cycle()
    rows: List[dict] = []
    live = None
    for d in jax.local_devices():
        label = _device_label(d)
        stats = None
        try:
            stats = d.memory_stats()
        except Exception:  # noqa: BLE001 — backend without the API
            stats = None
        if stats:
            used = int(stats.get("bytes_in_use", 0))
            limit = int(stats.get("bytes_limit")
                        or stats.get("bytes_reservable_limit") or 0)
            peak = int(stats.get("peak_bytes_in_use", used))
            source = "memory_stats"
        else:
            if live is None:
                live = _live_array_bytes()
            used = int(live.get(label, 0))
            limit = 0
            peak = max(_PEAK.get(label, 0), used)
            source = "live_arrays"
        _PEAK[label] = max(_PEAK.get(label, 0), used, peak)
        peak = _PEAK[label]
        tags = {"device": label}
        m["hbm_used"].set(used, tags)
        m["hbm_limit"].set(limit, tags)
        m["hbm_peak"].set(peak, tags)
        m["duty"].set(duty, tags)
        row = {"device": label, "used": used, "limit": limit,
               "peak": peak, "duty": duty, "source": source}
        rows.append(row)
        if record:
            events.record("device", "hbm", pid=os.getpid(), **row)
    return rows


# --- duty cycle --------------------------------------------------------


def _default_device_label() -> str:
    global _DEVICE_LABEL
    if _DEVICE_LABEL is None:
        if not _BACKEND_LIVE:
            # bare index, not "dev:0": to_chrome prefixes lanes with
            # "dev:" itself, and a double prefix would split one
            # device's duty lane from its post-jax "cpu:0" windows
            return "0"
        import jax
        _DEVICE_LABEL = _device_label(jax.local_devices()[0])
    return _DEVICE_LABEL


def record_device_window(seg: str, t0: float, t1: float, *,
                         device: Optional[str] = None,
                         trace: str = "") -> None:
    """One completed device-compute window (block_until_ready-bounded
    by the caller): feeds the duty-cycle estimator and records a
    "device"/"window" span (the per-node device lane in to_chrome)."""
    if not _ENABLED or t1 <= t0:
        return
    with _LOCK:
        _WINDOWS.append((t0, t1))
    # windows are HIGH RATE (one per decode block): they live in their
    # own budget bucket so a steady serving load can't age the rare
    # compile/storm/hbm events out of the "device" category
    events.record("device_window", "window", seg=seg, ts=t0,
                  dur=t1 - t0,
                  device=device or _default_device_label(),
                  pid=os.getpid(),
                  **({"trace": trace} if trace else {}))


@contextlib.contextmanager
def device_window(seg: str, device: Optional[str] = None):
    """Context manager form: ``with devmon.device_window("decode"): ...``
    around a block_until_ready-bounded device section."""
    t0 = time.time()
    try:
        yield
    finally:
        record_device_window(seg, t0, time.time(), device=device,
                             trace=_ambient_trace())


def duty_cycle(horizon_s: Optional[float] = None,
               now: Optional[float] = None) -> float:
    """Fraction of the trailing ``horizon_s`` wall-clock seconds spent
    inside device windows (overlapping windows union'd — concurrent
    prefill + decode must not report > 1.0).

    The estimate is PER PROCESS, not per chip: a process's windows
    cover all local devices its dispatches drive (the SPMD common
    case), and hbm_snapshot publishes the same value on every local
    device's ``device_duty_cycle`` gauge. On an MPMD host where one
    process drives a subset of chips, read the gauge per worker label,
    not per device."""
    if horizon_s is None:
        from ray_tpu.config import get_config
        horizon_s = float(getattr(get_config(),
                                  "devmon_duty_horizon_s", 30.0))
    horizon_s = max(1e-3, float(horizon_s))
    now = time.time() if now is None else now
    lo = now - horizon_s
    with _LOCK:
        spans = sorted((max(t0, lo), min(t1, now))
                       for t0, t1 in _WINDOWS if t1 > lo and t0 < now)
    busy, cur_lo, cur_hi = 0.0, None, None
    for t0, t1 in spans:
        if cur_hi is None or t0 > cur_hi:
            if cur_hi is not None:
                busy += cur_hi - cur_lo
            cur_lo, cur_hi = t0, t1
        else:
            cur_hi = max(cur_hi, t1)
    if cur_hi is not None:
        busy += cur_hi - cur_lo
    return min(1.0, busy / horizon_s)


# --- periodic monitor --------------------------------------------------


async def monitor_loop(interval_s: Optional[float] = None) -> None:
    """Per-process device monitor: installs the compile hooks the tick
    after jax first appears (workers must NOT import jax just to be
    observable — non-jax workloads pay nothing) and snapshots HBM /
    duty every ``Config.devmon_hbm_interval_s``. Run as a background
    task next to util/metrics.push_loop (runtime/worker.py)."""
    import asyncio
    import sys
    if not _ENABLED:
        return
    if interval_s is None:
        from ray_tpu.config import get_config
        interval_s = float(getattr(get_config(),
                                   "devmon_hbm_interval_s", 5.0))
    interval_s = max(0.25, float(interval_s))
    while True:
        await asyncio.sleep(interval_s)
        try:
            if "jax" not in sys.modules:
                continue
            install()
            hbm_snapshot()
        except asyncio.CancelledError:
            raise
        except Exception:  # noqa: BLE001 — observability never kills
            pass           # the worker; next tick retries


def _reset_for_tests() -> None:
    """Drop detector/duty state (NOT the installed listeners — those
    are process-global and idempotent)."""
    with _LOCK:
        _COMPILE_HIST.clear()
        _STORM_FLAGGED.clear()
        _EVER_COMPILED.clear()
        _WINDOWS.clear()
        _PEAK.clear()
