"""Metric-name lint: keep the metrics catalog consistent and greppable.

Rules (run over the process-global registry in ray_tpu/util/metrics.py
after importing every instrumented module):

  1. names are snake_case: ``^[a-z][a-z0-9_]*$``;
  2. every metric carries a unit suffix — ``_s`` (seconds), ``_total``
     (monotonic count), ``_bytes`` — EXCEPT unitless gauges (a level,
     e.g. ``queue_depth``) and dimensionless count *distributions*
     ending in ``_size``, ``_steps`` or ``_tokens`` (e.g.
     ``llm_batch_size``, ``llm_decode_block_steps``);
  3. no duplicate names, including case-insensitive collisions (the
     registry keys by exact name, so ``Foo``/``foo`` could otherwise
     coexist and split a series);
  4. every metric carries a NON-EMPTY help/description string — the
     catalog, the /metrics HELP lines, and the health plane's series
     listing all surface it; an undescribed series is unusable by
     anyone but its author.

It also lints the EVENT-CATEGORY catalog: every ``events.record(``
call site in the source tree must use a category enumerated in
``ray_tpu/util/events.py CATEGORIES`` — categories gate per-category
buffer budgets and timeline rendering, so an unregistered one would
silently share the default budget and render nowhere.

Usage: ``python scripts/check_metrics_lint.py`` (exits 1 on findings).
tests/test_metrics_lint.py runs the same lint as a tier-1 test.
"""

from __future__ import annotations

import os
import re
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# runnable as `python scripts/check_metrics_lint.py` from the repo root
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_NAME_RE = re.compile(r"^[a-z][a-z0-9_]*$")
UNIT_SUFFIXES = ("_s", "_total", "_bytes")
COUNT_SUFFIXES = ("_size", "_steps", "_tokens")


def lint(registry: dict) -> list:
    """Return a list of human-readable violations for a {name: Metric}
    registry (anything with a ``kind`` attribute works)."""
    errors = []
    seen_lower = {}
    for name, metric in registry.items():
        kind = getattr(metric, "kind", "untyped")
        if not _NAME_RE.match(name):
            errors.append(
                f"{name}: not snake_case (expected ^[a-z][a-z0-9_]*$)")
        if not name.endswith(UNIT_SUFFIXES):
            if kind == "gauge":
                pass        # unitless gauge (a level) is fine
            elif name.endswith(COUNT_SUFFIXES):
                pass        # dimensionless count distribution
            else:
                errors.append(
                    f"{name}: {kind} without a unit suffix "
                    f"({'/'.join(UNIT_SUFFIXES)}; unitless gauges and "
                    f"*_size distributions are exempt)")
        low = name.lower()
        if low in seen_lower and seen_lower[low] != name:
            errors.append(
                f"{name}: case-insensitive duplicate of "
                f"{seen_lower[low]}")
        seen_lower.setdefault(low, name)
        desc = getattr(metric, "description", None)
        if desc is not None and not str(desc).strip():
            errors.append(
                f"{name}: empty help/description string (every "
                f"registered metric must say what it measures)")
    return sorted(errors)


def instantiate_all() -> dict:
    """Import every instrumented module and force its metric
    registrations; returns {name: Metric} for exactly the metrics the
    framework itself registers (tests lint this dict so metrics created
    by other tests in the same process can't contaminate the run)."""
    out = {}

    def take(metrics):
        for m in (metrics.values() if isinstance(metrics, dict)
                  else [metrics]):
            out[m.name] = m

    from ray_tpu.runtime import core
    take(core._M_TASKS())
    from ray_tpu.llm import engine, kvcache, spec
    take(engine.engine_metrics())
    take(kvcache.kvcache_metrics())
    take(spec.spec_metrics())
    from ray_tpu.serve import autoscale, fault, proxy, replica
    take(proxy.proxy_metrics())
    take(replica.replica_metrics())
    take(fault.fault_metrics())
    take(autoscale.autoscale_metrics())
    from ray_tpu.dag import ring
    take(ring.allreduce_metrics())
    from ray_tpu.train import zero
    take(zero.zero_metrics())
    from ray_tpu.train import ckptio
    take(ckptio.ckpt_metrics())
    from ray_tpu.train import controller
    take(controller.train_metrics())
    from ray_tpu.train import pipeline
    take(pipeline.pipeline_metrics())
    from ray_tpu.util import devmon
    take(devmon.devmon_metrics())
    from ray_tpu.util import health
    take(health.health_metrics())
    from ray_tpu.util import goodput
    take(goodput.goodput_metrics())
    from ray_tpu.util import forensics
    take(forensics.forensics_metrics())
    return out


_RECORD_RE = re.compile(
    r"""events\.record\(\s*(?:(['"])(?P<cat>[^'"]*)\1|(?P<expr>[^,)]+))""")


def scan_event_categories(root: str = None) -> list:
    """Every ``events.record(`` call site under ray_tpu/ as
    ``(relpath:line, category)``; a non-literal first argument scans as
    the special category ``<dynamic>`` (flagged — the budget table
    can't reason about computed categories)."""
    if root is None:
        root = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "ray_tpu")
    found = []
    for dirpath, _dirs, files in os.walk(root):
        for fname in sorted(files):
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            if os.path.join("util", "events.py") in path:
                continue   # the registry itself (docstring mentions)
            with open(path, encoding="utf-8", errors="replace") as f:
                text = f.read()
            for m in _RECORD_RE.finditer(text):
                cat = m.group("cat")
                if cat is None:
                    cat = "<dynamic>"
                line = text.count("\n", 0, m.start()) + 1
                rel = os.path.relpath(path, os.path.dirname(root))
                found.append((f"{rel}:{line}", cat))
    return found


def lint_event_categories(found: list, allowed=None) -> list:
    """Violations for ``(site, category)`` pairs not in ``allowed``
    (default: the events.CATEGORIES registry)."""
    if allowed is None:
        from ray_tpu.util import events
        allowed = set(events.CATEGORIES)
    return sorted(
        f"{site}: event category {cat!r} not registered in "
        f"ray_tpu/util/events.py CATEGORIES"
        for site, cat in found if cat not in allowed)


def lint_category_caps() -> list:
    """Every budget-capped category must itself be registered: a cap
    keyed on an unregistered name would silently create a bucket no
    recorder ever routes into (the "train"/"collective" sub-budgets
    exist to protect task spans from floods — a typo there disables
    the protection without an error anywhere)."""
    from ray_tpu.util import events
    return sorted(
        f"events._CATEGORY_CAPS key {cat!r} not registered in "
        f"events.CATEGORIES"
        for cat in events._CATEGORY_CAPS
        if cat not in events.CATEGORIES)


# Lint-scanned metric families: every string literal in the source
# tree that LOOKS like one of these metric names must actually be
# registered by instantiate_all() — a call site emitting an
# unregistered name would silently create a series the catalog, docs,
# and dashboards don't know about. The scan is literal-based (same
# spirit as the events.record category grep above); names mentioned in
# docstrings/backticks don't match, only quoted strings. The device
# families came with the PR 11 devmon plane; ``health_``/``slo_`` are
# the cluster health plane's (util/health.py).
DEVICE_METRIC_PREFIXES = ("device_", "xla_", "llm_kv_")
HEALTH_METRIC_PREFIXES = ("health_", "slo_")
# ``ckpt_`` came with the durable checkpoint plane (train/ckptio.py).
CKPT_METRIC_PREFIXES = ("ckpt_",)
# ``serve_autoscale_`` is the SLO autoscaler's actuation family
# (serve/autoscale.py); ``llm_kv_`` (above) extends over the paged KV
# cache's block gauges/counters (llm/kvcache.py); ``llm_paged_`` is
# the paged-attention decode family (kernel-vs-gather impl counters,
# llm/kvcache.py + ops/pallas/paged_attention.py); ``llm_spec_`` is
# the speculative-decoding family (accept-rate gauge + draft token
# volume counter, llm/spec.py).
SERVE_METRIC_PREFIXES = ("serve_autoscale_", "llm_paged_",
                         "llm_spec_")
# ``goodput_`` is the step-anatomy ledger's family (util/goodput.py:
# seconds/steps counters + the straggler-rank gauge); ``train_mfu``
# covers extensions of the MFU gauge family.
GOODPUT_METRIC_PREFIXES = ("goodput_", "train_mfu")
# ``allreduce_quant_`` is the wire-codec error family (dag/ring.py):
# one gauge labelled {codec=int8|int4|bf16|fp16|fp32} — a call site
# inventing a sibling series must register it the same way.
COLLECTIVE_METRIC_PREFIXES = ("allreduce_quant_",)
# ``forensics_`` is the hang/desync forensics family (util/forensics.py:
# the stall-rank sentinel gauge + audit/bundle counters).
FORENSICS_METRIC_PREFIXES = ("forensics_",)
METRIC_FAMILY_PREFIXES = (DEVICE_METRIC_PREFIXES
                          + HEALTH_METRIC_PREFIXES
                          + CKPT_METRIC_PREFIXES
                          + SERVE_METRIC_PREFIXES
                          + GOODPUT_METRIC_PREFIXES
                          + COLLECTIVE_METRIC_PREFIXES
                          + FORENSICS_METRIC_PREFIXES)

# prefixed literals that are NOT metric names: control RPC method
# names etc. (Config knob names are exempted wholesale below — the
# health plane reads its knobs via quoted getattr calls).
EXEMPT_METRIC_LITERALS = {"health_state",
                          # derived row field in state.goodput rows
                          # (compute/wall share), not a metric series
                          "goodput_fraction",
                          # goodput ledger anatomy category (collides
                          # with the ckpt_ family), not a series name
                          "ckpt_stall",
                          # health objective name (util/health.py),
                          # not a series name
                          "goodput_straggler",
                          # jax device attribute probed via getattr
                          # (util/goodput.py), not a series name
                          "device_kind",
                          # worker RPC method name for the autopsy
                          # ledger pull (runtime/worker.py, agent.py)
                          "forensics_dump"}

_DEVICE_METRIC_RE = re.compile(
    r"""['"]((?:%s)[a-z0-9_]+)['"]"""
    % "|".join(re.escape(p) for p in METRIC_FAMILY_PREFIXES))


def scan_device_metric_names(root: str = None) -> list:
    """Every quoted device-family metric-name literal under ray_tpu/
    as ``(relpath:line, name)``."""
    if root is None:
        root = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "ray_tpu")
    found = []
    for dirpath, _dirs, files in os.walk(root):
        for fname in sorted(files):
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            with open(path, encoding="utf-8", errors="replace") as f:
                text = f.read()
            for m in _DEVICE_METRIC_RE.finditer(text):
                line = text.count("\n", 0, m.start()) + 1
                rel = os.path.relpath(path, os.path.dirname(root))
                found.append((f"{rel}:{line}", m.group(1)))
    return found


def lint_device_metric_registration(registry: dict,
                                    found: list = None) -> list:
    """Violations for family-prefixed metric literals that no
    registered metric matches (exact name only — a label value like
    "device" doesn't match the prefixed-name regex in the first
    place). Registered EVENT CATEGORIES are exempt ("device_window" /
    "health" are buffer-budget categories, not metric series), as are
    Config knob names (the health plane reads its knobs via quoted
    getattr) and the explicit EXEMPT_METRIC_LITERALS (RPC method
    names)."""
    if found is None:
        found = scan_device_metric_names()
    from dataclasses import fields as _fields

    from ray_tpu.config import Config
    from ray_tpu.util import events
    allowed = (set(registry) | set(events.CATEGORIES)
               | {f.name for f in _fields(Config)}
               | EXEMPT_METRIC_LITERALS)
    return sorted(
        f"{site}: metric literal {name!r} matches a lint-scanned "
        f"family ({'/'.join(METRIC_FAMILY_PREFIXES)}) but is not "
        f"registered by instantiate_all()"
        for site, name in found if name not in allowed)


# THE registry of lint-enforced Config knob families: family label ->
# (name prefix, name suffix). Every knob matching a family must be
# exercised by at least one test module — register new families here
# (one line) instead of cloning the scan.
KNOB_FAMILIES = {
    # deterministic fault injection (rpc, channel, serve, ...;
    # reference: rpc_chaos.h is exercised by its own gtest)
    "chaos": ("testing_", "_failure"),
    # collective auto-tuner (master switch, probe payload, chunk floor)
    "tuner": ("collective_tuner", ""),
    # request tracing (tail-sampling rate, slow-keep threshold)
    "trace": ("trace_", ""),
    # device observability (recompile-storm gate, HBM cadence, duty
    # horizon — util/devmon.py)
    "devmon": ("devmon_", ""),
    # pipeline parallelism (schedule kind, device-ref transport,
    # activation TTL, step timeout — train/pipeline.py)
    "pipeline": ("pipeline_", ""),
    # cluster health plane: time-series store retention/memory bounds
    # + baseline path (util/timeseries.py, util/health.py). The
    # prefix also covers the head liveness knobs (health_check_*) —
    # they are Config health surface too and deserve the same
    # coverage guarantee.
    "health": ("health_", ""),
    # SLO engine: burn thresholds, windows, derived-objective knobs
    "slo": ("slo_", ""),
    # durable checkpoint plane: commit coordinator timeout, restore
    # hash verification, staging double-buffer depth (train/ckptio.py)
    "ckpt": ("ckpt_", ""),
    # preemption-aware shutdown: the SIGTERM grace window
    # (runtime/worker.py + ckptio preemption hooks)
    "preempt": ("preempt_", ""),
    # paged KV cache: block size, pool sizing, prefix-reuse switch
    # (llm/kvcache.py + llm/engine.py paged mode)
    "kvcache": ("kvcache_", ""),
    # SLO-driven replica autoscaling: interval, cooldown, step,
    # utilization deadband (serve/autoscale.py)
    "autoscale": ("serve_autoscale_", ""),
    # goodput ledger: level switch + straggler z-threshold/window
    # (util/goodput.py, train/controller.py detector)
    "goodput": ("goodput_", ""),
    # speculative decoding: master switch, draft length, n-gram
    # horizon, accept-rate backoff window (llm/spec.py + llm/engine.py)
    "spec": ("spec_", ""),
    # wire codec selection + error feedback: auto-codec error bound /
    # min payload (collective_codec_*) and the EF master switch
    # (codec_error_feedback) — train/collective.py + dag/tuner.py.
    # A family may enumerate SEVERAL (prefix, suffix) pairs.
    "codec": (("collective_codec", ""), ("codec_error_feedback", "")),
    # hang & desync forensics: ledger switch/size, stall-watchdog
    # timeout, pre-flight verify level, bundle dir (util/forensics.py,
    # train/collective.py preflight, train/controller.py watchdog)
    "forensics": ("forensics_", ""),
}


def family_knobs(family: str) -> list:
    """Every ray_tpu/config.py Config knob in one lint family. A
    family spec is one (prefix, suffix) pair or a tuple of them."""
    from dataclasses import fields

    from ray_tpu.config import Config
    spec = KNOB_FAMILIES[family]
    pairs = spec if spec and isinstance(spec[0], tuple) else (spec,)
    return sorted(f.name for f in fields(Config)
                  if any(f.name.startswith(prefix)
                         and f.name.endswith(suffix)
                         for prefix, suffix in pairs))


def chaos_knobs() -> list:
    return family_knobs("chaos")


def tuner_knobs() -> list:
    return family_knobs("tuner")


def trace_knobs() -> list:
    return family_knobs("trace")


def _lint_knob_tests(label: str, knobs: list,
                     tests_dir: str = None) -> list:
    """THE knob-coverage scan every knob family shares: each named
    Config knob must appear in at least one test module (by name or
    RAY_TPU_* env form) — a config surface nothing exercises rots
    silently."""
    if tests_dir is None:
        tests_dir = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tests")
    blob = []
    for fname in sorted(os.listdir(tests_dir)):
        if fname.endswith(".py"):
            with open(os.path.join(tests_dir, fname),
                      encoding="utf-8", errors="replace") as f:
                blob.append(f.read())
    blob = "\n".join(blob)
    return sorted(
        f"{label} knob {k!r} (ray_tpu/config.py) has no test "
        f"exercising it under tests/"
        for k in knobs
        if k not in blob and f"RAY_TPU_{k.upper()}" not in blob)


def lint_knob_tests(families=None, tests_dir: str = None) -> list:
    """Violations across ALL registered knob families (or the named
    subset) — main() runs this one scan instead of per-family copies."""
    out = []
    for fam in (families if families is not None else KNOB_FAMILIES):
        out += _lint_knob_tests(fam, family_knobs(fam), tests_dir)
    return sorted(out)


def lint_tuner_knob_tests(tests_dir: str = None,
                          knobs: list = None) -> list:
    return _lint_knob_tests(
        "tuner", tuner_knobs() if knobs is None else knobs, tests_dir)


def lint_chaos_knob_tests(tests_dir: str = None,
                          knobs: list = None) -> list:
    return _lint_knob_tests(
        "chaos", chaos_knobs() if knobs is None else knobs, tests_dir)


def main() -> int:
    registered = instantiate_all()
    from ray_tpu.util import metrics
    errors = lint(metrics._REGISTRY)
    found = scan_event_categories()
    errors += lint_event_categories(found)
    errors += lint_category_caps()
    errors += lint_knob_tests()
    errors += lint_device_metric_registration(registered)
    if errors:
        print(f"{len(errors)} metric/event lint violation(s):")
        for e in errors:
            print(f"  {e}")
        return 1
    print(f"metrics lint ok: {len(metrics._REGISTRY)} registered "
          f"metric(s) pass, {len(found)} events.record call site(s) "
          f"over registered categories")
    return 0


if __name__ == "__main__":
    sys.exit(main())
