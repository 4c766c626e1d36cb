"""A median over the request spans the program's hops record
(``ray_tpu.util.tracing``: proxy ``request`` / ``queue`` / ``handler``,
handle ``submit``, replica ``queue`` / ``handler``, engine ``queue`` /
``prefill`` / ``generate``), collected ONCE a run through the public
``ray_tpu.timeline(all_nodes=True)`` while the runtime is still up, and
kept on ``ctx`` for the next metric. Spans are wall-clock; the cut is
made through ``tracing.wall``, the one offset the program's own
monotonic stamps go through (CLOCK_MONOTONIC is one clock for every
process of a host). The cut is at the TRACE's edges where the run has
them: the counters and the device time these medians are read beside
come from the traced seconds, and the profiler's stop stalls the
replica for the rest of a traced window (a median over the whole
window then reads the stall's backlog: 22.5 ms a token against 11.8
untraced in ``serve-exaone-reason-open``, PERF.md, PR 37). Without
them it is the window."""
import sys

from harness.window import inside, quantile


def spans(ctx) -> list:
    """Every request span of the run ([] where there is no runtime to
    ask, or it has none)."""
    if "request_spans" not in ctx:
        import ray_tpu
        evs = []
        if ray_tpu.is_initialized():    # timeline() would start one
            try:
                evs = ray_tpu.timeline(all_nodes=True)
            except Exception as e:  # noqa: BLE001 - a reader never raises
                print(f"request_spans: no timeline to read ({e!r})",
                      file=sys.stderr)
        ctx["request_spans"] = [
            e for e in evs
            if e.get("cat") == "request" and e.get("name") == "span"]
    return ctx["request_spans"]


def read(ctx, component, seg, num, minus=(), den=None, den_less=0,
         since=None, scale=1.0, q=0.5):
    """Quantile ``q`` over the ``component`` / ``seg`` spans of
    ``scale * (sum of num - sum of minus) / (den - den_less)``, each
    name an attribute of the span (``ts`` is its start).

    Without ``since`` a span counts when it ENDED inside the cut (the
    module's docstring). With ``since = [component, seg]`` the same
    trace's span of that kind is joined by trace id, its start is
    subtracted, and the span counts when that one STARTED inside the
    cut (a request that arrived in it). A span that lacks an attribute
    is left out; None when none is left (a program that does not
    record them)."""
    from ray_tpu.util import tracing
    window = tuple(tracing.wall(t)
                   for t in ctx.get("trace_edges") or ctx["window"])
    all_spans = spans(ctx)
    first = {}
    if since:
        for e in all_spans:
            if [e.get("component"), e.get("seg")] == list(since):
                first.setdefault(e.get("trace"), e["ts"])
    values = []
    for e in all_spans:
        if e.get("component") != component or e.get("seg") != seg:
            continue
        if any(e.get(k) is None for k in (*num, *minus, *([den] if den
                                                          else ()))):
            continue
        # what decides whether it counts, and what is subtracted
        t0 = first.get(e.get("trace")) if since \
            else e["ts"] + e.get("dur", 0.0)
        by = (e[den] - den_less) if den else 1
        if not inside(t0, window) or by <= 0:
            continue
        top = sum(e[k] for k in num) - sum(e[k] for k in minus) \
            - (t0 if since else 0.0)
        values.append(scale * top / by)
    return quantile(values, q)
