"""A state-space scope's share of its roofline: the least time the chip
could take for the work the scope REQUIRES, by the family's count, over the
device time of the instructions under the scope (``readers/scope_dev_ms.py
scope_time``; the scopes are plain XLA, no kernel has a name to go by).

``what: "step"``: ``ssm.step`` in the decode programs. The family's
``ssm_step_required_bytes`` / ``_flops`` of the LIVE slot-steps the profile
holds: the steps the profile holds (``scope_dev_ms_counted
steps_in_profile``) times the mean live slots a step between the trace's
edges (the engine's ``slot_steps`` over ``block_steps``: counters and
device time are brought to the same steps, as ``named_kernel`` does for
the grouped matmuls). A live slot's state read once and written once, its
rows in and out: what ANY implementation moves, so a program that moves
every slot's state reads under its live share.
``what: "scan"``: ``ssm.scan`` in the prefill programs, against
``ssm_scan_required_flops`` / ``_bytes`` of the prompts whose first token
reached the client between the trace's edges (the recurrence's own count:
the chunked form does more).
The binding one of operations at the chip's bf16 peak and bytes at its HBM
bandwidth. Where the family has no such count, the program no such scope
or the run no trace, there is nothing to read."""
from harness import peaks, spec
from harness.window import inside


def read(ctx, what):
    fam = spec.family(ctx["cell"]["family"])
    counted = spec._module("readers", "scope_dev_ms_counted")
    name = "ssm_step" if what == "step" else "ssm_scan"
    flops = getattr(fam, name + "_required_flops", None)
    nbytes = getattr(fam, name + "_required_bytes", None)
    if flops is None or nbytes is None or not ctx.get("trace_edges"):
        return None
    if what == "step":
        c = ctx["counters"].get("trace") or {}
        steps = counted.steps_in_profile(ctx)
        if not steps or not c.get("block_steps_sum") \
                or not c.get("slot_steps_sum"):
            return None
        work = steps * c["slot_steps_sum"] / c["block_steps_sum"]
        seconds = counted.scope_seconds(ctx, "ssm.step", "decode")
    else:
        work = [r.prompt_len for r in ctx["requests"]
                if r.t_tokens and inside(r.t_tokens[0], ctx["trace_edges"])]
        seconds = work and counted.scope_seconds(ctx, "ssm.scan", "prefill")
    if not work or not seconds:
        return None
    pk = peaks.peaks(ctx["info"]["device"]["kind"])
    least = max(flops(ctx["model"], work) / pk["bf16_flops"],
                nbytes(ctx["model"], work) / pk["hbm_bytes_per_s"])
    return 100.0 * least / seconds
