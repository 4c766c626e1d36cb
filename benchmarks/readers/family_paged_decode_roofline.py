"""The paged-decode kernels' share of their roofline for a family that
counts its own bytes (layers of several kinds): the KV bytes the LIVE
contexts REQUIRE by the family's ``paged_decode_required_bytes`` (a
window layer min(context, window) positions, a global layer all; head
size, layer kinds and window from the configuration) at the chip's HBM
bandwidth, over the time of every call of the paged-decode class in the
trace (both kinds of layer have the one operand signature). The contexts
are the client's: every output token received between the trace's edges
is one step of one slot over its prompt and the tokens before it.
Bandwidth rules (a few flops a byte). Where the family has no such
count, or the run no trace, there is nothing to read."""
from harness import peaks, spec
from harness.window import inside


def read(ctx):
    tr, edges = ctx.get("trace"), ctx.get("trace_edges")
    if not tr or not edges or "paged_decode" not in tr["kernels"]:
        return None
    count = getattr(spec.family(ctx["cell"]["family"]),
                    "paged_decode_required_bytes", None)
    contexts = [r.prompt_len + i for r in ctx["requests"]
                for i, t in enumerate(r.t_tokens) if i and inside(t, edges)]
    if count is None or not contexts:
        return None
    pk = peaks.peaks(ctx["info"]["device"]["kind"])
    least = count(ctx["model"], contexts) / pk["hbm_bytes_per_s"]
    return 100.0 * least / tr["kernels"]["paged_decode"]["s"]
