"""Flash prefill's share of its roofline for a family that counts its
own operations (layers of several kinds): the (query, key) pairs inside
each layer's mask - the band on a window layer - of the prompts whose
first token reached the client between the trace's edges, by the
family's ``flash_prefill_required_flops`` at the chip's bf16 peak, or
its ``flash_prefill_required_bytes`` at the HBM bandwidth, whichever
takes longer, over the time of every call of the flash-forward class in
the trace (both kinds of layer have the one operand signature). Where
the family has no such count, or the run no trace, there is nothing to
read."""
from harness import peaks, spec
from harness.window import inside


def read(ctx):
    tr, edges = ctx.get("trace"), ctx.get("trace_edges")
    if not tr or not edges or "flash_fwd" not in tr["kernels"]:
        return None
    fam = spec.family(ctx["cell"]["family"])
    flops = getattr(fam, "flash_prefill_required_flops", None)
    nbytes = getattr(fam, "flash_prefill_required_bytes", None)
    prompts = [r.prompt_len for r in ctx["requests"]
               if r.t_tokens and inside(r.t_tokens[0], edges)]
    if flops is None or nbytes is None or not prompts:
        return None
    pk = peaks.peaks(ctx["info"]["device"]["kind"])
    least = max(flops(ctx["model"], prompts) / pk["bf16_flops"],
                nbytes(ctx["model"], prompts) / pk["hbm_bytes_per_s"])
    return 100.0 * least / tr["kernels"]["flash_fwd"]["s"]
