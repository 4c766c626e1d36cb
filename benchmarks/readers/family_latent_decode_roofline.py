"""The latent-decode walk's share of its roofline: what the LIVE contexts
REQUIRE of the kernel by the family's counts - every context position's
cache row once a layer plus the queries in and sums out
(``latent_decode_required_bytes``) at the chip's HBM bandwidth, or the
scores and the weighted sums of every head over those rows
(``latent_decode_required_flops``) at its bf16 peak, whichever takes
longer (32 heads share one row, so the two are within a factor of a few)
- over the time of the kernel named ``latent_decode`` in this run's
profile (``named_kernel``: told by its own name). The contexts are the
client's: every output token received between the trace's edges is one
step of one slot over its prompt and the tokens before it. Where the
program has no such kernel, the family no such count, or the run no
trace, there is nothing to read."""
from harness import peaks, spec
from harness.window import inside

KERNEL = "latent_decode"


def read(ctx):
    edges = ctx.get("trace_edges")
    if not ctx.get("trace") or not edges:
        return None
    fam = spec.family(ctx["cell"]["family"])
    nbytes = getattr(fam, "latent_decode_required_bytes", None)
    flops = getattr(fam, "latent_decode_required_flops", None)
    if nbytes is None or flops is None:
        return None
    named = spec._module("readers", "named_kernel")
    path = named._profile(ctx)
    found = path and named.kernel_time(path, KERNEL)
    contexts = [r.prompt_len + i for r in ctx["requests"]
                for i, t in enumerate(r.t_tokens) if i and inside(t, edges)]
    if not found or not found[0] or not contexts:
        return None
    pk = peaks.peaks(ctx["info"]["device"]["kind"])
    least = max(nbytes(ctx["model"], contexts) / pk["hbm_bytes_per_s"],
                flops(ctx["model"], contexts) / pk["bf16_flops"])
    return 100.0 * least / found[0]
