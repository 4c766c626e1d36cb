"""The grouped-matmul kernels of a mixture-of-experts train step: their
device time a step, and their share of the roofline. In a train cell
the custom calls that are not flash kernels are the grouped matmuls
(``harness/kernels.py classify`` puts them in ``unknown_kernel``;
tests/test_aot_tpu_compile.py pins that). The required operations come
from the configuration by the family's count, not from the number of
calls: 2 per expert parameter a token is routed to, times the passes
the configured step makes. Compute rules (about 512 FLOP a byte at 2,048
rows an expert). Where the program has no such kernel, the family no
such count or the run no trace, there is nothing to read."""
from harness import peaks, spec


def read(ctx, what):
    tr = ctx.get("trace")
    k = tr and tr["kernels"].get("unknown_kernel")
    p = tr and tr["programs"].get("train")
    if not k or not p or not p["calls"] or not k["s"]:
        return None
    step_s = k["s"] / p["calls"]
    if what == "dev_ms":
        return 1e3 * step_s
    count = getattr(spec.family(ctx["cell"]["family"]),
                    "gmm_required_flops_per_step", None)
    if count is None:
        return None
    t, m = ctx["train"], ctx["model"]
    pk = peaks.peaks(ctx["info"]["device"]["kind"])
    flops = count(m, m["num_hidden_layers"], t["tokens_per_step"])
    return 100.0 * flops / (t["chips"] * pk["bf16_flops"]) / step_s
