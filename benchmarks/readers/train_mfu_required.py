"""Model FLOP/s utilisation on REQUIRED operations: what forward and
backward need per token by the cell's family (for ``llama``: 6 per
matmul parameter, causal attention, no recomputation) times the tokens
of a step, over the step's device time, the chips and the published
bf16 peak."""
from harness import peaks, spec


def read(ctx):
    tr = ctx.get("trace")
    p = tr and tr["programs"].get("train")
    if not p or not p["calls"]:
        return None
    t, m = ctx["train"], ctx["model"]
    pk = peaks.peaks(ctx["info"]["device"]["kind"])
    per_tok = spec.family(
        ctx["cell"]["family"]).train_required_flops_per_token(
            m, m["num_hidden_layers"], t["seq"])
    step_s = p["s"] / p["calls"]
    return 100.0 * per_tok * t["tokens_per_step"] / (
        step_s * t["chips"] * pk["bf16_flops"])
