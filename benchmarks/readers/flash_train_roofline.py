"""The flash kernels' share of their roofline in a train step. Each
call's required operations come from its own shapes in the trace (q of
(batch*heads, seq, head_dim) on this device): causal pairs * 4 *
head_dim forward, * 10 backward (dQ and dK/dV kernels together). Compute
rules at 4096 tokens. Every call counts, the remat's second forward
too: it does the same required work again."""
from harness import kernels, peaks


def read(ctx, which):
    tr = ctx.get("trace")
    if not tr:
        return None
    names = ["flash_fwd"] if which == "fwd" else ["flash_bwd_dq",
                                                  "flash_bwd_dkv"]
    ks = [tr["kernels"].get(n) for n in names]
    if not all(ks):
        return None
    pk = peaks.peaks(ctx["info"]["device"]["kind"])
    calls = ks[0]["calls"]
    bh, sq, hd = ks[0]["q_shape"]
    pairs = kernels.causal_pairs(sq)
    per_call = (kernels.flash_fwd_flops if which == "fwd"
                else kernels.flash_bwd_flops)(pairs, bh, hd)
    least = calls * per_call / pk["bf16_flops"]
    return 100.0 * least / sum(k["s"] for k in ks)
