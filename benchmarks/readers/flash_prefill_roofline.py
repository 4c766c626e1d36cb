"""Flash prefill's share of its roofline: the causal (query, key) pairs
of the prompts whose first token reached the client between the
trace's edges (no padding, no masked blocks), 4 *
head_dim flops a pair a head, at the chip's bf16 peak - or the bytes of
Q, K, V, O at its bandwidth, whichever takes longer (compute rules from
a few hundred tokens up) - over the forward kernel's time in the
trace."""
from harness import kernels, peaks
from harness.window import client_counts


def read(ctx):
    tr, edges = ctx.get("trace"), ctx.get("trace_edges")
    if not tr or not edges or "flash_fwd" not in tr["kernels"]:
        return None
    c = client_counts(ctx["requests"], edges)
    if not c["prefill_pairs"]:
        return None
    m = ctx["model"]
    h, kvh = m["num_attention_heads"], m["num_key_value_heads"]
    hd, L = m["hidden_size"] // h, m["num_hidden_layers"]
    pk = peaks.peaks(ctx["info"]["device"]["kind"])
    flops = L * kernels.flash_fwd_flops(c["prefill_pairs"], h, hd)
    nbytes = L * kernels.flash_fwd_bytes(
        c["prefill_tokens"], c["prefill_tokens"], h, kvh, hd)
    least = max(flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / tr["kernels"]["flash_fwd"]["s"]
