"""The paged-decode kernel's share of its roofline: the KV bytes the
LIVE contexts require (every valid position's K and V once per layer per
step, plus queries and outputs) at the chip's HBM bandwidth, over the
kernel's time in the trace. The contexts are the client's: every output
token received between the trace's edges is one step of one slot over
its prompt and the tokens before it. The kernel is memory-bound (4 flops a byte
of KV at group 4), so bandwidth is the bound that rules."""
from harness import kernels, peaks
from harness.window import client_counts


def read(ctx):
    tr, edges = ctx.get("trace"), ctx.get("trace_edges")
    if not tr or not edges or "paged_decode" not in tr["kernels"]:
        return None
    c = client_counts(ctx["requests"], edges)
    if not c["decode_slot_steps"]:
        return None
    m = ctx["model"]
    kvh = m["num_key_value_heads"]
    g = m["num_attention_heads"] // kvh
    hd = m["hidden_size"] // m["num_attention_heads"]
    pk = peaks.peaks(ctx["info"]["device"]["kind"])
    need = m["num_hidden_layers"] * kernels.paged_decode_bytes(
        c["decode_ctx_tokens"], c["decode_slot_steps"], kvh, g, hd)
    flops = m["num_hidden_layers"] * kernels.paged_decode_flops(
        c["decode_ctx_tokens"], kvh, g, hd)
    least = max(need / pk["hbm_bytes_per_s"], flops / pk["bf16_flops"])
    return 100.0 * least / tr["kernels"]["paged_decode"]["s"]
