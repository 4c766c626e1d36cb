"""The paged-decode kernel's share of its roofline, as
``paged_decode_roofline.py`` computes it (the KV bytes the LIVE contexts
require at the chip's HBM bandwidth, over the kernel's time in the
trace), with the contexts the ENGINE counted between the trace's edges
(``llm_decode_ctx_tokens`` and ``llm_decode_slot_steps``, observed as
each block is read back) in place of the client's token stamps, which
reach the client a block after the device made them. A block in flight
at an edge is counted on one side only."""
from harness import kernels, peaks


def read(ctx):
    tr = ctx.get("trace")
    c = ctx["counters"].get("trace") or {}
    if not tr or "paged_decode" not in tr["kernels"] \
            or not c.get("slot_steps_sum") or not c.get("ctx_tokens_sum"):
        return None
    m = ctx["model"]
    kvh = m["num_key_value_heads"]
    g = m["num_attention_heads"] // kvh
    hd = m["hidden_size"] // m["num_attention_heads"]
    pk = peaks.peaks(ctx["info"]["device"]["kind"])
    need = m["num_hidden_layers"] * kernels.paged_decode_bytes(
        c["ctx_tokens_sum"], c["slot_steps_sum"], kvh, g, hd)
    flops = m["num_hidden_layers"] * kernels.paged_decode_flops(
        c["ctx_tokens_sum"], kvh, g, hd)
    least = max(need / pk["hbm_bytes_per_s"], flops / pk["bf16_flops"])
    return 100.0 * least / tr["kernels"]["paged_decode"]["s"]
