"""Device time a decode step spends putting the new token's K and V
rows into the pool: in a serve cell the custom calls that are neither
the paged-decode kernel nor a flash kernel are the pool's block writer
(``kv_write`` of ops/pallas/paged_attention.py; ``harness/kernels.py
classify`` puts its six operands in ``unknown_kernel``, and
tests/test_aot_tpu_compile.py pins that), once a layer a step. Steps
are counted as ``decode_dev_ms_per_step`` counts them: the paged
kernel's calls over the layers. Where the program has no such writer
(before PR 31 the write was XLA's own), or the run no trace, there is
nothing to read."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    w = tr["kernels"].get("unknown_kernel")
    k = tr["kernels"].get("paged_decode")
    if not w or not k or not k["calls"]:
        return None
    steps = k["calls"] / ctx["model"]["num_hidden_layers"]
    return 1e3 * w["s"] / steps
