def read(ctx):
    """Device time of the decode programs in the trace (an executed
    module is 'decode' if the paged kernel ran in it) over the decode
    steps in the same trace: the paged kernel runs once a layer a step,
    so steps = its calls / layers."""
    tr = ctx.get("trace")
    if not tr:
        return None
    p, k = tr["programs"].get("decode"), tr["kernels"].get("paged_decode")
    if not p or not k or not k["calls"]:
        return None
    steps = k["calls"] / ctx["model"]["num_hidden_layers"]
    return 1e3 * p["s"] / steps
