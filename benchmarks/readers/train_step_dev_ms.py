def read(ctx):
    """Device time of one train-step program, from the trace."""
    tr = ctx.get("trace")
    p = tr and tr["programs"].get("train")
    if not p or not p["calls"]:
        return None
    return 1e3 * p["s"] / p["calls"]
