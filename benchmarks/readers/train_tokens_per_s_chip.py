def read(ctx):
    """Tokens of the whole steps that ended in the window, over the time
    to the end of the last of them, per chip."""
    t = ctx["train"]
    return t["steps"] * t["tokens_per_step"] / t["elapsed_s"] / t["chips"]
