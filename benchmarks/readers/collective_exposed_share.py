def read(ctx):
    """Time a collective was in flight on a device while no compute ran
    there, as a share of the traced window (mean over the chips)."""
    tr = ctx.get("trace")
    if not tr or not tr.get("window_s"):
        return None
    return 100.0 * tr["collective_exposed_s"] / tr["window_s"]
