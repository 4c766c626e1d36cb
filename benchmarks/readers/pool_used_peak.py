def read(ctx):
    """Most blocks of the KV pool in use at once during the window, as a
    share of the pool (the trash block is not allocatable)."""
    c = ctx["counters"]["window"]
    if not c.get("pool_blocks"):
        return None
    return 100.0 * c["blocks_used_peak"] / (c["pool_blocks"] - 1)
