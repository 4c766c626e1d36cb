def read(ctx):
    """Peak bytes in use on the fullest chip over its limit, from the
    backend's memory_stats."""
    i = ctx["info"]
    if not i.get("memory_limit_bytes"):
        return None
    return 100.0 * i["memory_peak_bytes"] / i["memory_limit_bytes"]
