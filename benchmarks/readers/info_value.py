def read(ctx, key, scale=1.0):
    """scale * what the engine's ``stats`` say under ``key`` (a size the
    engine was built with, not a count that moves)."""
    v = ctx["info"].get(key)
    return None if v is None else scale * v
