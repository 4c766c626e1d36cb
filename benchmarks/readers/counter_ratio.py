def read(ctx, num, den, scale=1.0, scope="window", minus=None):
    """scale * (num - minus) / den of the engine's public counters'
    change over the window (or over the traced part of it)."""
    c = ctx["counters"].get(scope)
    if not c or not c.get(den) or num not in c:
        return None
    return scale * (c[num] - (c[minus] if minus else 0)) / c[den]
