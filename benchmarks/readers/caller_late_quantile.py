"""How late the open-loop generator sent (send time minus due time): a
starved generator must not read as a fast server."""
from harness.window import quantile


def read(ctx, q):
    v = quantile(ctx.get("late_s") or [], q)
    return None if v is None else v * 1e3
