"""Quantile of the time from a request's DUE time to its first token at
the client, over every request whose first token arrived in the window.
A request that failed before any token counts as the longest wait."""
from harness.window import inside, quantile


def read(ctx, q):
    w = ctx["window"]
    vals = [r.t_tokens[0] - r.t_due for r in ctx["requests"]
            if r.t_tokens and inside(r.t_tokens[0], w)]
    vals += [float("inf") for r in ctx["requests"]
             if r.error and not r.t_tokens and inside(r.t_end, w)]
    v = quantile(vals, q)
    return None if v is None or v == float("inf") else v * 1e3
