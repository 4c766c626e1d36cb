"""Quantile over requests of (last token time - first token time) /
(output tokens - 1), at the client; every request that completed inside
the window counts."""
from harness.window import inside, quantile


def read(ctx, q):
    w = ctx["window"]
    vals = [(r.t_tokens[-1] - r.t_tokens[0]) / (len(r.t_tokens) - 1)
            for r in ctx["requests"]
            if not r.error and len(r.t_tokens) == r.max_new > 1
            and inside(r.t_tokens[-1], w)]
    v = quantile(vals, q)
    return None if v is None else v * 1e3
