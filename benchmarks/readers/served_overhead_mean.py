"""Mean first-token time at the client (from the send) minus the
engine's own mean submit-to-first-token wall time (llm_ttft_wall_s) over
the same window: what proxy, router, replica and the SSE hop add."""
from harness.window import inside


def read(ctx):
    w = ctx["window"]
    c = ctx["counters"]["window"]
    mine = [r.t_tokens[0] - r.t_send for r in ctx["requests"]
            if r.t_tokens and inside(r.t_tokens[0], w)]
    if not mine or not c.get("ttft_wall_count"):
        return None
    engine = c["ttft_wall_sum"] / c["ttft_wall_count"]
    return (sum(mine) / len(mine) - engine) * 1e3
