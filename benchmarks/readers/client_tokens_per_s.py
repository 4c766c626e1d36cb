"""Tokens processed per second as the client sees them PRODUCED: a
prompt's tokens count when its first token arrives (the prefill has then
run), each output token when it is received; both clipped to the window,
so a request in flight at either edge counts for what fell inside."""
from harness.window import inside


def read(ctx):
    w = ctx["window"]
    total = 0
    for r in ctx["requests"]:
        if r.t_tokens and inside(r.t_tokens[0], w):
            total += r.prompt_len
        total += sum(1 for t in r.t_tokens if inside(t, w))
    return total / (w[1] - w[0])
