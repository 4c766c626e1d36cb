from harness.window import client_counts


def read(ctx):
    """Device time of the prefill programs in the trace (an executed
    module is 'prefill' if a flash forward ran in it and no paged
    kernel) over the prompt tokens whose first token reached the client
    between the trace's edges, per thousand."""
    tr, edges = ctx.get("trace"), ctx.get("trace_edges")
    if not tr or not edges:
        return None
    p = tr["programs"].get("prefill")
    tokens = client_counts(ctx["requests"], edges)["prefill_tokens"]
    if not p or not tokens:
        return None
    return 1e6 * p["s"] / tokens
