"""Shared by the client-side readers: which samples fall in the window."""


def quantile(values, q):
    """Nearest-rank quantile of all the values given."""
    import math
    if not values:
        return None
    v = sorted(values)
    return v[max(0, min(len(v) - 1, math.ceil(q * len(v)) - 1))]


def inside(t, window):
    return t is not None and window[0] <= t < window[1]


def client_counts(requests, edges) -> dict:
    """What the device was REQUIRED to do between two instants, from the
    client's own records (the engine's public counters do not carry
    it): a prompt counts when its first token arrives - its tokens and
    its causal (query, key) pairs - and output token i (i >= 1) is one
    decode step of one slot over a context of prompt + i positions.
    Tokens reach the client a decode block after the device made them,
    so both edges are late by the same block or so."""
    out = dict(prefill_tokens=0, prefill_pairs=0, decode_slot_steps=0,
               decode_ctx_tokens=0)
    for r in requests:
        for i, t in enumerate(r.t_tokens):
            if not inside(t, edges):
                continue
            if i == 0:
                n = r.prompt_len
                out["prefill_tokens"] += n
                out["prefill_pairs"] += n * (n + 1) // 2
            else:
                out["decode_slot_steps"] += 1
                out["decode_ctx_tokens"] += r.prompt_len + i
    return out
