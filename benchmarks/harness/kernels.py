"""Kernels: how the trace names them, and what each call REQUIRES.

The program gives its Pallas kernels no name (``kernel_metadata={}`` in
the HLO), so a kernel is recognised by the signature of its
``tpu_custom_call``: operand count, ranks and dtypes. Operations and
bytes are what the algorithm needs for the call, from its shapes - not
what the kernel happens to execute (masked blocks it skips or fails to
skip, recomputation, padding).
"""

from __future__ import annotations

import re

_SHAPE = re.compile(r"\b(pred|[subf]\d+|bf16)\[([\d,]*)\]")
_OPCODE = re.compile(r"^%?([\w.\-]+) = (.*?)\s([a-z][a-z\-]*)\(")

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "collective-broadcast")
CONTAINERS = ("while", "conditional", "call")


def parse_op(hlo: str) -> dict:
    """{name, opcode, result: [(dtype, dims)], operands: [(dtype, dims)]}
    from one HLO instruction as the trace's event name carries it."""
    m = _OPCODE.match(hlo)
    if not m:
        return {"name": hlo.split(" ")[0].lstrip("%"), "opcode": "",
                "result": [], "operands": []}
    name, result, opcode = m.groups()
    rest = hlo[m.end():]
    depth, end = 1, len(rest)
    for i, ch in enumerate(rest):
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            end = i
            break

    def shapes(s):
        return [(d, tuple(int(x) for x in dims.split(",") if x))
                for d, dims in _SHAPE.findall(s)]
    return {"name": name, "opcode": opcode, "result": shapes(result),
            "operands": shapes(rest[:end]),
            "custom_kernel": "tpu_custom_call" in rest[end:]}


def short_name(op: dict) -> str:
    """A stable, compact label for the breakdown."""
    res = op["result"][0] if op["result"] else ("", ())
    return "_".join([op["name"], op["opcode"], res[0],
                     "_".join(str(d) for d in res[1])])


def classify(op: dict) -> str | None:
    """Which of the program's kernels a custom call is, or None."""
    if op["opcode"] != "custom-call" or not op.get("custom_kernel"):
        return None
    ops, res = op["operands"], op["result"]
    ranks = [len(s[1]) for s in ops]
    if (len(ops) == 5 and ops[0][0] == "s32" and ops[1][0] == "s32"
            and ranks[2:] == [4, 4, 4]):
        return "paged_decode"
    if len(ops) == 3 and ranks == [3, 3, 3]:
        return "flash_fwd"
    if len(ops) == 6 and ranks == [3] * 6:
        return "flash_bwd_dkv" if len(res) == 2 else "flash_bwd_dq"
    return "unknown_kernel"


# --- what a call requires --------------------------------------------------


def causal_pairs(n_q: int, n_kv: int | None = None) -> int:
    """(query, key) pairs of causal attention where the ``n_q`` queries
    are the LAST rows of an ``n_kv``-long sequence."""
    n_kv = n_q if n_kv is None else n_kv
    return n_q * (n_kv - n_q) + n_q * (n_q + 1) // 2


def flash_fwd_flops(pairs: int, heads: int, head_dim: int) -> int:
    """QK^T and PV: two matmuls of 2*head_dim flops per pair."""
    return 4 * head_dim * heads * pairs


def flash_bwd_flops(pairs: int, heads: int, head_dim: int) -> int:
    """Recompute S, then dV, dP, dQ, dK: five matmuls per pair. (The
    program's two-kernel backward recomputes S and dP twice; that is
    its cost, not the algorithm's.)"""
    return 10 * head_dim * heads * pairs


def flash_fwd_bytes(n_q: int, n_kv: int, heads: int, kv_heads: int,
                    head_dim: int, itemsize: int = 2) -> int:
    """Read Q, K, V once and write O once."""
    return itemsize * head_dim * (2 * n_q * heads + 2 * n_kv * kv_heads)


def paged_decode_bytes(ctx_tokens: int, slot_steps: int, kv_heads: int,
                       group: int, head_dim: int,
                       kv_itemsize: int = 2) -> int:
    """One layer: every live context position's K and V read once, each
    slot's query read (bf16) and output written (f32) once per step."""
    kv = 2 * ctx_tokens * kv_heads * head_dim * kv_itemsize
    qo = slot_steps * kv_heads * group * head_dim * (2 + 4)
    return kv + qo


def paged_decode_flops(ctx_tokens: int, kv_heads: int, group: int,
                       head_dim: int) -> int:
    return 4 * head_dim * kv_heads * group * ctx_tokens
