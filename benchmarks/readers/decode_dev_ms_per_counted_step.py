def read(ctx):
    """Device time of the decode programs in the trace (an executed
    module is 'decode' if the paged kernel ran in it) over the decode
    steps the ENGINE counted between the trace's edges
    (``llm_decode_block_steps``, observed as each block is read back).
    The count does not depend on how many kernel calls a layer makes.
    A block in flight at an edge is counted on one side only: at most
    one block in the ~17 of an 8-s trace, opposite in sign at the two
    edges."""
    tr = ctx.get("trace")
    steps = (ctx["counters"].get("trace") or {}).get("block_steps_sum")
    if not tr or not steps or not tr["programs"].get("decode"):
        return None
    return 1e3 * tr["programs"]["decode"]["s"] / steps
