def read(ctx):
    """What is left around a decode step once its device time and its
    stall are taken out: the engine's own windows of the blocks read
    back between the trace's edges (``llm_decode_block_window_s``:
    windows tile the time between read-backs) less what the slots
    stalled between blocks (``llm_decode_gap_s``), less the device time
    of the decode programs in the trace, all over the decode steps the
    ENGINE counted there (``block_steps_sum``, the denominator of
    ``decode_dev_ms_per_counted_step``). It is the launch, the copy
    back of the tokens and the edge error of the count (a block in
    flight at an edge is counted on one side only): a small positive
    number, where ``decode_launch_ms_per_step`` subtracts device time
    from two spans that no longer enclose it. A program without the
    window counter gives nothing."""
    tr = ctx.get("trace")
    c = ctx["counters"].get("trace") or {}
    steps = c.get("block_steps_sum")
    if not tr or not steps or not tr["programs"].get("decode") \
            or "block_window_sum" not in c or "gap_sum" not in c:
        return None
    around_s = c["block_window_sum"] - c["gap_sum"]
    return 1e3 * (around_s - tr["programs"]["decode"]["s"]) / steps
