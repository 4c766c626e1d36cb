def read(ctx):
    """What a decode step costs around its device time: the engine's
    own window of a block (``engine.decode.dispatch`` start to
    ``engine.decode.readback`` end: ``loop_decode_dispatch_sum`` +
    ``loop_decode_readback_sum``) minus the device time of the decode
    programs in the trace, both over the decode steps the ENGINE counted
    between the trace's edges (``block_steps_sum``: the denominator of
    ``decode_dev_ms_per_counted_step``). It is the launch, the executor
    hop inside the window and the device-to-host copy of the tokens:
    what dispatching block n + 1 before reading back block n would
    hide. A program without the loop's phase histograms gives
    nothing."""
    tr = ctx.get("trace")
    c = ctx["counters"].get("trace") or {}
    steps = c.get("block_steps_sum")
    if not tr or not steps or not tr["programs"].get("decode") \
            or "loop_decode_dispatch_sum" not in c \
            or "loop_decode_readback_sum" not in c:
        return None
    window_s = c["loop_decode_dispatch_sum"] + c["loop_decode_readback_sum"]
    return 1e3 * (window_s - tr["programs"]["decode"]["s"]) / steps
