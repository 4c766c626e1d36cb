def read(ctx):
    """Process start to the opening of the measured window: runtime and
    replica start, weights, compiles or cache loads, the correctness
    check, warm-up requests and the steady-state lead-in."""
    return ctx["setup_s"]
