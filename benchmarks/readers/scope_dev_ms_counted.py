"""``readers/scope_dev_ms.py``'s device time of the instructions under a
``jax.named_scope`` of the program (``scope``) in the programs whose name
holds ``program``, over the decode steps the SAME PROFILE holds, counted
by the layers that attend: the paged kernel runs once an ATTENTION layer a
step, and the family says how many of the model's layers attend
(``attention_layers(model)``; every layer where it does not say). The
accepted reader divides the kernel's calls by ``num_hidden_layers``, which
is the same number in a model whose every layer attends and seven times
too few steps in one whose layers are each one mixer (4 of 28 attend).
Where the program has no such scope, the run no trace or the profile no
paged kernel, there is nothing to read."""
from harness import spec


def steps_in_profile(ctx):
    """Decode steps the reduced trace holds, or None."""
    tr = ctx.get("trace")
    k = tr and tr["kernels"].get("paged_decode")
    if not k or not k["calls"]:
        return None
    layers = getattr(spec.family(ctx["cell"]["family"]), "attention_layers",
                     None)
    n = layers(ctx["model"]) if layers else ctx["model"]["num_hidden_layers"]
    return k["calls"] / n if n else None


def scope_seconds(ctx, scope, program):
    """Seconds of the scoped instructions in this run's profile, or None."""
    if not ctx.get("trace") or not ctx.get("trace_edges"):
        return None
    path = spec._module("readers", "named_kernel")._profile(ctx)
    found = path and spec._module("readers", "scope_dev_ms").scope_time(
        path, scope, program)
    return found[0] if found and found[1] else None


def read(ctx, scope, program):
    steps = steps_in_profile(ctx)
    seconds = steps and scope_seconds(ctx, scope, program)
    return 1e3 * seconds / steps if seconds else None
