#!/usr/bin/env python3
"""Does a served state-space model's correctness check notice a program
that is wrong: a recurrent state, or experts, kept or computed in a lower
precision than the configuration states, or a router that chooses by other
scores?

    python3 benchmarks/tools/ssm_parity_sensitivity.py \
        --workload serve-nemotron3-chat-open [--seeds 3000000019,...]

Not part of any run: a one-off for the chip (PERF.md records what it
printed), the twin of ``serve_moe_parity_sensitivity.py`` for a family
whose ``served`` takes a ``fault``. It makes the cell's weights as the
replica does (``serve/llm.py _load_model``) and repeats the run's parity
(the family's ``served`` half against its plain reference, ``compared``:
the harness's own comparison) with the PROGRAM made wrong in one way at a
time, while the reference keeps the true weights:

- ``state_bfloat16``: the recurrent states rounded to bfloat16 wherever
  they are stored (after the prefill's write and after every decode step):
  the nearest precision below the configuration's float32 state;
- ``state_products_bfloat16``: the state-space rule's operands (x, dt, B,
  C and the state it reads) rounded to bfloat16, its sums and what it
  stores float32: nothing a look at the stored values tells from sound;
- ``router_bias_dropped``: the 6 experts chosen by s and not by s + b (the
  selection bias zeroed in the program's weights);
- ``int8_expert_weights``: the routed experts' two matrices rounded to int8
  with one scale per output channel (below the configuration's bf16);
- ``float8_e4m3_expert_weights``: the same through float8 e4m3, one scale a
  tensor.

The weights fill a third of the chip and the reference's float32 copies
want room, so there is never a second copy: the variants that leave the
weights alone run on one making of them; each other one damages them in
place, runs the served half and drops them; then the true weights are made
once more and the reference compares every variant's served half. Each
line says whether the cell's tolerance catches it, and ``as_served`` is the
sound reading of that seed.
"""

import argparse
import os
import random
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (BENCH_DIR, os.path.dirname(BENCH_DIR)):
    if p not in sys.path:
        sys.path.insert(0, p)

EXPERTS = ("w_up", "w_down")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="3000000019")
    ap.add_argument("--only", default="")
    a = ap.parse_args()
    from harness import model as hmodel, result, spec
    hmodel.compile_cache()
    import functools

    import jax
    import jax.numpy as jnp
    from ray_tpu.serve.llm import LLMConfig, _load_model
    from ray_tpu.util import jaxenv

    cell = spec.cell(a.workload)
    if not hmodel.REHEARSAL:
        result.require_tpu(jaxenv.describe_device(), cell["chips"])
    m = hmodel.resolved(cell["model"])
    dep = m["deployment"]
    fam = spec.family(cell["family"])
    cfg = fam.config(m)
    buckets = tuple(dep.get("prefill_buckets", LLMConfig().prefill_buckets))
    interpret = hmodel.REHEARSAL

    @functools.partial(jax.jit, donate_argnums=(0,))
    def int8(w):
        # (a channel of zeros, the padding of a stored width, stays zeros)
        scale = jnp.maximum(jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2,
                                    keepdims=True) / 127.0, 1e-30)
        return (jnp.round(w.astype(jnp.float32) / scale)
                * scale).astype(w.dtype)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def float8(w):
        # by reduce_precision: the chip's compiler removes a convert to
        # float8 and back as excess precision
        w32 = w.astype(jnp.float32)
        scale = jnp.max(jnp.abs(w32)) / 240.0
        return (jax.lax.reduce_precision(w32 / scale, 4, 3)
                * scale).astype(w.dtype)

    def experts(f):
        def damage(p):
            for name in EXPERTS:
                p["expert_layers"][name] = f(p["expert_layers"][name])
            return p
        return damage

    # (what is done to the weights, what to the served program); the
    # variants that leave the weights alone share one making of them
    def no_bias(p):
        p["expert_layers"]["router_bias"] = jnp.zeros_like(
            p["expert_layers"]["router_bias"])
        return p

    variants = {
        "as_served": (None, None),
        "state_bfloat16": (None, "state_bfloat16"),
        "state_products_bfloat16": (None, "state_products_bfloat16"),
        "router_bias_dropped": (no_bias, None),
        "int8_expert_weights": (experts(int8), None),
        "float8_e4m3_expert_weights": (experts(float8), None),
    }
    if a.only:
        variants = {k: v for k, v in variants.items()
                    if k in a.only.split(",")}
    kw = dict(buckets=buckets, block=dep["kv_block_size"],
              kv_impl="gather" if interpret else "paged_flash",
              interpret=False)
    n, tol = dep["parity_prompt_len"], dep["parity_tolerance"]
    for seed in (int(s) for s in a.seeds.split(",")):
        def make():
            return _load_model(LLMConfig(model=cfg,
                                         seed=seed % (2 ** 31)))[1]
        rng = random.Random(seed)
        toks = [rng.randrange(1, cfg.vocab_size) for _ in range(n)]
        used = [rng.randrange(1, cfg.vocab_size)
                for _ in range(max(8, n // 3))]
        gots, params = {}, make()
        for name, (damage, fault) in variants.items():
            try:
                if damage is not None:
                    if params is None:
                        params = make()
                    params = damage(params)
                gots[name] = fam.served(params, cfg, toks, used=used,
                                        fault=fault, **kw)
            except Exception as e:  # noqa: BLE001 - report and go on
                result.note(variant=name, seed=seed,
                            error=f"{type(e).__name__}: {e}")
            if damage is not None:
                params = None       # damaged: dropped before the next
        params = params if params is not None else make()
        for name, got in gots.items():
            out = fam.compared(got, params, cfg, n)
            result.note(variant=name, seed=seed, tolerance=tol, caught=bool(
                out["prefill_rel_err"] > tol or out["decode_rel_err"] > tol
                or not out["finite"]), **out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
