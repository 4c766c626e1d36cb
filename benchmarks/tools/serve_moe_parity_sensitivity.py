#!/usr/bin/env python3
"""Does a served expert model's correctness check notice experts kept in
a lower precision than the configuration states?

    python3 benchmarks/tools/serve_moe_parity_sensitivity.py \
        --workload serve-exaone-reason-open

Not part of any run: a one-off for the chip (PERF.md records what it
printed), the serving twin of ``moe_parity_sensitivity.py``. It makes the
cell's weights as the replica does (``serve/llm.py _load_model``) and
repeats the run's parity (the family's ``served`` half against its plain
reference) with the PROGRAM's weights made wrong in one way at a time,
while the reference keeps the true ones:

- ``int8_expert_weights``, ``float8_e4m3_expert_weights``: the routed
  experts' three matrices alone, rounded to int8 with one scale per
  output channel, or through float8 e4m3 (the nearest precision below
  the configuration's bf16) with one scale a tensor;
- ``int8_weights``: every matmul weight (attention, dense layer, experts,
  shared expert, head);
- ``selection_bias_left_out``: the router's bias zeroed (the choice by
  the scores alone).

The weights fill most of the chip, so there is never a second copy:
each variant makes the weights from the seed, damages them in place,
runs the served half, drops them, makes them again and runs the
reference. Each line says whether the cell's tolerance catches it.
"""

import argparse
import os
import random
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (BENCH_DIR, os.path.dirname(BENCH_DIR)):
    if p not in sys.path:
        sys.path.insert(0, p)

EXPERTS = ("w_gate", "w_up", "w_down")
MATMULS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
           "shared_gate", "shared_up", "shared_down")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=3000000019)
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

    def make():
        return _load_model(LLMConfig(model=cfg, seed=a.seed % (2 ** 31)))[1]

    @functools.partial(jax.jit, donate_argnums=(0,))
    def int8(w):
        scale = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2,
                        keepdims=True) / 127.0
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

    def in_stacks(names, f):
        def damage(p):
            for stack in ("dense_layers", "layers"):
                for name in names:
                    if name in p.get(stack, {}):
                        p[stack][name] = f(p[stack][name])
            return p
        return damage

    def everything(p):
        p = in_stacks(MATMULS, int8)(p)
        p["lm_head"] = int8(p["lm_head"])
        return p

    def no_bias(p):
        p["layers"]["router_bias"] = jnp.zeros_like(
            p["layers"]["router_bias"])
        return p

    variants = {
        "as_served": lambda p: p,
        "int8_expert_weights": in_stacks(EXPERTS, int8),
        "float8_e4m3_expert_weights": in_stacks(EXPERTS, float8),
        "int8_weights": everything,
        "selection_bias_left_out": no_bias,
    }
    kw = dict(buckets=buckets, block=dep["kv_block_size"],
              kv_impl="gather" if interpret else "paged_flash",
              interpret=False)
    n = dep["parity_prompt_len"]
    for name, damage in variants.items():
        if a.only and name not in a.only.split(","):
            continue
        try:
            rng = random.Random(a.seed)
            toks = [rng.randrange(1, cfg.vocab_size) for _ in range(n)]
            params = damage(make())
            got = fam.served(params, cfg, toks, **kw)
            del params
            out = fam.compared(got, make(), cfg, n)
        except Exception as e:  # noqa: BLE001 - report and go on
            result.note(variant=name, error=f"{type(e).__name__}: {e}")
            continue
        tol = dep["parity_tolerance"]
        result.note(variant=name, tolerance=tol, caught=bool(
            out["prefill_rel_err"] > tol or out["decode_rel_err"] > tol),
            **out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
