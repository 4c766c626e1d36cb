#!/usr/bin/env python3
"""Does a train cell's correctness check notice a worse forward?

    python3 benchmarks/tools/parity_sensitivity.py --workload <train cell>

Not part of any run: a one-off for the chip (PERF.md records what it
printed). It builds the cell's state as a run does, then repeats the
run's parity check with the PROGRAM given damaged weights while the
reference keeps the true ones: matmul weights rounded through float8
(e4m3), rounded to int8 with one scale per output channel, and one
layer's attention output projection zeroed (a partly wrong forward).
Each line says whether the cell's tolerances would have caught it.
"""

import argparse
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (BENCH_DIR, os.path.dirname(BENCH_DIR)):
    if p not in sys.path:
        sys.path.insert(0, p)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=3000000019)
    a = ap.parse_args()
    from harness import model as hmodel, result, spec, train_cell
    hmodel.compile_cache()
    import jax
    import jax.numpy as jnp
    from ray_tpu.parallel import mesh as pmesh
    from ray_tpu.util import jaxenv

    cell = spec.cell(a.workload)
    result.require_tpu(jaxenv.describe_device(), cell["chips"])
    m = hmodel.resolved(cell["model"])
    dep = m["deployment"]
    fam = spec.family(cell["family"])
    cfg = fam.config(m, **dep["model_overrides"])
    mesh = pmesh.make_mesh(pmesh.MeshSpec(data=1, context=1, **dep["mesh"]),
                           devices=jax.devices()[:cell["chips"]])
    init_fn, _ = pmesh.make_train_step(cfg, mesh, model=fam.module())
    key = jax.random.PRNGKey(a.seed % (2 ** 31))
    rows = mesh.shape["data"] * mesh.shape["fsdp"]
    n = int(dep["parity_tokens"])

    def through(dtype):
        return lambda w: w.astype(dtype).astype(w.dtype)

    def int8(w):
        scale = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2,
                        keepdims=True) / 127.0
        q = jnp.round(w.astype(jnp.float32) / scale)
        return (q * scale).astype(w.dtype)

    def matmuls(f):
        def go(params):
            layers = {k: (f(v) if v.ndim == 3 else v)
                      for k, v in params["layers"].items()}
            return {**params, "layers": layers, "lm_head": f(
                params["lm_head"])}
        return go

    def drop_wo(params):
        wo = params["layers"]["wo"]
        return {**params, "layers": {**params["layers"],
                                     "wo": wo.at[1].set(0)}}

    variants = {"as_served": lambda p: p,
                "float8_e4m3_weights": matmuls(through(jnp.float8_e4m3fn)),
                "int8_per_channel_weights": matmuls(int8),
                "layer_1_wo_zeroed": drop_wo}
    with mesh:
        params = init_fn(key).params
        toks = jax.random.randint(jax.random.fold_in(key, 1),
                                  (rows, n + 1), 0, cfg.vocab_size,
                                  dtype=jnp.int32)
        sub = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
        for name, damage in variants.items():
            try:
                damaged = jax.jit(damage)(params)
                _, _, logits_err, loss_err = train_cell.parity(
                    fam, cfg, mesh, damaged, params, sub)
            except Exception as e:  # noqa: BLE001 - report and go on
                result.note(variant=name, error=f"{type(e).__name__}: {e}")
                continue
            result.note(
                variant=name, logits_rel_err=logits_err,
                loss_rel_err=loss_err,
                caught=bool(
                    logits_err > dep["parity_logits_tolerance"]
                    or loss_err > dep["parity_loss_tolerance"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
