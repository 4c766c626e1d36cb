#!/usr/bin/env python3
"""Does the MoE train cell's correctness check notice a wrong expert
layer?

    python3 benchmarks/tools/moe_parity_sensitivity.py --workload train-olmoe

Not part of any run: a one-off for the chip (PERF.md records what it
printed). It builds the cell's state as a run does and repeats the run's
parity check (program on this mesh against the family's plain reference
on the same slice) with the PROGRAM made wrong in one way at a time,
while the reference keeps the true weights and equations:

- ``float8_e4m3_weights``, ``int8_weights``: every matmul weight
  (attention, experts, head) rounded through float8 e4m3 (the nearest
  precision below the configuration's bf16), or to int8 with one scale
  per output channel: the controls of ``parity_sensitivity.py``;
- ``int8_expert_weights``: the same int8 rounding on the experts alone;
- ``one_expert_zeroed``: expert 0's down projection zeroed in the last
  layer;
- ``top_7_of_8``: one expert a token fewer (k - 1);
- ``renormalised_gates``: the kept gates divided by their sum;
- ``q_k_norm_left_out``;
- ``dropped_at_capacity_1.25``: under a router skewed towards a few
  experts (the true weights of this control, in program and reference
  alike), assignments past 1.25 * k * s / E rows an expert and sequence
  are dropped. The program cannot drop, so here the DEFECT is on the
  reference's side (its ``keep`` hook): the line gives the distance
  between the dropless program and a dropping model, which is what a
  dropping program would read against the true reference, up to bf16's
  noise; ``skewed_router_dropless`` beside it is the program against the
  true reference under the same skew, and must NOT be caught.

The norm weights are initialised to 1, and a q or k of random weights
already has RMS 1, so leaving the norm out would change nothing: the
true weights of every control have the q/k-norm weights moved to
1 + 0.5 cos(i). Each line says whether the cell's tolerances catch it.
"""

import argparse
import dataclasses
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
    from harness import model as hmodel, reference, result, spec
    hmodel.compile_cache()
    import jax
    import jax.numpy as jnp
    from ray_tpu.parallel import mesh as pmesh
    from ray_tpu.util import jaxenv

    cell = spec.cell(a.workload)
    if not hmodel.REHEARSAL:
        result.require_tpu(jaxenv.describe_device(), cell["chips"])
    m = hmodel.resolved(cell["model"])
    dep = m["deployment"]
    fam = spec.family(cell["family"])
    module = fam.module()
    cfg = fam.config(m, **dep["model_overrides"])
    mesh = pmesh.make_mesh(pmesh.MeshSpec(data=1, context=1, **dep["mesh"]),
                           devices=jax.devices()[:cell["chips"]])
    init_fn, _ = pmesh.make_train_step(cfg, mesh, model=module)
    key = jax.random.PRNGKey(a.seed % (2 ** 31))
    rows = mesh.shape["data"] * mesh.shape["fsdp"]
    n = int(dep["parity_tokens"])
    E, k = cfg.n_experts, cfg.experts_per_token

    def int8(w):
        scale = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2,
                        keepdims=True) / 127.0
        q = jnp.round(w.astype(jnp.float32) / scale)
        return (q * scale).astype(w.dtype)

    def float8(w):
        """e4m3 with one scale a tensor, as fp8 weights are stored. By
        ``reduce_precision``: a convert to float8 and back is removed by
        the chip's compiler as excess precision, and changes nothing."""
        w32 = w.astype(jnp.float32)
        scale = jnp.max(jnp.abs(w32)) / 240.0
        return (jax.lax.reduce_precision(w32 / scale, 4, 3)
                * scale).astype(w.dtype)

    def layers(**changed):
        return lambda p: {**p, "layers": {**p["layers"], **{
            name: f(p["layers"][name]) for name, f in changed.items()}}}

    def every_matmul(f):
        inner = layers(**{name: f for name in (
            "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")})
        return lambda p: {**inner(p), "lm_head": f(p["lm_head"])}

    def wavy(w):
        i = jnp.arange(w.size, dtype=jnp.float32).reshape(w.shape)
        return (1.0 + 0.5 * jnp.cos(i)).astype(w.dtype)

    def skewed(router):
        # eight experts' logits scaled up: they take most assignments
        return router.at[..., :8].multiply(4.0)

    capacity = -(-int(1.25 * k * n) // E)

    def drop(chosen):
        """Assignments past the capacity of their expert in their
        sequence are dropped, earlier tokens first."""
        ahead = jnp.cumsum(chosen, axis=1) - chosen
        return chosen * (ahead < capacity)

    true = layers(q_norm=wavy, k_norm=wavy)
    skew = layers(router=skewed)
    # name: (damage to the program's weights, the program's config,
    #        change to the TRUE weights, the reference's keep hook)
    def same(p):
        return p
    variants = {
        "as_trained": (same, cfg, same, None),
        "float8_e4m3_weights": (every_matmul(float8), cfg, same, None),
        "int8_weights": (every_matmul(int8), cfg, same, None),
        "int8_expert_weights": (layers(w_gate=int8, w_up=int8, w_down=int8),
                                cfg, same, None),
        "one_expert_zeroed": (
            layers(w_down=lambda w: w.at[-1, 0].set(0)), cfg, same, None),
        f"top_{k - 1}_of_{k}": (same, dataclasses.replace(
            cfg, experts_per_token=k - 1), same, None),
        "renormalised_gates": (same, dataclasses.replace(
            cfg, norm_topk_prob=True), same, None),
        "q_k_norm_left_out": (same, dataclasses.replace(
            cfg, qk_norm=False), same, None),
        "skewed_router_dropless": (same, cfg, skew, None),
        "dropped_at_capacity_1.25": (same, cfg, skew, drop),
    }
    with mesh:
        params = jax.jit(true)(init_fn(key).params)
        toks = jax.random.randint(jax.random.fold_in(key, 1),
                                  (rows, n + 1), 0, cfg.vocab_size,
                                  dtype=jnp.int32)
        sub = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
        for name, (damage, prog_cfg, change, keep) in variants.items():
            try:
                ref_params = jax.jit(change)(params)
                damaged = jax.jit(damage)(ref_params)
                got_logits = jax.jit(lambda p, t: module.forward(
                    p, t, prog_cfg, mesh))(damaged, sub["tokens"])
                got = float(pmesh.make_eval_step(
                    prog_cfg, mesh, model=module)(damaged, sub))
                want_logits, want = fam.logits_and_loss(
                    ref_params, sub, cfg, keep=keep)
                want = float(want)
                logits_err = float(reference.rel_err_device(
                    got_logits, want_logits))
                loss_err = abs(got - want) / abs(want)
            except Exception as e:  # noqa: BLE001 - report and go on
                result.note(variant=name, error=f"{type(e).__name__}: {e}")
                continue
            result.note(
                variant=name, logits_rel_err=logits_err,
                loss_rel_err=loss_err, loss=got, reference=want,
                caught=bool(
                    logits_err > dep["parity_logits_tolerance"]
                    or loss_err > dep["parity_loss_tolerance"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
