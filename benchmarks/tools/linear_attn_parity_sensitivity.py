#!/usr/bin/env python3
"""Does the linear-attention train cell's correctness check notice a wrong
mixer, a wrong expert share, or a lower precision?

    python3 benchmarks/tools/linear_attn_parity_sensitivity.py \
        --workload train-qwen3next-ep16 [--seeds 12]

Not part of any run: a one-off for the chip (PERF.md and the
configuration's ``parity_tolerance_reason`` record what it printed). It
builds the cell's state as a run does and repeats the run's parity check
(program on this mesh against the family's plain reference on the same
slice): first UNDAMAGED over ``--seeds`` seeds (the readings the
tolerances are set from), then, on the first seed, with one fault at a
time.

On the PROGRAM's side (the reference keeps the true weights):
- ``float8_e4m3_weights``: every matmul weight rounded through float8
  e4m3, the nearest precision below the configuration's bf16.

On the REFERENCE's side (the family's ``FAULTS``: the line gives the
distance between the true program and a defective model, which is what a
defective program would read against the true reference, up to bf16's
noise):
- ``state_reset_64``: the carried state zeroed at every chunk edge (a
  chunked rule that loses its carry);
- ``g_zero``, ``beta_one``: no decay; every write at full strength;
- ``no_conv``: the causal convolution left out;
- ``no_output_gate``: the full layer's o * sigmoid(gate) left out;
- ``rope_all``: RoPE on all of a head instead of its first quarter;
- ``top_k_less_one``: one expert a token fewer;
- ``no_shared_gate``: the shared expert at full weight;
- ``all_experts``: the layer takes its held experts for all of them, so
  every token's k assignments land here;
- ``state_bf16_64``, ``gates_bf16``: the state rounded to bf16 where a
  chunked rule carries it, every 64 tokens; g and beta rounded to bf16
  (the rule's state and gates in the precision below the float32 the op
  states).

The norm weights start from 0 (zero-centred) and the gated norm's from 1:
the true weights of every line have them moved by 0.5 cos(i), so that a
norm that forgot its ``1 +`` would show. Each line says whether the
cell's tolerances catch it.
"""

import argparse
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (BENCH_DIR, os.path.dirname(BENCH_DIR)):
    if p not in sys.path:
        sys.path.insert(0, p)

MATMULS = ("wq", "wk", "wv", "wo", "w_qkvz", "w_ba", "w_out", "w_gate",
           "w_up", "w_down", "shared_gate", "shared_up", "shared_down")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=4500000019)
    ap.add_argument("--seeds", type=int, default=12)
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
    rows = mesh.shape["data"] * mesh.shape["fsdp"]
    n = int(dep["parity_tokens"])

    def float8(w):
        """e4m3 with one scale a tensor, as fp8 weights are stored. By
        ``reduce_precision``: a convert to float8 and back is removed by
        the chip's compiler as excess precision, and changes nothing."""
        w32 = w.astype(jnp.float32)
        scale = jnp.max(jnp.abs(w32)) / 240.0
        return (jax.lax.reduce_precision(w32 / scale, 4, 3)
                * scale).astype(w.dtype)

    def every_matmul(p):
        out = {k: ({name: float8(w) if name in MATMULS else w
                    for name, w in v.items()} if isinstance(v, dict) else v)
               for k, v in p.items()}
        return {**out, "lm_head": float8(p["lm_head"])}

    def wavy(p):
        """Norm weights moved off their start."""
        def move(name, w):
            if not name.endswith("norm"):
                return w
            i = jnp.arange(w.size, dtype=jnp.float32).reshape(w.shape)
            return (w.astype(jnp.float32) + 0.5 * jnp.cos(i)).astype(w.dtype)
        return {k: ({name: move(name, w) for name, w in v.items()}
                    if isinstance(v, dict) else move(k, v))
                for k, v in p.items()}

    forward = jax.jit(lambda p, t: module.forward(p, t, cfg, mesh))
    loss_of = pmesh.make_eval_step(cfg, mesh, model=module)

    def check(params, ref_params, sub, faults=()):
        got_logits = forward(params, sub["tokens"])
        got = float(loss_of(params, sub))
        want_logits, want = fam.logits_and_loss(ref_params, sub, cfg,
                                                faults=faults)
        want = float(want)
        logits_err = float(reference.rel_err_device(got_logits, want_logits))
        return logits_err, abs(got - want) / abs(want), got, want

    def state_of(seed):
        key = jax.random.PRNGKey(seed % (2 ** 31))
        params = jax.jit(wavy)(init_fn(key).params)
        toks = jax.random.randint(jax.random.fold_in(key, 1), (rows, n + 1),
                                  0, cfg.vocab_size, dtype=jnp.int32)
        return params, {"tokens": toks[:, :-1], "targets": toks[:, 1:]}

    def note(variant, seed, logits_err, loss_err, got, want):
        result.note(variant=variant, seed=seed, logits_rel_err=logits_err,
                    loss_rel_err=loss_err, loss=got, reference=want,
                    caught=bool(
                        logits_err > dep["parity_logits_tolerance"]
                        or loss_err > dep["parity_loss_tolerance"]))

    with mesh:
        for i in range(a.seeds):
            seed = a.seed + 7919 * i
            params, sub = state_of(seed)
            note("as_trained", seed, *check(params, params, sub))
            del params
        params, sub = state_of(a.seed)
        variants = [("float8_e4m3_weights", jax.jit(every_matmul), ())] \
            + [(f, None, (f,)) for f in fam.FAULTS]
        for name, damage, faults in variants:
            try:
                damaged = damage(params) if damage else params
                note(name, a.seed, *check(damaged, params, sub, faults))
            except Exception as e:  # noqa: BLE001 - report and go on
                result.note(variant=name, error=f"{type(e).__name__}: {e}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
