#!/usr/bin/env python3
"""Does a served latent-attention model's correctness check notice the
mathematics of the layer done wrong, or its cache kept in a lower
precision than the configuration states?

    python3 benchmarks/tools/latent_parity_sensitivity.py \
        --workload serve-mistral4-longdoc-open [--seeds 1,2,3]

Not part of any run: a one-off for the chip (PERF.md records what it
printed). It makes the cell's weights as the replica does
(``serve/llm.py _load_model``), runs the family's ``served`` half once a
seed (the chunked prefill, the latent pool, the absorbed decode: the
sound path), and compares it with the family's plain reference computed
SOUND (``as_served``: the run's own parity) and with one of the
reference's ``FAULTS`` at a time, on the same weights and tokens:

- ``rows_float8``, ``rows_int8``: the cache rows [c | kr] rounded through
  float8 e4m3 (the nearest precision below the configuration's bf16) or
  int8, one scale a row, before keys and values are expanded from them;
- ``kr_left_out``: the rotary key (and query) left out of the scores;
- ``query_scale_off``: a(t) = 1 (the position-dependent query scale);
- ``yarn_off``: the plain theta_i in place of YaRN's blend;
- ``mscale_off``: m = 1 in the softmax scale;

and with the family's ``POOL_FAULTS``, planted in the PROGRAM's place and
compared with the sound reference: ``pool_float8``, ``pool_int8`` (the
served path run again with its pool's rows rounded through that precision
after the prefill's scatter and after every decode step's write, so the
decode steps attend such a cache and the rows compared are its rows).

Each line says whether the cell's tolerance catches it, with every
compared position's error and margin.
"""

import argparse
import os
import random
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (BENCH_DIR, os.path.dirname(BENCH_DIR)):
    if p not in sys.path:
        sys.path.insert(0, p)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="3000000019")
    ap.add_argument("--only", default="",
                    help="comma-separated variants (as_served, a fault)")
    a = ap.parse_args()
    from harness import model as hmodel, result, spec
    hmodel.compile_cache()
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
    variants = ["as_served", *fam.FAULTS, *fam.POOL_FAULTS]
    if a.only:
        variants = [v for v in variants if v in a.only.split(",")]
    n, tol = dep["parity_prompt_len"], dep["parity_tolerance"]
    for seed in (int(s) for s in a.seeds.split(",")):
        params = _load_model(LLMConfig(model=cfg, seed=seed % (2 ** 31)))[1]
        rng = random.Random(seed)
        toks = [rng.randrange(1, cfg.vocab_size) for _ in range(n)]

        def served(**kw):
            return fam.served(
                params, cfg, toks, buckets=buckets,
                block=dep["kv_block_size"],
                kv_impl="gather" if hmodel.REHEARSAL else "paged_flash",
                interpret=False, **kw)

        got = served()
        for name in variants:
            try:
                if name in fam.POOL_FAULTS:
                    out = fam.compared(served(pool_fault=name), params, cfg,
                                       n)
                else:
                    out = fam.compared(
                        got, params, cfg, n,
                        () if name == "as_served" else (name,))
            except Exception as e:  # noqa: BLE001 - report and go on
                result.note(variant=name, seed=seed,
                            error=f"{type(e).__name__}: {e}")
                continue
            result.note(variant=name, seed=seed, tolerance=tol, caught=bool(
                out["prefill_rel_err"] > tol or out["decode_rel_err"] > tol),
                **out)
        del params, got
    return 0


if __name__ == "__main__":
    sys.exit(main())
