#!/usr/bin/env python3
"""Does the correctness check of a served model with a MIXED residual
stream (hyper-connections) over latent attention notice the mixing done
wrong, computed in a lower precision, or the cache kept in one?

    python3 benchmarks/tools/mhc_parity_sensitivity.py \
        --workload serve-xing4-rag-open [--seeds 1,2,3] [--only a,b]

Not part of any run: a one-off for the chip (the configuration file's
``parity_tolerance_reason`` and PERF.md record what it printed). It makes
the cell's weights as the replica does (``serve/llm.py _load_model``),
runs the family's ``served`` half once a seed (the chunked prefill with
the four-wide stream, the latent pool, the absorbed decode: the sound
path), and compares it with the family's plain reference computed SOUND
(``as_served``: the run's own parity) and with one of the reference's
``FAULTS`` at a time, on the same weights and tokens:

- ``res_identity``: Hres = I (the stream's rows never mix);
- ``sinkhorn_1``: one Sinkhorn iteration in place of ``hc_sinkhorn_iters``;
- ``post_without_2``: Hpost = sigmoid(.) without its factor 2;
- ``static_coefficients``: a_pre = a_post = a_res = 0 (the coefficients
  no longer depend on the position's stream);
- ``coeff_bfloat16``: x~, the projections, the sigmoids, exp and Sinkhorn
  in bfloat16 (the precision below the float32 the file states for them);
- ``kr_left_out``, ``yarn_off``: the latent family's own;
- ``rows_float8``, ``rows_int8``: the cache rows [c | kr] rounded through
  float8 e4m3 / int8, one scale a row, before keys and values are
  expanded from them;
- ``late_kr_left_out`` (the family's ``LATE_FAULTS``): ``kr_left_out``
  from the reply's position ``LATE_FROM`` on behind a sound reference
  before it: a fault that starts at a later decode step, which a set's
  lower quartile passes;

and with the family's ``POOL_FAULTS`` planted in the PROGRAM's place
(``pool_float8``, ``pool_int8``: the pool's rows rounded after the
prefill's scatter and after every decode step's write).

A line a variant: which limit it is meant for (the ROWS' limit,
tolerance / ROWS_WEIGHT, for the cache's precision; the COEFFICIENTS'
limit, tolerance / COEFF_WEIGHT, for ``coeff_bfloat16``; the STREAM's
limit, tolerance / STREAM_WEIGHT on the largest error of any position's
row in the first layer with a router, for a late fault; the LOGITS'
limit, the tolerance, for everything else), that limit, the prefill's and
the decode's reading by it, and whether it reads over (``over``); the
full comparison (every position's error and margin) follows on a line of
its own with ``--full``.
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
    ap.add_argument("--full", action="store_true")
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
    variants = ["as_served", *fam.FAULTS, *fam.LATE_FAULTS,
                *fam.POOL_FAULTS]
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
            what = "rows" if name.startswith(("rows_", "pool_")) \
                else "coeff" if name == "coeff_bfloat16" \
                else "stream" if name in fam.LATE_FAULTS else "logits"
            limit = tol / {"rows": fam.ROWS_WEIGHT, "logits": 1.0,
                           "coeff": fam.COEFF_WEIGHT,
                           "stream": fam.STREAM_WEIGHT}[what]
            readings = [out["coeff_rel_err"]] * 2 if what == "coeff" else [
                out[f"{part}_{what}_rel_err"]
                for part in ("prefill", "decode")]
            result.note(
                variant=name, seed=seed, meant_for=what, limit=limit,
                prefill=readings[0], decode=readings[1],
                over=None if name == "as_served"
                else bool(max(readings) > limit),
                correct=bool(out["prefill_rel_err"] <= tol
                             and out["decode_rel_err"] <= tol),
                prefill_quartile=out["prefill_quartile_rel_err"],
                decode_quartile=out["decode_quartile_rel_err"],
                prefill_least=min(out["prefill_rel_errs"]),
                decode_least=min(out["decode_rel_errs"]),
                prefill_median=out["prefill_median_rel_err"],
                decode_median=out["decode_median_rel_err"],
                prefill_rows=out["prefill_rows_rel_err"],
                decode_rows=out["decode_rows_rel_err"],
                coeff=out["coeff_rel_err"],
                prefill_stream=out["prefill_stream_rel_err"],
                decode_stream=out["decode_stream_rel_err"],
                stream_median=out["stream_median_rel_err"],
                prefill_logits=out["prefill_logits_rel_err"],
                decode_logits=out["decode_logits_rel_err"],
                clear_positions=out["clear_positions"])
            if a.full:
                result.note(variant=name, seed=seed, full=out)
        del params, got
    return 0


if __name__ == "__main__":
    sys.exit(main())
