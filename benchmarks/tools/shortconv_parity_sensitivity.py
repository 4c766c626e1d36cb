#!/usr/bin/env python3
"""Does the correctness check of a served model whose layers are gated
SHORT CONVOLUTIONS beside attention at heads of 64 notice the operator done
wrong, a conv tail lost between chunks or between decode steps, the
router's bias ignored, or the cache kept in a lower precision?

    python3 benchmarks/tools/shortconv_parity_sensitivity.py \
        --workload serve-lfm2-mixed-open [--seeds 1,2,3] [--only a,b]

Not part of any run: a one-off for the chip (the configuration file's
``parity_tolerance_reason`` and PERF.md record what it printed). It makes
the cell's weights as the replica does (``serve/llm.py _load_model``),
runs the family's ``served`` half once a seed (the chunked prefill with
each conv layer's tail handed from chunk to chunk, the packed K/V pool, the
decode steps that move the slot's tails on: the sound path), and compares
it with the family's plain reference computed SOUND (``as_served``: the
run's own parity) and with one of the reference's ``FAULTS`` at a time, on
the same weights and tokens:

- ``tail_zero_at_chunk``: the conv forgets what lies before a served
  chunk's first position (a chunk that starts from zeros, not from the
  tail the chunk before it left): the first two positions of a chunk are
  wrong in every conv layer and nothing else, 300 positions before the
  compared logits; the ROWS see it;
- ``tail_zero_at_decode``: the conv forgets everything before the token at
  every decode step (a step that reads a zero tail);
- ``c_left_out``: the output gate C left out, y = c;
- ``qk_norm_left_out``: no norm over a head's q and k;
- ``select_without_bias``: the 4 experts with the largest s, not s + bias
  (the reference's own scores then part from the program's choice far
  from any boundary: MISROUTED positions beyond the family's limit);

and with the family's ``POOL_FAULTS`` planted in the PROGRAM's place
(``pool_float8``, ``pool_int8``: the pool's K/V rows AND the slots' conv
tails rounded after the prefill's scatter and after every decode step):
float8 is the precision below the bf16 the configuration states.

A line a variant: the limit (the configuration's ``parity_tolerance``), the
prefill set's and the decode set's number by it (the largest of the
compared positions' logits' errors, ROWS_WEIGHT x the worst position's row
error in the first attention layer, TAIL_WEIGHT x layer 0's tail's error),
whether it reads over or misroutes a position (``over``), each part beside
them, and the routing's readings (the farthest from a boundary that the
program's choice parted from the reference's, the memberships taken); every
position's error and margin follow on a line of its own with ``--full``.
"""

import argparse
import os
import random
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (BENCH_DIR, os.path.dirname(BENCH_DIR)):
    if p not in sys.path:
        sys.path.insert(0, p)

PARTS = ("logits", "rows", "tail", "median")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="6100000019")
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
    variants = ["as_served", *fam.FAULTS, *fam.POOL_FAULTS]
    if a.only:
        variants = [v for v in variants if v in a.only.split(",")]
    n, tol = dep["parity_prompt_len"], dep["parity_tolerance"]
    cuts = fam.chunk_starts(n, buckets)
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
                        () if name == "as_served" else (name,), cuts)
            except Exception as e:  # noqa: BLE001 - report and go on
                result.note(variant=name, seed=seed,
                            error=f"{type(e).__name__}: {e}")
                continue
            sets = out["prefill_rel_err"], out["decode_rel_err"]
            result.note(
                variant=name, seed=seed, limit=tol, prefill=sets[0],
                decode=sets[1],
                over=None if name == "as_served"
                else bool(max(sets) > tol or not out["finite"]),
                correct=bool(out["finite"] and max(sets) <= tol),
                misrouted_positions=out["misrouted_positions"],
                parted_margin_max=out["parted_margin_max"],
                parted_decisions=out["parted_decisions"],
                routing_decisions=out["routing_decisions"],
                routing_taken=out["routing_taken"],
                routing_near_share=out["routing_near_share"],
                margin_least=min(out["margins"]),
                rows_median=out["rows_median_rel_err"],
                rows_worst_position=out["rows_worst_position"],
                prefill_least=min(out["prefill_rel_errs"]),
                decode_least=min(out["decode_rel_errs"]),
                prefill_most=max(out["prefill_rel_errs"]),
                decode_most=max(out["decode_rel_errs"]),
                **{f"{part}_{what}": out[f"{part}_{what}_rel_err"]
                   for part in ("prefill", "decode") for what in PARTS})
            if a.full:
                result.note(variant=name, seed=seed, full=out)
        del params, got
    return 0


if __name__ == "__main__":
    sys.exit(main())
