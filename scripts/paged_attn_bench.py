"""The paged-decode kernel alone, on the chip: time one call of
``ops/pallas/paged_attention.paged_attention`` at the shapes of the
cell ``serve-chat-open`` (32 slots, 5,882 pool blocks of 16, a table of
256 blocks, 8 KV heads x group 4, head 128, bf16) for contexts of
64 / 256 / 1,024 / 4,096 tokens at 8 and 32 live slots, and give the
share of the HBM roofline each reading is: the K and V bytes of the
LIVE contexts, plus queries and outputs, at the chip's published
bandwidth over the measured time (``benchmarks/harness/kernels.py
paged_decode_bytes`` and ``peaks.py``: the cell's metric's own count).

Slots that are not live are idle as the engine presents them: an
all-trash table row and length 0 (PR 57: the walk skips them; a module
from before it, ``--old``, walks the trash block for each). Live slots'
tables name random pool blocks, so no two fetches are neighbours in HBM.

Then the pool's block writer, ``kv_write``, alone (PR 31): the 32
entries of one layer of a decode step, K and V, each a read-modify-write
of a whole pool block; all 32 slots live on distinct blocks, mixes of
12 and of 2 live slots with the idle ones' entries negative (skipped)
and, ``_trash``, pointed at the trash block as before PR 57, and 160
entries as a verify round of width 5 writes them.

``--old PATH`` times a second module beside it (the parent commit's
kernel, unpacked under ``.scratch/``), same inputs, and compares the
outputs. It is a one-off for PERF.md, no cell's code; without a TPU it
exits 3 (``--rehearse`` runs tiny shapes through the interpreter to
check the control flow; its times mean nothing).

    chiprun -- python scripts/paged_attn_bench.py --old \\
        .scratch/parent/ray_tpu/ops/pallas/paged_attention.py
"""

import argparse
import functools
import importlib.util
import json
import os
import statistics
import sys
import time

# the required bytes and the peak are the benchmark's own
sys.path[:0] = [".", "benchmarks"]
from harness import kernels, peaks  # noqa: E402

CELL = dict(slots=32, blocks=5882, bs=16, width=256, kvh=8, g=4, hd=128)
TINY = dict(slots=4, blocks=41, bs=16, width=16, kvh=2, g=2, hd=128)


def _load(path):
    spec = importlib.util.spec_from_file_location("old_paged", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--old", help="path of a second kernel module")
    # a program's launch and read-back cost the host ~1.5 ms: at 32 calls
    # a program that was 50 us a call on top of a 2-us writer (PR 57)
    ap.add_argument("--reps", type=int, default=512,
                    help="kernel calls chained in one program")
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default="chiprun_out/paged_attn_bench.json")
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops.pallas import paged_attention as new

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind}
    if dev.platform != "tpu" and not a.rehearse:
        print(json.dumps({"error": "needs a TPU", "device": device}))
        return 3
    # a rehearsal's shares mean as little as its times
    hbm = peaks.PEAKS["TPU v5 lite"]["hbm_bytes_per_s"] if a.rehearse \
        else peaks.peaks(dev.device_kind)["hbm_bytes_per_s"]
    shp = TINY if a.rehearse else CELL
    slots, nb, bs, w = (shp[k] for k in ("slots", "blocks", "bs", "width"))
    kvh, g, hd = shp["kvh"], shp["g"], shp["hd"]
    mods = {"new": new}
    if a.old:
        mods["old"] = _load(a.old)

    rng = np.random.default_rng(0)
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (slots, kvh, g, hd), jnp.bfloat16)
    k_pool = jax.random.normal(kk, (nb, kvh, bs, hd), jnp.bfloat16)
    v_pool = jax.random.normal(kv_, (nb, kvh, bs, hd), jnp.bfloat16)

    def chained(fn):
        """``reps`` calls in one program, each query depending on the
        call before it, so that none is hoisted or overlapped."""
        @jax.jit
        def run(q, k_pool, v_pool, tables, lengths):
            def body(_, q):
                o = fn(q, k_pool, v_pool, tables, lengths,
                       interpret=a.rehearse)
                return (q.astype(jnp.float32) + 1e-6 * o).astype(q.dtype)
            return jax.lax.fori_loop(0, a.reps, body, q)
        return run

    runs = {name: chained(m.paged_attention) for name, m in mods.items()}
    rows = []
    for ctx in (64, 256, 1024, 4096) if not a.rehearse else (16, 256):
        for live in (8, 32) if not a.rehearse else (1, 4):
            lengths = np.zeros((slots,), np.int32)
            lengths[:live] = ctx
            tables = np.zeros((slots, w), np.int32)     # trash rows
            tables[:live] = rng.integers(1, nb, (live, w))
            tb, ln = jnp.asarray(tables), jnp.asarray(lengths)
            need = kernels.paged_decode_bytes(
                int(lengths.sum()), slots, kvh, g, hd)
            row = {"ctx": ctx, "live_slots": live, "required_bytes": need,
                   "fetched_positions": int(
                       new.fetched_positions(lengths, bs).sum())}
            outs = {}
            for name, run in runs.items():
                jax.block_until_ready(run(q, k_pool, v_pool, tb, ln))
                ts = []
                for _ in range(a.rounds):
                    t0 = time.perf_counter()
                    jax.block_until_ready(run(q, k_pool, v_pool, tb, ln))
                    ts.append((time.perf_counter() - t0) / a.reps)
                t = statistics.median(ts)
                row[f"{name}_us"] = 1e6 * t
                row[f"{name}_roofline_pct"] = 100.0 * need / hbm / t
                outs[name] = np.asarray(mods[name].paged_attention(
                    q, k_pool, v_pool, tb, ln, interpret=a.rehearse))
            ref = np.asarray(new.paged_attention_reference(
                q, k_pool, v_pool, tb, ln))
            for name, o in outs.items():     # the live rows: idle ones
                row[f"{name}_vs_reference"] = float(    # are nobody's
                    np.linalg.norm(o[:live] - ref[:live])
                    / np.linalg.norm(ref[:live]))
            if "old" in outs:
                row["speedup"] = row["old_us"] / row["new_us"]
            print(json.dumps(row), flush=True)
            rows.append(row)
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def write(k_pool, v_pool, blocks, at, k_new, v_new):
        """``reps`` writes in one program, the pools carried in place."""
        def body(_, pools):
            return tuple(new.kv_write(*pools, blocks, at, k_new, v_new,
                                      interpret=a.rehearse))
        return jax.lax.fori_loop(0, a.reps, body, (k_pool, v_pool))

    writes = []
    block_bytes = kvh * bs * hd * k_pool.dtype.itemsize
    for what, n, live, idle in (("all_live", slots, slots, -1),
                                ("cell_mix", slots, 3 * slots // 8, -1),
                                ("cell_mix_trash", slots, 3 * slots // 8, 0),
                                ("chat_mix", slots, slots // 16, -1),
                                ("chat_mix_trash", slots, slots // 16, 0),
                                ("verify_w5", 5 * slots, 5 * slots, -1)):
        blocks = np.full((n,), idle, np.int32)
        blocks[:live] = rng.permutation(np.arange(1, nb))[:live]
        at = jnp.asarray(rng.integers(0, bs, (n,)), jnp.int32)
        blocks = jnp.asarray(blocks)
        k_new, v_new = (jax.random.normal(
            jax.random.PRNGKey(i), (n, kvh, hd), jnp.bfloat16)
            for i in (1, 2))
        # block 0 takes the idle entries in an order of its own
        want = np.asarray(k_pool.at[blocks[:live], :, at[:live]].set(
            k_new[:live])[1:].astype(jnp.float32))
        ts = []
        for _ in range(a.rounds + 1):                   # first: compile
            t0 = time.perf_counter()
            k_pool, v_pool = jax.block_until_ready(
                write(k_pool, v_pool, blocks, at, k_new, v_new))
            ts.append((time.perf_counter() - t0) / a.reps)
        t = statistics.median(ts[1:])
        # what the writer moves, not what the algorithm needs (2 KB a row)
        moved = 4 * n * block_bytes
        row = {"kv_write": what, "entries": n, "live": live,
               "us": 1e6 * t, "us_per_entry": 1e6 * t / n,
               "moved_bytes": moved, "moved_GB_per_s": moved / t / 1e9,
               "equals_scatter": bool(np.array_equal(
                   np.asarray(k_pool[1:].astype(jnp.float32)), want))}
        print(json.dumps(row), flush=True)
        writes.append(row)
    doc = {"device": device, "shape": shp, "reps": a.reps,
           "chunk_blocks": new.chunk_blocks(2 * kvh * hd * 2, bs), "rows": rows,
           "kv_write": writes}
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(doc, f, indent=1)
    print(json.dumps({"ok": True, "device": device, "rows": len(rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
