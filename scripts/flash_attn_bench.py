"""The three flash kernels alone, on the chip: time ``flash_fwd``,
``flash_bwd_dkv`` and ``flash_bwd_dq`` of
``ops/pallas/flash_attention`` at the shapes the train cells run them
(batch*heads 128 / 112 / 64, 4096 causal tokens, head 128, 1024 x 1024
tiles, bf16) and give each reading's share of the compute roofline by
the benchmark's own count (``benchmarks/harness/kernels.py``: causal
pairs x 4 x head_dim forward, x 10 for the two backward kernels
together) and its own reduction of a profiler trace
(``harness/xplane.py``: the kernels found by their operand signatures).

``--old PATH`` times a second module beside it (the parent commit's
kernels, unpacked under ``.scratch/``) on the same inputs and compares
the outputs. ``--sub QxK ...`` times the body at other sub-tile widths
than the module picks. A one-off for PERF.md, no cell's code; without a
TPU it exits 3 (``--rehearse`` runs tiny shapes through the interpreter
to check the control flow; its times mean nothing).

    chiprun -- python scripts/flash_attn_bench.py --old \\
        .scratch/parent/ray_tpu/ops/pallas/flash_attention.py
"""

import argparse
import glob
import importlib.util
import json
import os
import sys
import tempfile

# the required operations, the peak and the trace's reduction are the
# benchmark's own
sys.path[:0] = [".", "benchmarks"]
from harness import kernels, peaks, xplane  # noqa: E402

SEQ, HD, TILE = 4096, 128, 1024


def _load(path):
    spec = importlib.util.spec_from_file_location("old_flash", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--old", help="path of a second kernel module")
    ap.add_argument("--bh", type=int, nargs="*", default=[128, 112, 64])
    ap.add_argument("--sub", nargs="*", default=[],
                    help="sub-tile widths QxK to time beside the module's")
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default="chiprun_out/flash_attn_bench.json")
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops.pallas import flash_attention as new

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind}
    if dev.platform != "tpu" and not a.rehearse:
        print(json.dumps({"error": "needs a TPU", "device": device}))
        return 3
    peak = peaks.PEAKS["TPU v5 lite"]["bf16_flops"] if a.rehearse \
        else peaks.peaks(dev.device_kind)["bf16_flops"]
    seq, tile = (256, 128) if a.rehearse else (SEQ, TILE)
    bhs = [2] if a.rehearse else a.bh
    kw = dict(sm_scale=HD ** -0.5, causal=True, block_q=tile, block_k=tile,
              interpret=a.rehearse)

    def programs(mod):
        fwd = jax.jit(lambda q, k, v: mod.flash_attention_fwd(q, k, v, **kw))
        bwd = jax.jit(lambda q, k, v, o, do, lse: mod.flash_attention_bwd(
            q, k, v, o, do, lse, **kw))
        return fwd, bwd

    def measure(mod, bh):
        """us a call of each kernel, from a trace of ``reps`` calls."""
        key = jax.random.PRNGKey(bh)
        q, k, v, do = (jax.random.normal(kk, (bh, seq, HD), jnp.bfloat16)
                       for kk in jax.random.split(key, 4))
        fwd, bwd = programs(mod)
        o, lse = fwd(q, k, v)
        grads = bwd(q, k, v, o, do, lse)
        jax.block_until_ready(grads)
        out = {"outputs": [np.asarray(x, np.float32)
                           for x in (o, *grads)]}
        if a.rehearse:
            return out
        with tempfile.TemporaryDirectory() as d:
            with jax.profiler.trace(d):
                for _ in range(a.reps):
                    o, lse = fwd(q, k, v)
                    grads = bwd(q, k, v, o, do, lse)
                jax.block_until_ready(grads)
            tr = xplane.reduce_file(glob.glob(
                os.path.join(d, "plugins/profile/*/*.xplane.pb"))[0])
        for name in ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"):
            kn = tr["kernels"][name]
            assert kn["calls"] == a.reps, (name, kn)
            out[name + "_us"] = 1e6 * kn["s"] / kn["calls"]
        pairs = kernels.causal_pairs(seq)
        out["fwd_roofline"] = 100 * kernels.flash_fwd_flops(
            pairs, bh, HD) / peak / (out["flash_fwd_us"] / 1e6)
        out["bwd_roofline"] = 100 * kernels.flash_bwd_flops(
            pairs, bh, HD) / peak / (
                (out["flash_bwd_dkv_us"] + out["flash_bwd_dq_us"]) / 1e6)
        return out

    rows = []

    def row(label, mod, bh, ref=None):
        r = measure(mod, bh)
        outs = r.pop("outputs")
        if ref is not None:     # relative norm of the difference, o dq dk dv
            r["vs_old"] = [float(np.linalg.norm(x - y) / np.linalg.norm(y))
                           for x, y in zip(outs, ref)]
        r = {"variant": label, "bh": bh, **r}
        rows.append(r)
        print(json.dumps(r), flush=True)
        return outs

    old = _load(a.old) if a.old else None
    picked = new._sub_tiles
    for bh in bhs:
        ref = row("old", old, bh) if old else None
        row("new", new, bh, ref)
        if bh != bhs[0]:
            continue
        for sub in a.sub:
            sq, sk = (int(w) for w in sub.split("x"))
            new._sub_tiles = lambda bq, bk, sq=sq, sk=sk: (sq, sk)
            row(sub, new, bh, ref)
        new._sub_tiles = picked
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    with open(a.out, "w") as f:
        json.dump({"device": device, "seq": seq, "head_dim": HD,
                   "tile": tile, "reps": a.reps, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
