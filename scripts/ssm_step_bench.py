"""The decode step's state-space rule on the chip, by live slots: the kernel
``ops/pallas/ssm_step.py ssm_step`` beside its plain reference (``ops/ssm.py
ssd_step`` + ``where(live, ...)`` + the write into the stack, what impl
'gather' runs), at the served widths (12 state layers x 64 slots x 64 heads
x 64 x 128 float32, 8 groups).

    python scripts/ssm_step_bench.py [--live 0,1,8,18,24,32,64]
        [--variants 262144:4:2,...]   chunk bytes : buffers : ahead
        [--steps 20] [--no-reference]

One line of JSON a reading: ms a decode step (all state layers, the rule
alone: no projections, no conv), the bytes the live slots' states require
(read once, written once) and their share of the HBM rate. Before the
timings, the kernel against the reference on this device at two layers x
eight slots: y, the live slots' states, and that nothing else moved. No CPU
fallback: a timing off the chip is no timing."""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                                      # noqa: E402
import jax.numpy as jnp                         # noqa: E402
import numpy as np                              # noqa: E402
from jax import lax                             # noqa: E402

from ray_tpu.ops import ssm                     # noqa: E402
from ray_tpu.ops.pallas import ssm_step as sk   # noqa: E402

H, P, G, N = 64, 64, 8, 128
HBM_BYTES_PER_S = 819e9                         # one v5e chip


def _rows(key, slots):
    ks = jax.random.split(key, 6)
    return dict(
        x=jax.random.normal(ks[0], (slots, H, P), jnp.float32),
        dt=jax.nn.softplus(jax.random.normal(ks[1], (slots, H)) - 2.0),
        A=-jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.5)),
        B=jax.random.normal(ks[3], (slots, G, N), jnp.float32),
        C=jax.random.normal(ks[4], (slots, G, N), jnp.float32),
        D=jax.random.normal(ks[5], (H,), jnp.float32))


def reference(states, layer, live, r):
    """What impl 'gather' runs: every slot's state through the rule, the
    live ones kept, the layer put back into the stack."""
    st = lax.dynamic_index_in_dim(states, layer, keepdims=False)
    y, new = ssm.ssd_step(r["x"], r["dt"], r["A"], r["B"], r["C"], r["D"], st)
    new = jnp.where(live[:, None, None, None], new, st)
    return y, lax.dynamic_update_index_in_dim(states, new, layer, 0)


def check():
    layers, slots = 2, 8
    r = _rows(jax.random.PRNGKey(1), slots)
    states = jax.random.normal(jax.random.PRNGKey(2),
                               (layers, slots, H, P, N), jnp.float32)
    live = jnp.asarray([True, False, True, True, False, False, True, False])
    ids, count = sk.live_slots(live)
    y, new = jax.jit(lambda s: sk.ssm_step(
        s, jnp.int32(1), ids, count, r["x"], r["dt"], r["A"], r["B"], r["C"],
        r["D"]))(states)
    y0, want = jax.jit(lambda s: reference(s, jnp.int32(1), live, r))(states)
    lv = np.asarray(live)
    y, new, y0, want = (np.asarray(a) for a in (y, new, y0, want))
    out = {
        "check": "kernel against reference",
        "y_rel_err": float(np.abs(y[lv] - y0[lv]).max()
                           / np.abs(y0[lv]).max()),
        "state_rel_err": float(np.abs(new[1, lv] - want[1, lv]).max()
                               / np.abs(want[1, lv]).max()),
        "idle_rows_of_y_zero": bool((y[~lv] == 0).all()),
        "idle_states_identical": bool(
            (new[1, ~lv] == np.asarray(states)[1, ~lv]).all()),
        "other_layer_identical": bool((new[0] == np.asarray(states)[0]).all())}
    print(json.dumps(out), flush=True)
    return out


def timed(fn, states, steps):
    states = fn(states)                         # compile, warm
    jax.block_until_ready(states)
    best = None
    for _ in range(3):
        t = time.perf_counter()
        states = fn(states)
        jax.block_until_ready(states)
        dt = time.perf_counter() - t
        best = dt if best is None else min(best, dt)
    return 1e3 * best / steps, states


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--live", default="0,1,8,18,24,32,64")
    ap.add_argument("--variants", default="262144:4:2")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--slots", type=int, default=64)
    ap.add_argument("--no-reference", action="store_true")
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"ssm_step_bench times the chip, not {dev.platform}")
    print(json.dumps({"device": dev.device_kind}), flush=True)
    check()
    r = _rows(jax.random.PRNGKey(0), a.slots)
    states = jax.random.normal(
        jax.random.PRNGKey(3), (a.layers, a.slots, H, P, N), jnp.float32)
    layer_ids = jnp.arange(a.layers, dtype=jnp.int32)

    def program(step):
        def run(states):
            def one(_, carry):
                def layer(carry, l):
                    states, acc = carry
                    y, states = step(states, l)
                    return (states, acc + y[0, 0, 0]), None
                return lax.scan(layer, carry, layer_ids)[0]
            states, acc = lax.fori_loop(0, a.steps, one,
                                        (states, jnp.float32(0)))
            # the sum keeps every y alive; fold it into nothing
            return states.at[0, 0, 0, 0, 0].add(0.0 * acc)
        return jax.jit(run, donate_argnums=(0,))

    variants = [tuple(int(v) for v in s.split(":"))
                for s in a.variants.split(",")]
    for n_live in (int(v) for v in a.live.split(",")):
        live = np.zeros((a.slots,), bool)
        # scattered over the slots, as an engine's are
        live[np.random.default_rng(n_live).permutation(a.slots)[:n_live]] = 1
        live = jnp.asarray(live)
        ids, count = sk.live_slots(live)
        need = n_live * a.layers * 2 * H * P * N * 4
        for chunk, buffers, ahead in variants:
            ms, states = timed(program(lambda s, l: sk.ssm_step(
                s, l, ids, count, r["x"], r["dt"], r["A"], r["B"], r["C"],
                r["D"], chunk_bytes=chunk, buffers=buffers, ahead=ahead)),
                states, a.steps)
            print(json.dumps({
                "impl": "ssm_step", "live": n_live, "chunk_bytes": chunk,
                "buffers": buffers, "ahead": ahead, "ms_per_step": ms,
                "state_bytes": need,
                "hbm_share": need / (ms / 1e3) / HBM_BYTES_PER_S}),
                flush=True)
        if not a.no_reference and n_live in (1, 18, 64):
            ms, states = timed(program(
                lambda s, l: reference(s, l, live, r)), states, a.steps)
            print(json.dumps({
                "impl": "reference", "live": n_live, "ms_per_step": ms,
                "state_bytes": need,
                "hbm_share": need / (ms / 1e3) / HBM_BYTES_PER_S}),
                flush=True)


if __name__ == "__main__":
    main()
