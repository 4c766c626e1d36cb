"""A model of two-sub-layer blocks whose first sub-layer is a gated SHORT
CONVOLUTION in three layers of four and attention at K/V heads of 64 in
the fourth, behind a dense lead that is a conv layer too, with sigmoid-routed
experts chosen by score + bias - SERVED: the one engine, the one block
manager (a conv tail A SLOT, alone, beside a paged K/V pool whose rows hold
two heads of 64 in 128 lanes) and the forwards of ``llm/model.py`` against
the plain reference of ``benchmarks/families/lfm2_moe.py``, on the CPU at
tiny widths with seeded weights."""
import asyncio
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import kvcache as kc
from ray_tpu.llm import model as lm
from ray_tpu.models import moe
from ray_tpu.ops import shortconv

BENCH = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                     "benchmarks")
CUT = ("state", "global", "state", "state", "state", "global", "state",
       "state", "state")


@pytest.fixture(scope="module")
def fam():
    """benchmarks/families/lfm2_moe.py: the plain reference."""
    sys.path.insert(0, BENCH)
    try:
        from harness import spec
        yield spec.family("lfm2_moe")
    finally:
        sys.path.remove(BENCH)


# the decode step's kernels under the interpreter beside their reference
IMPLS = pytest.mark.parametrize("kv_impl,interpret", [
    ("gather", False), ("paged_flash", True)])


def _cfg(**kw):
    """The benchmark's cut at tiny widths: a dense conv layer, then two
    periods of (attention, conv, conv, conv) with 8 experts, 2 a token;
    4 query / 2 K/V heads of 32, two a pool row."""
    base = dict(vocab_size=256, dim=128, n_layers=9, n_heads=4, n_kv_heads=2,
                head_size=32, ffn_dim=64, n_experts=8, experts_per_token=2,
                n_dense_layers=1, dense_ffn_dim=192, layer_types=CUT,
                max_seq_len=256, dtype="float32", attn_impl="reference",
                gmm_impl="ragged_dot")
    base.update(kw)
    return moe.lfm2_24b_a2b(**base)


@pytest.fixture(scope="module")
def params():
    return moe.init_params(jax.random.PRNGKey(0), _cfg())


def _prompt(n, seed=0):
    return [int(t) for t in
            np.random.default_rng(seed).integers(1, 256, size=n)]


def _close(a, b, tol=2e-4):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.linalg.norm(a - b) <= tol * np.linalg.norm(b)


# --- the shapes ---------------------------------------------------------------


def test_the_preset_is_the_published_one():
    cfg = moe.lfm2_24b_a2b()
    assert (cfg.dim, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim) == (2048, 40, 32, 8, 64)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.ffn_dim,
            cfg.dense_ffn_dim, cfg.n_dense_layers) == (64, 4, 1536, 11776, 2)
    assert cfg.layer_types.count("global") == 10
    assert cfg.layer_types[:7] == ("state", "state", "global", "state",
                                   "state", "state", "global")
    assert not cfg.single_mixer and lm.operator_stacks(cfg)
    # 24B in all, about 2.3B a token
    assert 23.5e9 < cfg.num_params() < 24.5e9
    assert 2.0e9 < cfg.num_active_params() < 2.6e9


def test_the_cut_is_a_dense_stack_and_one_scanned_period():
    segs = lm._segments(_cfg())
    assert [(s.stack, s.row, s.layer0, s.kinds, s.repeats) for s in segs] \
        == [("dense_layers", 0, 0, ("state",), 1),
            ("layers", 0, 1, ("global", "state", "state", "state"), 2)]
    assert kc.pool_kinds(_cfg()) == (("global", (1, 5)),
                                     ("state", (0, 2, 3, 4, 6, 7, 8)))


def test_a_slots_state_is_a_tail_alone_and_a_row_holds_two_heads():
    cfg = moe.lfm2_24b_a2b()
    assert kc.row_shapes(cfg, kc.STATE) == ((4096,),)
    assert list(kc.state_arrays(cfg, "bfloat16")) == ["conv"]
    assert kc.state_slot_bytes(cfg, "bfloat16") == 30 * 2 * 2048 * 2
    # the benchmark's cut: 7 conv layers, 57,344 B a slot
    cut = moe.lfm2_24b_a2b(n_layers=9, n_dense_layers=1, layer_types=CUT)
    assert kc.state_slot_bytes(cut, "bfloat16") == 57344
    # K and V: (4 rows, 128 lanes) where the model has (8 heads, 64)
    assert kc.row_shapes(cfg, kc.GLOBAL) == ((4, 128), (4, 128))
    assert kc.row_bytes(cfg, kc.GLOBAL, "bfloat16") == 2048
    pool = kc.init_pool(_cfg(), 5, 8, jnp.bfloat16, state_slots=3)
    assert {k: v.shape for k, v in pool.items()} == {
        "k": (2, 5, 1, 8, 64), "v": (2, 5, 1, 8, 64), "conv": (7, 3, 256)}
    assert kc.kind_block_bytes(pool) == {
        "global": 2 * 2 * 8 * 64 * 2, "state": 7 * 256 * 2}
    # a head of a whole tile is a row of its own, as ever
    assert kc.row_shapes(moe.tiny(head_size=128), kc.GLOBAL) == (
        (2, 128), (2, 128))
    with pytest.raises(ValueError, match="kv_row_heads=2"):
        kc.row_shapes(_cfg(n_kv_heads=1, n_heads=4), kc.GLOBAL)


def test_the_parameters_are_a_stack_a_kind_beside_the_layers(params):
    shapes = jax.tree.map(lambda w: w.shape, params)
    assert set(shapes) == {"embed", "final_norm", "dense_layers", "layers",
                           "state_layers", "attn_layers"}      # tied: no head
    assert shapes["state_layers"] == {
        "w_in": (7, 128, 384), "conv": (7, 128, 3), "w_out": (7, 128, 128)}
    assert set(shapes["attn_layers"]) == {"wq", "wk", "wv", "wo", "q_norm",
                                          "k_norm"}
    assert shapes["attn_layers"]["wk"] == (2, 128, 64)
    assert set(shapes["dense_layers"]) == {"attn_norm", "mlp_norm", "w_gate",
                                           "w_up", "w_down"}
    assert set(shapes["layers"]) == {"attn_norm", "mlp_norm", "router",
                                     "router_bias", "w_gate", "w_up",
                                     "w_down"}
    assert shapes["layers"]["w_gate"] == (8, 8, 128, 64)
    n = sum(int(np.prod(s)) for s in jax.tree.leaves(
        shapes, is_leaf=lambda x: isinstance(x, tuple)))
    assert n == _cfg().num_params()


def test_a_periods_outputs_come_in_the_periods_own_order(params):
    """``_run_kind_stacks`` names a period's kinds in the period's order
    (global, then state), whatever the process's hash seed makes of a set."""
    seen = []

    def body(carry, lp, ref):
        seen.append((ref.kind, ref.layer if isinstance(ref.layer, int)
                     else None))
        return carry, jnp.zeros(())
    _, ys = lm._run_kind_stacks(params, _cfg(), jnp.zeros(()), body, {})
    assert list(ys) == ["state", "global"]      # the dense lead is a conv
    assert [k for k, _ in seen] == ["state", "global", "state", "state",
                                    "state"]
    assert ys["state"].shape == (7,) and ys["global"].shape == (2,)


# --- the operator -------------------------------------------------------------


def test_one_token_by_the_step_is_the_same_token_by_prefill(fam, params):
    cfg = _cfg()
    lp = jax.tree.map(lambda w: w[0], params["state_layers"])
    u = jax.random.normal(jax.random.PRNGKey(1), (21, 128))
    want, z = fam.short_conv(u, lp, cfg)
    out, tail = shortconv.prefill(u, lp, cfg, jnp.zeros((256,)), 21)
    _close(out, want)
    _close(tail, z[19:21].reshape(-1))
    # from a tail: the row's second part behind its first
    first, mid = shortconv.prefill(u[:13], lp, cfg, jnp.zeros((256,)), 13)
    rest, tail2 = shortconv.prefill(u[13:], lp, cfg, mid, 8)
    _close(jnp.concatenate([first, rest]), want)
    _close(tail2, tail)
    # a padded row hands on the tail at its true length; a length under
    # two keeps the predecessor's last row
    _, short = shortconv.prefill(u, lp, cfg, jnp.zeros((256,)), 13)
    _close(short, mid)
    _, one = shortconv.prefill(u[13:], lp, cfg, mid, 1)
    _close(one, jnp.concatenate([mid[128:], z[13]]))
    # token by token
    t = jnp.zeros((1, 256))
    for i in range(21):
        o, t = shortconv.step(u[i:i + 1], lp, cfg, t)
        _close(o[0], want[i], 1e-3)
    _close(t[0], tail)


# --- the forwards against the reference ---------------------------------------


def test_cold_prefill_is_the_reference(fam, params):
    cfg = _cfg()
    toks = _prompt(50)
    ref = fam.forward(params, jnp.asarray([toks]), cfg)[0]
    for n in (50, 37, 1):
        logits, kv = lm.prefill(
            params, jnp.asarray(lm.pad_prompt(toks[:n], 64)), jnp.int32(n),
            cfg, 128)
        _close(logits, ref[n - 1])
    assert {k: v.shape for k, v in kv.items()} == {
        "k": (2, 128, 1, 64), "v": (2, 128, 1, 64), "conv": (7, 256)}


@IMPLS
def test_chunks_and_steps_through_the_pool_are_the_reference(
        fam, params, kv_impl, interpret):
    """The family's own comparison, the one that decides the cell's
    ``correct``: 75 tokens in chunks of 32 (two chunk boundaries and an odd
    tail of 11 in its own bucket), every conv layer's tail handed on in the
    accumulator, the rows scattered into a pool of packed heads, the tails
    written to slot 1 of two, 16 decode steps; LOGITS against the full
    forward, every position's K/V row of the first attention layer, layer
    0's tail, the idle slot untouched."""
    out = fam.serve_parity(params, _cfg(), 7, 75, buckets=(8, 16, 32),
                           block=8, kv_impl=kv_impl, interpret=interpret,
                           cache_dtype="float32")
    assert out["finite"] and out["idle_tail_max"] == 0.0
    assert len(out["prefill_rel_errs"]) == 11      # the odd tail's
    assert len(out["decode_rel_errs"]) == 16
    assert max(out["prefill_rel_errs"] + out["decode_rel_errs"]) < 1e-4
    assert out["prefill_rel_err"] < 1e-4 and out["decode_rel_err"] < 1e-4
    assert out["prefill_tail_rel_err"] < 1e-5 > out["decode_tail_rel_err"]


@pytest.mark.parametrize("fault,part", [
    ("tail_zero_at_chunk", "prefill_rows"), ("tail_zero_at_decode", "decode"),
    ("c_left_out", "prefill"), ("qk_norm_left_out", "prefill"),
    ("select_without_bias", None)])
def test_the_comparison_sees_the_references_faults(fam, params, fault, part):
    cfg = _cfg()
    toks = _prompt(75, seed=3)
    if part is None:    # a bias that decides far more than a near tie
        params = {**params, "layers": {
            **params["layers"],
            "router_bias": 20 * params["layers"]["router_bias"]}}
    got = fam.served(params, cfg, toks, buckets=(8, 16, 32), block=8,
                     kv_impl="gather", interpret=False,
                     cache_dtype="float32")
    # the routing of the last chunk's rows and of the reply, no other
    assert got["experts"].shape == (9, 75 + 16, 2)
    assert (got["experts"][1:, 64:] >= 0).all()
    assert (got["experts"][:, :64] == -1).all()
    assert (got["experts"][0] == -1).all()                  # the dense lead
    out = fam.compared(got, params, cfg, 75, (fault,),
                       fam.chunk_starts(75, (8, 16, 32)))
    if part is None:
        # a router that ignores its bias parts from the program's choice
        # far from any boundary, at more positions than rounding explains
        assert out["misrouted_positions"] > fam.MISROUTED_LIMIT
        assert not out["finite"]
        assert out["parted_margin_max"] > fam.CLEAR_MARGIN
        return
    assert out[f"{part}_rel_err"] > 0.02, out[f"{part}_rel_err"]
    if fault == "tail_zero_at_chunk":       # the chunks' first positions
        assert out["rows_worst_position"] in (32, 33, 64, 65)


def test_the_reference_follows_a_given_choice_and_measures_it(fam):
    """``routing`` with another router's choice: its memberships are taken
    (the gates stay the reference's own scores), the distance from the
    boundary of every disagreement is reported (``parted``: within
    CLEAR_MARGIN rounding decides it, farther out nothing does); rows with
    no choice given (-1) keep the reference's own."""
    m = fam.CLEAR_MARGIN
    # scores s + b by hand: expert 0 and 1 clear, 2 and 3 a near tie at the
    # boundary (k = 2 would cut between 1 and 2: use k = 3)
    cfg3 = _cfg(experts_per_token=3)
    logit = jnp.log(jnp.asarray([0.9, 0.8, 0.5 + m / 4, 0.5, 0.2, 0.1, 0.1,
                                 0.1]) / (1 - jnp.asarray(
                                     [0.9, 0.8, 0.5 + m / 4, 0.5, 0.2, 0.1,
                                      0.1, 0.1])))
    router = jnp.zeros((8, 8)).at[0].set(logit)    # x @ router = logit
    x = jnp.zeros((4, 8)).at[:, 0].set(1.0)
    zero = jnp.zeros((8,))
    given = jnp.asarray([[0, 1, 2], [0, 1, 3], [0, 4, 2], [-1, -1, -1]])
    g, r = fam.routing(x, router, zero, cfg3, given)
    chose = np.asarray(g > 0)
    assert chose[0].tolist() == [1, 1, 1, 0, 0, 0, 0, 0]    # its own
    assert chose[1].tolist() == [1, 1, 0, 1, 0, 0, 0, 0]    # the tie, taken
    assert chose[2].tolist() == [1, 0, 1, 0, 1, 0, 0, 0]    # taken too
    assert chose[3].tolist() == chose[0].tolist()           # none given
    assert np.asarray(r["taken"]).tolist() == [0, 2, 2, 0]
    assert np.asarray(r["parted"])[:2].max() <= m
    # expert 4 for expert 1: 0.3 from the boundary on both sides
    assert float(r["parted"][2]) > 10 * m
    # the gates are the reference's own scores over the members it took
    sc = np.asarray([0.9, 0.8, 0.5 + m / 4, 0.5, 0.2, 0.1, 0.1, 0.1])
    np.testing.assert_allclose(
        np.asarray(g[2]), chose[2] * sc / (sc[[0, 2, 4]].sum() + 1e-6),
        rtol=1e-5)
    np.testing.assert_allclose(np.asarray(r["margin"]), m / 4, rtol=1e-3)


def test_the_comparison_sees_a_pool_kept_in_float8(fam, params):
    cfg = _cfg()
    got = fam.served(params, cfg, _prompt(75, seed=3), buckets=(8, 16, 32),
                     block=8, kv_impl="gather", interpret=False,
                     cache_dtype="float32", pool_fault="pool_float8")
    out = fam.compared(got, params, cfg, 75)
    assert out["decode_rows_rel_err"] > 0.01 < out["decode_tail_rel_err"]


def test_the_routing_comes_out_of_the_programs_that_give_the_logits(params):
    """``prefill_chunk_routed`` is ``prefill_chunk`` (the same logits and
    accumulator) with every row's experts in layer order; the decode step's
    ``chosen`` likewise; a model without operator stacks has no such
    entry."""
    cfg = _cfg()
    pool = kc.init_pool(cfg, 8, 8, jnp.float32, state_slots=2)
    toks = jnp.asarray(_prompt(32), jnp.int32)

    def acc():
        return {"k": jnp.zeros((2, 64, 1, 64)), "v": jnp.zeros((2, 64, 1, 64)),
                **kc.fresh_state(pool)}
    l0, a0 = lm.prefill_chunk(params, toks, jnp.int32(20), 0, acc(), cfg)
    l1, a1, chosen = lm.prefill_chunk_routed(params, toks, jnp.int32(20), 0,
                                             acc(), cfg)
    np.testing.assert_array_equal(l0, l1)
    jax.tree.map(np.testing.assert_array_equal, a0, a1)
    assert chosen.shape == (9, 32, 2) and chosen.dtype == jnp.int32
    assert (np.asarray(chosen[0]) == -1).all()
    assert ((np.asarray(chosen[1:]) >= 0) & (np.asarray(chosen[1:]) < 8)).all()
    with pytest.raises(NotImplementedError, match="operator_stacks"):
        tiny = moe.tiny(dtype="float32", attn_impl="reference")
        lm.prefill_chunk_routed(
            moe.init_params(jax.random.PRNGKey(0), tiny), toks,
            jnp.int32(20), 0,
            {"k": jnp.zeros((2, 64, 2, 16)), "v": jnp.zeros((2, 64, 2, 16))},
            tiny)


def test_the_router_chooses_by_the_biased_score_and_weighs_by_the_plain(
        fam, params):
    cfg = _cfg()
    y = jax.random.normal(jax.random.PRNGKey(2), (64, 128))
    router = params["layers"]["router"][0]
    bias = jnp.zeros((8,)).at[3].set(1.0)       # lifts expert 3 over the rest
    gates, experts, probs = moe._route(y, router, bias, cfg)
    _, plain, _ = moe._route(y, router, jnp.zeros((8,)), cfg)
    assert bool(jnp.all(jnp.any(experts == 3, axis=-1)))
    assert not bool(jnp.all(jnp.any(plain == 3, axis=-1)))
    s = jnp.take_along_axis(probs, experts, axis=-1)
    np.testing.assert_allclose(
        gates, s / (jnp.sum(s, -1, keepdims=True) + 1e-6), rtol=1e-6)
    want = fam.gates(y, router, bias, cfg)
    got = jnp.zeros((64, 8)).at[jnp.arange(64)[:, None], experts].set(gates)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)
    # a family without the eps divides by the bare sum, as it did
    assert moe.tiny().route_eps == 0.0


# --- a slot's tail --------------------------------------------------------------


@IMPLS
def test_a_decode_step_moves_a_live_slots_tail_and_no_other(
        params, kv_impl, interpret):
    """A slot whose table row is TRASH holds no request: its tails come
    out of the steps as they went in, bit for bit (an idle step too: the
    second block has no live slot at all); the live slot's move on by a row
    a step, the older row shifting to the front."""
    cfg = _cfg()
    pool = kc.init_pool(cfg, 6, 8, jnp.float32, state_slots=3)
    pool = {**pool, "conv": pool["conv"] - 0.25}
    before = np.asarray(pool["conv"])
    tables = np.full((3, 4), kc.TRASH, np.int32)
    tables[1] = [1, 2, 3, 4]
    args = (jnp.asarray([0, 3, 0], jnp.int32),
            jnp.asarray([5, 7, 9], jnp.int32), jnp.zeros((3,)),
            jax.random.PRNGKey(0), cfg)
    _, after = kc.paged_decode_steps(
        params, pool, {"global": jnp.asarray(tables)}, *args, 1,
        impl=kv_impl, interpret=interpret)
    after = {k: np.asarray(v) for k, v in after.items()}
    for idle in (0, 2):
        np.testing.assert_array_equal(after["conv"][:, idle],
                                      before[:, idle])
    np.testing.assert_array_equal(after["conv"][:, 1, :128],
                                  before[:, 1, 128:])
    assert not np.allclose(after["conv"][:, 1, 128:], before[:, 1, 128:])
    _, idle = kc.paged_decode_steps(
        params, {k: jnp.asarray(v) for k, v in after.items()},
        {"global": jnp.full((3, 4), kc.TRASH, jnp.int32)}, *args, 2,
        impl=kv_impl, interpret=interpret)
    np.testing.assert_array_equal(np.asarray(idle["conv"]), after["conv"])


# --- the engine -----------------------------------------------------------------


def _engine(params, **kw):
    from ray_tpu.llm.engine import LLMEngine
    kw = {"max_slots": 2, "max_len": 128, "prefill_buckets": (16, 32),
          "cache_dtype": "float32", "kv_block_size": 8, "steps_per_sync": 4,
          **kw}
    return LLMEngine(_cfg(), params, **kw)


def _greedy(fam, params, prompt, n, width=96):
    """The reference's greedy continuation; every forward over ``width``
    tokens (the model is causal: what pads the row changes nothing before
    it), so the reference compiles once."""
    toks = list(prompt)
    for _ in range(n):
        row = jnp.asarray([toks + [0] * (width - len(toks))])
        logits = fam.forward(params, row, _cfg())
        toks.append(int(jnp.argmax(logits[0, len(toks) - 1])))
    return toks[len(prompt):]


@pytest.mark.parametrize("kv_impl", ["gather", "paged_flash"])
def test_the_engine_serves_it_through_slots_that_are_used_again(
        fam, params, kv_impl):
    """Three requests of different lengths into two slots, admitted at
    different steps (the third takes the slot the first left, whose tails
    it overwrites whole; the second is a chunked prefill of two chunks and
    an odd tail): each reads exactly what it reads alone, by the
    reference's greedy continuation."""
    prompts = [_prompt(n, seed=n) for n in (20, 75, 9)]
    new = (6, 12, 8)

    async def run():
        eng = _engine(params, kv_impl=kv_impl)
        st = eng.stats
        assert st["kv_impl"] == kv_impl
        assert st["state_layers"] == 7
        assert st["state_bytes_per_slot"] == 7 * 256 * 4
        assert st["state_bytes"] == 2 * 7 * 256 * 4
        assert st["pool_blocks_state"] == 2

        async def one(p, n, delay):
            await asyncio.sleep(delay)
            return (await eng.generate(p, max_new_tokens=n))["tokens"]
        outs = await asyncio.gather(*(
            one(p, n, d) for p, n, d in zip(prompts, new, (0, 0.2, 0.4))))
        st = eng.stats
        await eng.stop()
        return outs, st
    outs, st = asyncio.run(run())
    for p, n, out in zip(prompts, new, outs):
        assert out == _greedy(fam, params, p, n)
    assert st["state_admits"] == 3 and st["blocks_used_state"] == 0
    assert st["prefix_hit_tokens"] == 0


def test_a_request_parked_on_the_pool_reads_what_it_reads_alone(fam, params):
    """The engine reserves a request's whole horizon of blocks at admission
    and parks what does not fit (it never takes a running request's blocks
    away): with a pool of one horizon the second request waits for the
    first to end, is admitted to a slot whose tails another request left,
    and reads the same tokens as alone."""
    prompts = [_prompt(40, seed=5), _prompt(33, seed=6)]

    async def run():
        eng = _engine(params, kv_pool_blocks=9, max_len=64)

        async def one(p):
            return (await eng.generate(p, max_new_tokens=10))["tokens"]
        outs = await asyncio.gather(*(one(p) for p in prompts))
        st = eng.stats
        await eng.stop()
        return outs, st
    outs, st = asyncio.run(run())
    for p, out in zip(prompts, outs):
        assert out == _greedy(fam, params, p, 10)
    assert st["state_admits"] == 2


def test_each_refusal_names_the_kind(params):
    with pytest.raises(ValueError, match="short-convolution layers"):
        _engine(params, prefix_cache=True)
    with pytest.raises(ValueError, match="short convolution's tail"):
        _engine(params, spec=True)
    eng = _engine(params)
    assert eng._kv.prefix_cache is False      # the default resolves to off
    with pytest.raises(ValueError, match="no recurrent state or conv tail"):
        asyncio.run(eng.generate_prefilled([1, 2, 3], {"k": 0}))
    from ray_tpu.llm.pd import PrefillEngine
    with pytest.raises(ValueError, match="short convolution's tail"):
        PrefillEngine(_cfg(), params)
    with pytest.raises(NotImplementedError, match="gated short convolution"):
        lm.verify_tokens_core(params, {}, jnp.zeros((1, 2), jnp.int32),
                              jnp.zeros((1,), jnp.int32), _cfg(), None)
    with pytest.raises(NotImplementedError, match="gated short convolution"):
        moe.forward(params, jnp.zeros((1, 8), jnp.int32), _cfg())
    with pytest.raises(ValueError, match="beside window or latent"):
        lm.layer_kinds(_cfg(layer_types=("state", "window") * 4 + ("state",),
                            sliding_window=8))
    with pytest.raises(ValueError, match="two sub-layers"):
        lm.layer_kinds(_cfg(layer_types=("state", "experts") * 4
                            + ("state",)))
    with pytest.raises(ValueError, match="short-convolution model"):
        moe.init_params(jax.random.PRNGKey(0),
                        _cfg(layer_types=("state", "latent") * 4
                             + ("state",)))
