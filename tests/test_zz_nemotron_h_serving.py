"""A model whose layers are each ONE mixer behind one norm - a Mamba-2
state-space mixer, a layer of non-gated relu^2 experts of which this device
holds a slice, or attention - SERVED: the one engine, the one block manager
(a state and a conv tail A SLOT beside the paged K/V pool) and the forwards
of ``llm/model.py`` against the plain reference of
``benchmarks/families/nemotron_h.py`` (the token-by-token recurrence), on
the CPU at tiny widths with seeded weights."""
import asyncio
import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import kvcache as kc
from ray_tpu.llm import model as lm
from ray_tpu.models import moe
from ray_tpu.ops import ssm

BENCH = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                     "benchmarks")


@pytest.fixture(scope="module")
def fam():
    """benchmarks/families/nemotron_h.py: the plain reference."""
    sys.path.insert(0, BENCH)
    try:
        from harness import spec
        yield spec.family("nemotron_h")
    finally:
        sys.path.remove(BENCH)


# the decode step's kernels under the interpreter beside their reference
IMPLS = pytest.mark.parametrize("kv_impl,interpret", [
    ("gather", False), ("paged_flash", True)])


def _cfg(**kw):
    """Two periods of MEMEM*E at tiny widths: 4 state heads of 16 with a
    state of 16 in 2 groups, chunks of 16 tokens; 4 of 16 experts held."""
    base = dict(vocab_size=256, dim=64, n_layers=14, n_heads=4, n_kv_heads=2,
                head_size=16, ffn_dim=32, n_experts=16, experts_per_token=3,
                experts_held=4, first_expert=4, n_shared_experts=2,
                ssm_heads=4, ssm_head_dim=16, ssm_state=16, ssm_groups=2,
                ssm_chunk=16, max_seq_len=256, dtype="float32",
                attn_impl="reference", gmm_impl="ragged_dot")
    base.update(kw)
    return moe.nemotron_3_nano_30b_a3b(**base)


@pytest.fixture(scope="module")
def params():
    return moe.init_params(jax.random.PRNGKey(0), _cfg())


def _rule(s, seed=0, h=4, p=16, g=2, n=16):
    """Seeded inputs of the state-space rule for a row of s tokens."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return dict(
        x=jax.random.normal(ks[0], (s, h, p)),
        dt=jax.nn.softplus(jax.random.normal(ks[1], (s, h)) - 2.0),
        A=-jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0, maxval=2.5)),
        B=jax.random.normal(ks[3], (s, g, n)),
        C=jax.random.normal(ks[4], (s, g, n)),
        D=jnp.ones((h,)),
        state0=jax.random.normal(ks[5], (h, p, n)))


# --- (a), (b): the rule -----------------------------------------------------


@pytest.mark.parametrize("s,length,chunk", [
    (32, 32, 16), (48, 37, 16), (40, 40, 16), (64, 5, 16), (16, 1, 16),
    (24, 24, 128)])
def test_the_chunked_scan_is_the_recurrence(fam, s, length, chunk):
    """With an initial state, at lengths that are no multiple of the chunk:
    the outputs of the positions before ``length`` are the recurrence's,
    and the state is the one after position ``length - 1``: the padded
    positions leave it as it was."""
    r = _rule(s)
    with jax.default_matmul_precision("highest"):
        y, state = ssm.ssd_chunk_scan(
            r["x"], r["dt"], r["A"], r["B"], r["C"], r["D"], r["state0"],
            jnp.int32(length), chunk)
        want_y, want_state = fam.recurrence(
            r["x"][:length], r["dt"][:length], r["A"], r["B"][:length],
            r["C"][:length], r["D"], r["state0"])
    np.testing.assert_allclose(y[:length], want_y, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(state, want_state, atol=2e-4, rtol=2e-4)


def test_a_step_continues_what_the_scan_left(fam):
    """(b) 20 tokens by the scan, then 6 by ``ssd_step``, are 26 by the
    recurrence; a batch's rows are each their own."""
    r = _rule(26, seed=1)
    with jax.default_matmul_precision("highest"):
        _, state = ssm.ssd_chunk_scan(
            r["x"][:20], r["dt"][:20], r["A"], r["B"][:20], r["C"][:20],
            r["D"], r["state0"], jnp.int32(20), 16)
        ys = []
        state = jnp.stack([state, jnp.zeros_like(state)])   # a second row
        for t in range(20, 26):
            y, state = ssm.ssd_step(
                jnp.stack([r["x"][t]] * 2), jnp.stack([r["dt"][t]] * 2),
                r["A"], jnp.stack([r["B"][t]] * 2),
                jnp.stack([r["C"][t]] * 2), r["D"], state)
            ys.append(y[0])
        want_y, want_state = fam.recurrence(
            r["x"], r["dt"], r["A"], r["B"], r["C"], r["D"], r["state0"])
    np.testing.assert_allclose(jnp.stack(ys), want_y[20:], atol=2e-4,
                               rtol=2e-4)
    np.testing.assert_allclose(state[0], want_state, atol=2e-4, rtol=2e-4)


def test_the_mixer_hands_on_its_tail_at_the_rows_length(fam, params):
    """A padded row's state and conv tail are those at ``length``: the
    tail is the last three rows of xBC before the conv, and a chunk that
    starts from them continues the row."""
    cfg = _cfg()
    lp = {k: v[0] for k, v in params["state_layers"].items()}
    u = jax.random.normal(jax.random.PRNGKey(3), (32, cfg.dim))
    zero = lm.fresh_state(cfg, jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, st, tail = ssm.mixer_prefill(u, lp, cfg, *zero, jnp.int32(32))
        a, st_a, tail_a = ssm.mixer_prefill(
            jnp.pad(u[:21], ((0, 11), (0, 0))), lp, cfg, *zero,
            jnp.int32(21))
        b, st_b, tail_b = ssm.mixer_prefill(
            jnp.pad(u[21:], ((0, 5), (0, 0))), lp, cfg, st_a, tail_a,
            jnp.int32(11))
        want, want_state = fam.mamba(u, lp, cfg)
    xbc = u @ lp["w_xbc"]
    np.testing.assert_allclose(tail_a, xbc[18:21], atol=1e-6)
    np.testing.assert_allclose(tail_b, tail, atol=1e-6)
    np.testing.assert_allclose(whole, want, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(jnp.concatenate([a[:21], b[:11]]), want,
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(st_b, want_state, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(st, want_state, atol=2e-4, rtol=2e-4)


# --- the layers, the stacks, the cache --------------------------------------


def test_the_layers_run_as_one_scanned_segment():
    """MEMEM*E four times is ONE segment of period 7, whatever stack a
    kind's rows lie in; the published 52 layers end irregularly."""
    seg, = lm._segments(_cfg())
    assert (seg.stack, seg.kinds, seg.repeats) == (
        None, ("state", "experts", "state", "experts", "state", "global",
               "experts"), 2)
    cut, = lm._segments(moe.nemotron_3_nano_30b_a3b(n_layers=28))
    assert (len(cut.kinds), cut.repeats) == (7, 4)
    full = moe.nemotron_3_nano_30b_a3b()
    kinds = lm.layer_kinds(full)
    assert (kinds.count("state"), kinds.count("experts"),
            kinds.count("global")) == (23, 23, 6)
    assert sum(len(s.kinds) * s.repeats for s in lm._segments(full)) == 52
    assert lm.kind_layers(_cfg()) == {
        "global": (5, 12), "state": (0, 2, 4, 7, 9, 11)}
    assert lm.single_mixer(_cfg()) and not lm.single_mixer(moe.tiny())


def test_a_state_is_a_slots_and_has_no_position(params):
    """The fourth kind: two arrays indexed (the kind's layers, SLOT, ...),
    the state float32 whatever the cache's dtype; the manager counts a
    state a sequence beside the blocks."""
    cfg = _cfg()
    assert kc.pool_kinds(cfg) == (("global", (5, 12)),
                                  ("state", (0, 2, 4, 7, 9, 11)))
    assert kc.row_shapes(cfg, kc.STATE) == ((4, 16, 16), (3, 128))
    pool = kc.init_pool(cfg, 9, 8, jnp.bfloat16, state_slots=3)
    assert {k: (v.shape, str(v.dtype)) for k, v in pool.items()} == {
        "k": ((2, 9, 2, 8, 16), "bfloat16"),
        "v": ((2, 9, 2, 8, 16), "bfloat16"),
        "ssm": ((6, 3, 4, 16, 16), "float32"),
        "conv": ((6, 3, 3, 128), "bfloat16")}
    per_slot = 6 * (4 * 16 * 16 * 4 + 3 * 128 * 2)
    assert kc.kind_block_bytes(pool)[kc.STATE] == per_slot \
        == kc.state_slot_bytes(cfg, jnp.bfloat16)
    with pytest.raises(ValueError, match="state_slots"):
        kc.init_pool(cfg, 9, 8, jnp.bfloat16)
    # the token-order rows of such a model are its global layers' alone
    assert kc._layout(pool, kc.pool_kinds(cfg)) == (("global", (0, 1)),)
    mgr = kc.KVBlockManager(9, 8, table_width=4, prefix_cache=False,
                            state_slots=2)
    assert mgr.used_by_kind() == {"global": 0, "state": 0}
    a = mgr.alloc_seq("a", [1] * 9, 4)
    assert set(a["tables"]) == {"global"}
    mgr.alloc_seq("b", [1] * 9, 4)
    assert mgr.used_by_kind() == {"global": 4, "state": 2}
    assert mgr.free_by_kind() == {"global": 4, "state": 0}
    # (the engine's slots bound the sequences; the manager only reports)
    mgr.free_seq("a")
    assert mgr.used_by_kind() == {"global": 2, "state": 1}
    assert mgr.free_by_kind() == {"global": 6, "state": 1}


# --- (c): the forwards against the reference --------------------------------


def test_cold_prefill_is_the_reference(fam, params):
    cfg = _cfg()
    toks = list(np.random.default_rng(0).integers(1, 256, size=50))
    logits, kv = lm.prefill(params, jnp.asarray(lm.pad_prompt(toks, 64)),
                            jnp.int32(50), cfg, 64)
    assert {k: v.shape for k, v in kv.items()} == {
        "k": (2, 64, 2, 16), "v": (2, 64, 2, 16), "ssm": (6, 4, 16, 16),
        "conv": (6, 3, 128)}
    # the debug entry: the same logits and cache, and their routing
    again, kv2, experts = lm.prefill_routed(
        params, jnp.asarray(lm.pad_prompt(toks, 64)), jnp.int32(50), cfg, 64)
    assert experts.shape == (6, 64, 3) and _digest(kv2) == _digest(kv)
    np.testing.assert_array_equal(again, logits)
    want = fam.forward(params, jnp.asarray([toks]), cfg)[0, -1]
    assert fam.rel_err(logits, want) < 2e-5
    np.testing.assert_allclose(kv["ssm"][0], fam.first_state(
        params, toks, cfg, bf16=False), atol=1e-4, rtol=1e-4)


@IMPLS
def test_prefill_and_decode_through_the_state_are_the_reference(
        fam, params, kv_impl, interpret):
    """(c) ``serve_parity``: a prompt admitted to a USED slot, prefilled,
    its K/V scattered and its states written, 16 tokens decoded through
    the pool, against the reference's full forward: logits, not tokens.
    The slot's first state is what the served prefill leaves over the same
    tokens, and the slot beside it keeps its zeros."""
    r = fam.serve_parity(params, _cfg(), 3, 40, buckets=(32, 64), block=8,
                         kv_impl=kv_impl, interpret=interpret,
                         cache_dtype="float32")
    assert r["finite"] and r["idle_state_max"] == 0.0
    assert r["misrouted_positions"] == 0
    assert r["prefill_rel_err"] < 2e-5 and r["decode_rel_err"] < 1e-4
    assert r["state_rel_err"] < 2e-6 and r["state_vs_float32"] < 2e-5
    assert r["state_bfloat16_share"] < 1e-3
    assert r["routing_decisions"] == 6 * 56 and r["routing_taken"] == 0
    assert 0 < r["routing_excused_share"] < r["routing_decisions_own_share"] \
        <= r["routing_held_own_share"] <= 1
    assert len(r["prefill_rel_errs"]) == 15 and len(r["decode_rel_errs"]) \
        == 16


def test_a_given_routing_is_held_to_the_references_where_it_is_clear(
        fam, params):
    """The reference takes from the program's routing the memberships of
    the experts that lie within CLEAR_MARGIN of the boundary in its own
    scores, and nothing else: a choice that differs farther out is
    MISROUTED (no rounding explains it) and is NOT followed."""
    cfg = _cfg()
    toks = jnp.asarray([np.random.default_rng(2).integers(1, 256, size=24)])
    with jax.default_matmul_precision("highest"):
        _, _, experts = lm.prefill_routed(params, jnp.asarray(lm.pad_prompt(
            list(np.asarray(toks[0])), 32)), jnp.int32(24), cfg, 32)
    given = np.asarray(experts)[:, None, :24]           # (6, 1, 24, 3)
    own, r = fam.forward_margins(params, toks, cfg)
    assert not np.asarray(r["parted"]).any() and not np.asarray(
        r["taken"]).any()
    same, r = fam.forward_margins(params, toks, cfg, given=jnp.asarray(given))
    assert not np.asarray(r["parted"]).any()    # nowhere another choice
    np.testing.assert_array_equal(same, own)


def test_the_reference_takes_what_rounding_decides_and_nothing_else(
        fam, monkeypatch):
    """``routing`` on rows of its own: a swap of the two experts nearest
    the boundary is taken from the given table; a swap that drops the
    expert farthest inside is MISROUTED and that expert stays."""
    cfg = _cfg(first_expert=0)
    rng = np.random.default_rng(5)
    x = rng.standard_normal((64, 64)).astype(np.float32)
    router = jnp.asarray(rng.standard_normal((64, 16)) / 8, jnp.float32)
    bias = jnp.asarray(0.01 * rng.standard_normal(16), jnp.float32)
    k = cfg.experts_per_token
    monkeypatch.setattr(fam, "CLEAR_MARGIN", 0.05)

    def scores(x):
        v = np.asarray(jax.nn.sigmoid(jnp.asarray(x) @ router) + bias)
        order = np.argsort(-v, -1)
        at = v[np.arange(len(v))[:, None], order]
        return order, at[:, k - 1] - at[:, k], at[:, 0] - at[:, k]
    # rows whose boundary's two neighbours are near and whose best expert
    # is not
    _, gap, inner = scores(x)
    x = x[(gap < 0.04) & (inner > 0.06)][:8]
    assert len(x) == 8
    order, gap, inner = scores(x)
    own, x = order[:, :k], jnp.asarray(x)
    g0, r0 = fam.routing(x, router, bias, cfg)
    assert not np.asarray(r0["parted"]).any() \
        and (np.asarray(r0["near"]) >= 2).all()
    near = own.copy()
    near[:, k - 1] = order[:, k]            # last in <-> first out
    g1, r1 = fam.routing(x, router, bias, cfg, jnp.asarray(near))
    assert (np.asarray(r1["taken"]) == 2).all()
    np.testing.assert_allclose(r1["parted"], gap, rtol=1e-5)
    assert (np.asarray(g1)[np.arange(8), order[:, k]] > 0).all() \
        and (np.asarray(g1)[np.arange(8), order[:, k - 1]] == 0).all()
    wrong = own.copy()
    wrong[:, 0] = order[:, k]               # the best expert dropped
    g2, r2 = fam.routing(x, router, bias, cfg, jnp.asarray(wrong))
    np.testing.assert_allclose(r2["parted"], inner, rtol=1e-5)
    assert (np.asarray(r2["parted"]) > fam.CLEAR_MARGIN).all()
    assert (np.asarray(g2)[np.arange(8), order[:, 0]] > 0).all()   # stays
    np.testing.assert_allclose(np.asarray(g2).sum(-1), cfg.routed_scaling,
                               rtol=1e-5)


def test_the_state_comparison_sees_a_bfloat16_state(fam, params):
    """The control of the chip's parity at tiny widths: a state rounded to
    bfloat16 wherever it is stored moves the logits by less than bf16
    activations would, and the state's own comparison by a thousand times
    its sound reading."""
    r = fam.serve_parity(params, _cfg(), 3, 40, buckets=(32, 64), block=8,
                         kv_impl="gather", interpret=False,
                         cache_dtype="float32", fault="state_bfloat16")
    assert r["decode_logits_rel_err"] < 5e-3
    assert r["state_rel_err"] > 1e-3 and r["state_bfloat16_share"] == 1.0
    assert r["state_vs_reference"] > 1e-3
    assert r["decode_rel_err"] == fam.STATE_WEIGHT * r["state_vs_reference"]


def test_the_state_comparison_sees_products_of_bfloat16_operands(fam, params):
    """The second control: the rule's operands rounded to bfloat16, its sums
    and what it stores float32. Nothing a look at the stored values tells
    from sound; the reference's recurrence does."""
    r = fam.serve_parity(params, _cfg(), 3, 40, buckets=(32, 64), block=8,
                         kv_impl="gather", interpret=False,
                         cache_dtype="float32",
                         fault="state_products_bfloat16")
    assert r["state_bfloat16_share"] < 1e-3
    assert r["state_vs_reference"] > 1e-3
    from ray_tpu.ops import ssm
    assert ssm.ssd_step.__name__ == "ssd_step"      # the rule is back


def test_a_chunked_prefill_is_a_whole_one(fam, params):
    """(c) 45 tokens as chunks of 32 and 13 through ``prefill_chunk`` (the
    state and the tail handed on in the accumulator) leave the logits, the
    K/V rows and the states of one ``prefill`` over 64."""
    cfg = _cfg()
    toks = list(np.random.default_rng(1).integers(1, 256, size=45))
    logits, kv = lm.prefill(params, jnp.asarray(lm.pad_prompt(toks, 64)),
                            jnp.int32(45), cfg, 64)
    pool = kc.init_pool(cfg, 12, 8, jnp.float32, state_slots=1)
    acc = {"k": jnp.zeros((2, 96, 2, 16)), "v": jnp.zeros((2, 96, 2, 16)),
           **kc.fresh_state(pool)}
    for off, n, b in ((0, 32, 32), (32, 13, 16)):
        got, acc = lm.prefill_chunk(
            params, jnp.asarray(lm.pad_prompt(toks[off:off + n], b)),
            jnp.int32(n), jnp.int32(off), acc, cfg)
    assert fam.rel_err(got, logits) < 2e-5
    for key in ("ssm", "conv"):
        np.testing.assert_allclose(acc[key], kv[key], atol=2e-5, rtol=2e-4)
    for key in ("k", "v"):
        np.testing.assert_allclose(acc[key][:, :45], kv[key][:, :45],
                                   atol=2e-5)


@IMPLS
def test_a_decode_step_leaves_an_idle_slots_state_alone(params, kv_impl,
                                                        interpret):
    """PR 57's rule for the fourth kind: a slot whose table row is TRASH
    holds no request, and its state and tail come out of the step as they
    went in, bit for bit; the live slot's move. Under the kernel
    (``paged_flash``) as under its reference."""
    cfg = _cfg()
    pool = kc.init_pool(cfg, 6, 8, jnp.float32, state_slots=2)
    pool = {**pool, "ssm": pool["ssm"] + 0.5, "conv": pool["conv"] - 0.25}
    before = jax.tree.map(np.asarray, pool)
    tables = np.full((2, 4), kc.TRASH, np.int32)
    tables[1] = [1, 2, 3, 4]
    out, after = kc.paged_decode_steps(
        params, pool, {"global": jnp.asarray(tables)},
        jnp.asarray([0, 3], jnp.int32), jnp.asarray([5, 7], jnp.int32),
        jnp.zeros((2,)), jax.random.PRNGKey(0), cfg, 2, impl=kv_impl,
        interpret=interpret)
    for key in ("ssm", "conv"):
        np.testing.assert_array_equal(after[key][:, 0], before[key][:, 0])
        assert not np.allclose(after[key][:, 1], before[key][:, 1])


# --- (d): the engine --------------------------------------------------------


def _engine(params, **kw):
    from ray_tpu.llm.engine import LLMEngine
    kw = {"max_slots": 2, "max_len": 128, "prefill_buckets": (16, 32),
          "cache_dtype": "float32", "kv_block_size": 8, "steps_per_sync": 4,
          **kw}
    return LLMEngine(_cfg(), params, **kw)


def _greedy(fam, params, prompt, n):
    toks = list(prompt)
    for _ in range(n):
        logits = fam.forward(params, jnp.asarray([toks]), _cfg())
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


@pytest.mark.parametrize("kv_impl", ["gather", "paged_flash"])
def test_the_engine_serves_it_through_slots_that_are_used_again(
        fam, params, kv_impl):
    """(d) Three requests of different lengths into two slots, admitted at
    different steps (the third takes the slot the first left; the second is
    a chunked prefill): each reads exactly what it reads alone, by the
    reference's greedy continuation. The kernel (``paged_flash``, which the
    engine interprets off the chip) moves a state a live slot a step,
    ``state_slot_steps`` = ``slot_steps``; its reference every slot's."""
    from ray_tpu.llm.engine import engine_metrics
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(1, 256, size=n)) for n in (20, 45, 9)]
    new = (6, 12, 8)

    def sums():
        m = engine_metrics()
        return {k: sum(m[k]._sums.values())
                for k in ("state_slot_steps", "slot_steps", "block_steps")}
    before = sums()

    async def run():
        eng = _engine(params, kv_impl=kv_impl)
        assert eng.stats["kv_impl"] == kv_impl
        assert eng.stats["kv_interpret"] == (kv_impl == "paged_flash")
        assert eng.stats["state_layers"] == 6
        assert eng.stats["state_bytes_per_slot"] == 6 * (4096 + 1536)
        assert eng.stats["state_bytes"] == 2 * 6 * (4096 + 1536)
        assert eng.stats["pool_blocks_state"] == 2

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
    d = {k: v - before[k] for k, v in sums().items()}
    # a reply's first token is the prefill's: one decode step each other
    assert d["slot_steps"] == sum(n - 1 for n in new)
    assert st["state_slot_steps"] == d["state_slot_steps"] == (
        d["slot_steps"] if kv_impl == "paged_flash"
        else 2 * d["block_steps"])


# --- (e): the expert layer's share ------------------------------------------


def _rows(n=24):
    return jax.random.normal(jax.random.PRNGKey(5), (n, 64), jnp.float32)


def test_the_relu2_layer_has_two_matrices_an_expert(params):
    """No gate, routed or shared; an expert's width is STORED in whole
    128-lane tiles, what lies past ``ffn_dim`` (32) zeros that add nothing;
    the in-projection's columns are three leaves."""
    layers = params["expert_layers"]
    assert not {"w_gate", "shared_gate"} & set(layers)
    assert layers["w_up"].shape == (6, 4, 64, 128)
    assert layers["w_down"].shape == (6, 4, 128, 64)
    assert not np.asarray(layers["w_up"][..., 32:]).any()
    assert not np.asarray(layers["w_down"][:, :, 32:]).any()
    assert np.asarray(layers["w_up"][..., :32]).all()
    assert layers["shared_up"].shape == (6, 64, 64)
    assert _cfg().expert_mats == 2 and moe.tiny().expert_mats == 3
    state = params["state_layers"]
    assert (state["w_z"].shape, state["w_xbc"].shape, state["w_dt"].shape) \
        == ((6, 64, 64), (6, 64, 128), (6, 64, 4))
    # the published widths need no padding but an expert's 1,856
    full = jax.eval_shape(lambda: moe.init_params(
        jax.random.PRNGKey(0), moe.nemotron_3_nano_30b_a3b(
            n_layers=7, experts_held=2, vocab_size=1024)))
    assert full["expert_layers"]["w_up"].shape == (3, 2, 2688, 1920)
    assert full["expert_layers"]["w_down"].shape == (3, 2, 1920, 2688)
    assert full["state_layers"]["w_xbc"].shape == (3, 2688, 6144)


def test_the_shares_add_up_to_the_uncut_layer(fam):
    """(e) The guide's share test: the four shares' routed parts (experts
    0-4, 4-8, 8-12, 12-16) plus the shared expert counted once are the
    uncut reference's layer, in the program and in the reference."""
    whole = _cfg(experts_held=0, first_expert=0)
    stack = moe.init_params(jax.random.PRNGKey(2), whole)["expert_layers"]
    lp = {k: v[0] for k, v in stack.items()}
    x = _rows()
    with jax.default_matmul_precision("highest"):
        want = fam.layer_share(x, lp, whole)        # all 16 experts
        shared = want - fam.routed_share(x, lp, whole)[0]
        program, reference = shared, shared
        for first in range(0, 16, 4):
            cfg = _cfg(first_expert=first)
            mine = {**lp, **{k: lp[k][first:first + 4]
                             for k in ("w_up", "w_down")}}
            program = program + moe.serve_block(x, mine, cfg)[0] - shared
            reference = reference + fam.routed_share(x, mine, cfg)[0]
    np.testing.assert_allclose(reference, want, atol=2e-5)
    np.testing.assert_allclose(program, want, atol=5e-5)


# --- (f): what is refused ---------------------------------------------------


def test_each_refusal_says_why(params):
    with pytest.raises(ValueError, match="prefix_cache=True with state"):
        _engine(params, prefix_cache=True)
    with pytest.raises(ValueError, match="speculative decoding is not "
                       "supported with state layers"):
        _engine(params, spec=True)
    eng = _engine(params)
    assert eng._kv.prefix_cache is False      # the default resolves to off
    with pytest.raises(ValueError, match="prefill/decode hand-off"):
        asyncio.run(eng.generate_prefilled([1, 2, 3], {"k": 0}))
    from ray_tpu.llm.pd import PrefillEngine
    with pytest.raises(ValueError, match="hand-off is not supported with "
                       "state layers"):
        PrefillEngine(_cfg(), params)
    with pytest.raises(NotImplementedError, match="verify forward does not "
                       "run state layers"):
        lm.verify_tokens_core(params, {}, jnp.zeros((1, 2), jnp.int32),
                              jnp.zeros((1,), jnp.int32), _cfg(), None)
    with pytest.raises(NotImplementedError, match="no state-space layer "
                       "has a backward"):
        moe.forward(params, jnp.zeros((1, 8), jnp.int32), _cfg())
    with pytest.raises(ValueError, match="beside window or latent"):
        lm.layer_kinds(_cfg(layer_types=("state", "window") * 7,
                            sliding_window=8))
    with pytest.raises(NotImplementedError, match="beside global layers "
                       "only"):
        kc.pool_kinds(_cfg(layer_types=("state", "experts") * 7))
    with pytest.raises(ValueError, match="prefix caching is not supported "
                       "with state layers"):
        kc.KVBlockManager(9, 8, table_width=4, state_slots=2)


# --- (g): the families that stand -------------------------------------------


def _digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


STANDING = {
    # family: (its tiny config, the tree's digest, serve_block's output's):
    # read at the parent commit (232a76f), the same seed
    "hybrid": (lambda: moe.k_exaone_236b_a23b(
        vocab_size=256, dim=64, n_layers=9, n_heads=4, n_kv_heads=2,
        head_size=32, ffn_dim=64, n_experts=16, experts_per_token=4,
        experts_held=4, first_expert=4, n_dense_layers=1, dense_ffn_dim=128,
        sliding_window=32, max_seq_len=512, dtype="float32",
        attn_impl="reference", gmm_impl="ragged_dot"),
        "3c0a621858752667", "fab480360e81ddda"),
    "latent": (lambda: moe.mistral_small_4_119b(
        vocab_size=256, dim=64, n_layers=3, n_heads=4, n_kv_heads=4,
        head_size=32, ffn_dim=32, n_experts=16, experts_per_token=4,
        experts_held=4, first_expert=4, q_lora_rank=24, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=32,
        rope_factor=4.0, rope_original_len=16, dtype="float32",
        attn_impl="reference", gmm_impl="ragged_dot"),
        "254b9d79473b38e1", "acf721763d2ae6c2"),
    "train": (lambda: moe.tiny(dtype="float32", gmm_impl="ragged_dot",
                               n_shared_experts=1),
              "c518be5909eec3b3", "57ddc5079cd138ce"),
}


@pytest.mark.parametrize("family", sorted(STANDING))
def test_the_swiglu_families_are_as_they_were(family):
    """(g) ``expert_act`` "swiglu" is the default and changes nothing: a
    standing family's ``init_params`` tree and its ``serve_block`` output
    are the parent commit's, bit for bit at the same seed."""
    make, tree, block = STANDING[family]
    cfg = make()
    assert cfg.expert_act == "swiglu" and not cfg.single_mixer
    got = moe.init_params(jax.random.PRNGKey(7), cfg)
    assert {"w_gate", "shared_gate"} <= set(got["layers"])
    assert _digest(got) == tree
    lp = {k: v[0] for k, v in got["layers"].items()}
    x = jax.random.normal(jax.random.PRNGKey(8), (12, cfg.dim), jnp.float32)
    assert _digest(moe.serve_block(x, lp, cfg)[0]) == block


# --- the preset and what it counts ------------------------------------------


def test_the_preset_is_the_published_one():
    cfg = moe.nemotron_3_nano_30b_a3b()
    assert (cfg.dim, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim) == (2688, 52, 32, 2, 128)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups,
            cfg.ssm_conv_kernel, cfg.ssm_chunk) == (64, 64, 128, 8, 4, 128)
    assert cfg.ssm_widths == (4096, 6144)
    assert (cfg.ffn_dim, cfg.n_experts, cfg.experts_per_token,
            cfg.n_shared_experts * cfg.ffn_dim, cfg.routed_scaling,
            cfg.scoring, cfg.expert_act) == (1856, 128, 6, 3712, 2.5,
                                             "sigmoid", "relu2")
    assert moe._serving_only(cfg) and cfg.rope_layers == "none"
    # the issue's arithmetic: a Mamba-2 layer 38.74M, attention 23.40M, an
    # expert layer whole 1,297.6M and with 16 held 179.96M
    assert cfg._state_params() == pytest.approx(38.74e6, rel=1e-3)
    assert cfg._attn_params() - 2 * cfg.dim == pytest.approx(23.40e6,
                                                             rel=1e-3)
    assert cfg._layer_params(128) == pytest.approx(1297.6e6, rel=1e-3)
    assert cfg._layer_params(16) == pytest.approx(179.96e6, rel=1e-3)
    cut = moe.nemotron_3_nano_30b_a3b(n_layers=28, experts_held=16,
                                      vocab_size=16384)
    assert cut.num_params() == pytest.approx(2806e6, rel=2e-3)
    assert kc.state_slot_bytes(cut, jnp.bfloat16) \
        == 12 * (2097152 + 36864)
    assert kc.row_bytes(cut, kc.GLOBAL, jnp.bfloat16) * 4 == 4096
