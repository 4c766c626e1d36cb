"""A model of window and global layers, a dense first layer and
sigmoid-routed experts of which this device holds a slice, SERVED: the
one engine, the one block manager and the forwards of ``llm/model.py``
against the plain reference of ``benchmarks/families/exaone_moe.py``, on
the CPU at tiny widths with seeded weights."""
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

BENCH = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                     "benchmarks")


@pytest.fixture(scope="module")
def fam():
    """benchmarks/families/exaone_moe.py: the plain reference."""
    sys.path.insert(0, BENCH)
    try:
        from harness import spec
        yield spec.family("exaone_moe")
    finally:
        sys.path.remove(BENCH)


def _cfg(**kw):
    """Dense layer 0, then two periods (window, window, global, window):
    the runner scans them. head_dim 32 beside hidden 64 / 4 heads = 16."""
    base = dict(vocab_size=256, dim=64, n_layers=9, n_heads=4, n_kv_heads=2,
                head_size=32, ffn_dim=64, n_experts=16, experts_per_token=4,
                experts_held=4, first_expert=4, n_dense_layers=1,
                dense_ffn_dim=128, sliding_window=32, max_seq_len=512,
                dtype="float32", attn_impl="reference",
                gmm_impl="ragged_dot")
    base.update(kw)
    return moe.k_exaone_236b_a23b(**base)


@pytest.fixture(scope="module")
def params():
    return moe.init_params(jax.random.PRNGKey(0), _cfg())


def test_the_layers_run_as_segments():
    segs = lm._segments(_cfg())
    assert [(s.stack, s.kinds, s.repeats) for s in segs] == [
        ("dense_layers", ("window",), 1),
        ("layers", ("window", "window", "global", "window"), 2)]
    # the published depth: the dense layer, eleven periods, the rest
    full = lm._segments(moe.k_exaone_236b_a23b())
    assert [(s.layer0, len(s.kinds), s.repeats) for s in full] == [
        (0, 1, 1), (1, 4, 11), (45, 2, 1), (47, 1, 1)]
    # a Llama model is one scan of the whole stack
    from ray_tpu.models import llama
    one, = lm._segments(llama.tiny())
    assert (one.kinds, one.repeats) == (("global",), llama.tiny().n_layers)


KERNELS = dict(attn_impl="flash_interpret", gmm_impl="pallas_interpret")
PATHS = {
    "xla": ({}, "gather", False, 100, (64, 128)),
    "paged_kernel": ({}, "paged_flash", True, 100, (64, 128)),
    "all_kernels": (KERNELS, "paged_flash", True, 130, (64, 256)),
}


@pytest.mark.parametrize("path", sorted(PATHS))
def test_served_prefill_and_decode_are_the_reference(fam, params, path):
    """Prompt and decode both past the window (32), across a block
    edge: the band, the freed block and the window walk are all in the
    comparison. A float32 pool: agreement to rounding."""
    over, impl, interpret, prompt, buckets = PATHS[path]
    got = fam.serve_parity(params, _cfg(**over), 3, prompt, buckets=buckets,
                           block=8, kv_impl=impl, interpret=interpret,
                           cache_dtype="float32")
    assert got["finite"] and got["window_blocks_freed"] >= 1
    # every compared position, whatever its margin
    assert len(got["prefill_rel_errs"]) == len(got["prefill_margins"]) == 15
    assert len(got["decode_rel_errs"]) == 16
    assert max(got["prefill_rel_errs"]) < 5e-6, got["prefill_rel_errs"]
    assert max(got["decode_rel_errs"]) < 5e-6, got["decode_rel_errs"]


def test_the_margin_is_the_distance_to_another_set_of_held_experts(fam):
    """Experts 4-7 held, 4 of 16 chosen. The margin of a token is how far
    its scores must move before it gets another set of HELD experts: a
    swap among experts held elsewhere does not count."""
    cfg = _cfg(dim=16)
    router = jnp.eye(16, dtype=jnp.float32)
    bias = jnp.zeros((16,), jnp.float32)
    logit = lambda p: float(np.log(p / (1 - p)))                 # noqa: E731

    def row(scores):    # expert e scores scores[e] (0.1 where not said)
        return jnp.asarray([[logit(scores.get(e, 0.1)) for e in range(16)]],
                           jnp.float32)
    # chosen 0, 1, 2, 5 (0.9, 0.8, 0.7, 0.6); first left out: 9 at 0.59,
    # then held 6 at 0.4. Held 5 falls out after 0.01; 6 is 0.2 away.
    near = row({0: .9, 1: .8, 2: .7, 5: .6, 9: .59, 6: .4})
    assert float(fam.held_margin(near, router, bias, cfg)[0]) \
        == pytest.approx(0.01, abs=1e-5)
    # the same tie between two experts held elsewhere: the held 5 is
    # safely in (0.3 over the first left out), the held 6 0.19 under the
    # last chosen
    far = row({0: .9, 1: .8, 5: .7, 2: .6, 9: .59, 6: .4})
    assert float(fam.held_margin(far, router, bias, cfg)[0]) \
        == pytest.approx(0.11, abs=1e-5)
    # the bias moves the choice: it counts
    assert float(fam.held_margin(
        near, router, bias.at[6].set(0.195), cfg)[0]) \
        == pytest.approx(0.005, abs=1e-5)


def test_the_comparison_judges_the_clear_positions(fam):
    errs, margins = [0.011, 0.3, 0.012, 0.02], [1.0, 1e-4, 0.5, 0.2]
    assert fam._judged(errs, margins) == 0.02        # the tie is left out
    assert fam._judged(errs, [0.0] * 4) == pytest.approx(0.016)  # median
    # a fault in most positions, none of them clear: the median's
    assert fam._judged([0.3, 0.3, 0.3, 0.011], [0, 0, 0, 1.0]) == 0.3
    assert 0 < fam.CLEAR_MARGIN < 0.05


def _rows(n=24, d=64):
    return jax.random.normal(jax.random.PRNGKey(5), (n, d), jnp.float32)


def _layer(params, row=2):
    return jax.tree.map(lambda w: w[row], params["layers"])


@pytest.mark.parametrize("first", [0, 4, 8, 12])
def test_the_expert_layer_computes_its_share(fam, first):
    """Experts ``first ... first + 4`` of 16 held: the program's sorted,
    grouped layer is the reference's sum over the held experts."""
    cfg = _cfg(first_expert=first)
    lp = _layer(moe.init_params(jax.random.PRNGKey(1), cfg))
    x = _rows()
    with jax.default_matmul_precision("highest"):
        got, _ = moe.serve_block(x, lp, cfg)
        want = fam.layer_share(x, lp, cfg)
    np.testing.assert_allclose(got, want, atol=2e-5)


def _latent_cfg(**kw):
    """The latent-attention family's tiny model
    (tests/test_zz_latent_serving.py): softmax scores, 4 of 16 held."""
    base = dict(vocab_size=256, dim=64, n_layers=3, n_heads=4, n_kv_heads=4,
                head_size=32, ffn_dim=32, n_experts=16, experts_per_token=4,
                experts_held=4, first_expert=4, q_lora_rank=24,
                kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=16,
                v_head_dim=32, rope_factor=4.0, rope_original_len=16,
                dtype="float32", attn_impl="reference",
                gmm_impl="ragged_dot")
    base.update(kw)
    return moe.mistral_small_4_119b(**base)


# family of the reference -> the program's tiny config of it
SHARED = {"exaone_moe": _cfg, "mistral4": _latent_cfg}


@pytest.mark.parametrize("family", sorted(SHARED))
def test_the_shares_add_up_to_the_uncut_layer(family):
    """The guide's share test: the four shares' routed parts (experts
    0-4, 4-8, 8-12, 12-16) plus the shared expert counted once are the
    uncut reference's layer, in the program and in the reference; for
    the sigmoid-scored family with its selection bias and for the
    softmax-scored one."""
    sys.path.insert(0, BENCH)
    try:
        from harness import spec
        fam = spec.family(family)
    finally:
        sys.path.remove(BENCH)
    make = SHARED[family]
    whole = make(experts_held=0, first_expert=0)
    lp = _layer(moe.init_params(jax.random.PRNGKey(2), whole))
    x = _rows()
    with jax.default_matmul_precision("highest"):
        want = fam.layer_share(x, lp, whole)        # all 16 experts
        shared = fam.layer_share(x, lp, whole) - fam.routed_share(
            x, lp, whole)
        program, reference = shared, shared
        for first in range(0, 16, 4):
            cfg = make(first_expert=first)
            mine = {**lp, **{k: lp[k][first:first + 4]
                             for k in ("w_gate", "w_up", "w_down")}}
            program = program + moe.serve_block(x, mine, cfg)[0] - shared
            reference = reference + fam.routed_share(x, mine, cfg)
    np.testing.assert_allclose(reference, want, atol=2e-5)
    np.testing.assert_allclose(program, want, atol=5e-5)


def test_the_layer_counts_what_it_routed():
    cfg = _cfg()
    lp = _layer(moe.init_params(jax.random.PRNGKey(1), cfg))
    active = jnp.arange(24) < 10
    _, stats = moe.serve_block(_rows(), lp, cfg, active=active)
    assert int(stats["routed"]) == 10 * cfg.experts_per_token
    assert 0 <= int(stats["local"]) <= int(stats["routed"])
    assert 0 <= int(stats["experts_hit"]) <= cfg.n_held


@pytest.mark.parametrize("path", ["ragged_dot", "kernel_stack"])
def test_the_layer_groups_the_live_rows_alone(path):
    """PR 57. With ``active`` the rows that are no live request reach no
    expert: ``experts_hit``, ``local`` and ``routed`` are those of the
    live rows' assignments (what the grouped matmuls get as groups), a
    live row's output is, bit for bit, the one the layer gave when every
    row was grouped, and an idle row keeps the shared expert's part
    alone. Without ``active`` (prefill, verify) nothing is masked: the
    layer is the reference's share, as before."""
    cfg = _cfg(**({} if path == "ragged_dot"
                  else {"gmm_impl": "pallas_interpret"}))
    params = moe.init_params(jax.random.PRNGKey(1), cfg)
    lp, x = _layer(params), _rows()
    kw = {} if path == "ragged_dot" else {"stack": params["layers"],
                                          "row": 2}
    active = (jnp.arange(24) % 12) == 5                      # 2 live rows
    every, none = moe.serve_block(x, lp, cfg, **kw)
    got, stats = moe.serve_block(x, lp, cfg, active=active, **kw)
    assert none is None
    np.testing.assert_array_equal(got[active], every[active])
    np.testing.assert_array_equal(got[~active],
                                  moe._shared(x, lp)[~active])
    _, experts, _ = moe._route(x, lp["router"], lp.get("router_bias"), cfg)
    mine = np.asarray(experts) - cfg.first_expert
    held = (mine >= 0) & (mine < cfg.n_held)
    live = held & np.asarray(active)[:, None]
    assert int(stats["routed"]) == 2 * cfg.experts_per_token
    assert int(stats["local"]) == live.sum() > 0
    assert int(stats["experts_hit"]) == len(set(mine[live]))
    # the idle rows alone reach held experts the live ones do not: the
    # counts above are not those of every row
    assert len(set(mine[held])) > len(set(mine[live]))
    _, all_live = moe.serve_block(x, lp, cfg, active=jnp.ones(24, bool),
                                  **kw)
    assert int(all_live["local"]) == held.sum()
    assert int(all_live["experts_hit"]) == len(set(mine[held]))


@pytest.mark.parametrize("post_norm, embed_rms, attn_norm",
                         [(True, 1.0, 0.25), (False, 64 ** -0.5, 1.0)])
def test_a_post_norm_stream_is_made_token_specific(params, post_norm,
                                                   embed_rms, attn_norm):
    """Random weights of a post-norm model: the embedding at unit RMS and
    the attention's norm at 0.25, so that a token's own row, not the mean
    of its context, is most of what the router reads; a pre-norm model
    keeps the fan-in scaled embedding and norms of 1."""
    if not post_norm:
        params = moe.init_params(jax.random.PRNGKey(0),
                                 _cfg(post_norm=False))
    rms = float(jnp.sqrt(jnp.mean(params["embed"] ** 2)))
    assert abs(rms / embed_rms - 1) < 0.02
    for stack in ("dense_layers", "layers"):
        np.testing.assert_array_equal(params[stack]["attn_norm"], attn_norm)
        np.testing.assert_array_equal(params[stack]["mlp_norm"], 1.0)


def test_the_train_forward_refuses_serving_only_shapes(params):
    with pytest.raises(NotImplementedError):
        moe.forward(params, jnp.zeros((1, 8), jnp.int32), _cfg())


def _engine(params, **kw):
    from ray_tpu.llm.engine import LLMEngine
    return LLMEngine(_cfg(), params, max_slots=4, max_len=256,
                     prefill_buckets=(64, 128), cache_dtype="float32",
                     kv_block_size=8, steps_per_sync=4, **kw)


def test_the_engine_serves_it_and_frees_what_the_window_passed(fam, params):
    """Through LLMEngine: greedy tokens equal the reference's (float32
    pool; 20 tokens, prompts below and past the window), a window layer
    never holds more than its ring of blocks a sequence, frees blocks
    while requests decode, and gives everything back."""
    from ray_tpu.llm.engine import engine_metrics

    async def run():
        eng = _engine(params)
        rng = np.random.default_rng(0)
        prompts = [[int(t) for t in rng.integers(1, 256, n)]
                   for n in (20, 100, 70)]
        outs = await asyncio.gather(*[
            eng.generate(p, max_new_tokens=20) for p in prompts])
        stats = eng.stats
        await eng.stop()
        return prompts, outs, stats

    before = _sums("kv_blocks_window", "kv_window_freed", "moe_routed",
                   "moe_local", "moe_experts_hit", "moe_experts_held")
    prompts, outs, stats = asyncio.run(run())
    for p, o in zip(prompts, outs):
        # one forward over prompt + reply: each reply token is the
        # reference's greedy choice given everything before it
        logits = np.asarray(fam.forward(
            params, jnp.asarray([p + o["tokens"]], jnp.int32), _cfg()))[0]
        want = np.argmax(logits[len(p) - 1:-1], axis=-1)
        assert o["tokens"] == [int(t) for t in want]
    ring = kc.window_ring_blocks(32, 8, 4)
    assert stats["pool_blocks_window"] == 4 * ring + 1
    assert stats["blocks_used_window"] == 0 and stats["blocks_used"] == 0
    assert stats["window_blocks_freed"] >= 4
    after = _sums("kv_blocks_window", "kv_window_freed", "moe_routed",
                  "moe_local", "moe_experts_hit", "moe_experts_held")
    d = {k: after[k] - before[k] for k in after}
    assert d["kv_window_freed"] == stats["window_blocks_freed"]
    assert 0 < d["moe_local"] < d["moe_routed"]
    assert 0 < d["moe_experts_hit"] <= d["moe_experts_held"]
    assert engine_metrics()["kv_blocks_window"] is not None


@pytest.mark.parametrize("stagger_s", [0.0, 0.01])
def test_window_rings_with_a_block_in_flight(fam, params, stagger_s):
    """PR 39: the engine enqueues block n + 1 before it reads back
    block n, so a window layer's ring is advanced (blocks given back,
    the next ones taken) for a block whose predecessor still runs, and
    a request that ends on an eos mid-block rides out one more block
    with its ring held. Five requests through four slots, prompts below
    and past the window, budgets that are no power of two, one ended by
    an eos inside a block: every reply is the reference's greedy
    choice, and every block of both pools comes back."""
    from ray_tpu.llm.engine import engine_metrics
    rng = np.random.default_rng(1)
    prompts = [[int(t) for t in rng.integers(1, 256, n)]
               for n in (20, 100, 70, 33, 50)]
    news = (21, 13, 27, 19, 11)

    def reference(p, o):
        logits = np.asarray(fam.forward(
            params, jnp.asarray([p + o], jnp.int32), _cfg()))[0]
        return [int(t) for t in np.argmax(logits[len(p) - 1:-1], axis=-1)]

    def ahead():
        h = engine_metrics()["decode_ahead"]
        return (sum(h._sums.values()),
                sum(sum(c) for c in h._counts.values()))

    async def one(eng, i, p, kw):
        await asyncio.sleep(i * stagger_s)
        return await eng.generate(p, **kw)

    async def run(kws):
        eng = _engine(params)
        s0, n0 = ahead()
        outs = await asyncio.gather(*[
            one(eng, i, p, kw) for i, (p, kw) in enumerate(zip(prompts, kws))])
        for _ in range(200):        # the block that rode out an eos
            if eng._inflight is None:
                break
            await asyncio.sleep(0.005)
        stats = eng.stats
        await eng.stop()
        s1, n1 = ahead()
        return [o["tokens"] for o in outs], stats, s1 - s0, n1 - n0

    plain, stats, went_ahead, blocks = asyncio.run(
        run([{"max_new_tokens": n} for n in news]))
    for p, o in zip(prompts, plain):
        assert o == reference(p, o)
    assert went_ahead / blocks > 0.5, (went_ahead, blocks)
    assert stats["blocks_used_window"] == 0 and stats["blocks_used"] == 0
    assert stats["window_blocks_freed"] >= 4
    # the third request again, ended by an eos inside a block of four
    # (reply index 1-4 is the first block, 5-8 the second, ...)
    o = plain[2]
    at = next(i for i in range(2, len(o)) if i % 4 and o.index(o[i]) == i)
    kws = [{"max_new_tokens": n} for n in news]
    kws[2]["eos_id"] = o[at]
    cut, stats, _, _ = asyncio.run(run(kws))
    assert cut[2] == o[:at + 1]
    assert [c for i, c in enumerate(cut) if i != 2] == \
        [c for i, c in enumerate(plain) if i != 2]
    assert stats["blocks_used_window"] == 0 and stats["blocks_used"] == 0


def _sums(*keys):
    from ray_tpu.llm.engine import engine_metrics
    out = {}
    for key in keys:
        h = engine_metrics()[key]
        out[key] = sum(float(ln.rsplit(" ", 1)[1])
                       for ln in h.render().splitlines()
                       if ln.startswith(h.name + "_sum"))
    return out


def test_prefix_reuse_and_speculation_are_refused_at_start(params):
    with pytest.raises(ValueError, match="prefix"):
        _engine(params, prefix_cache=True)
    with pytest.raises(ValueError, match="speculative"):
        _engine(params, spec=True)


def test_load_model_makes_the_familys_parameters():
    from ray_tpu.serve.llm import LLMConfig, _load_model
    cfg = _cfg(dtype="bfloat16")
    got_cfg, params = _load_model(LLMConfig(model=cfg, seed=3))
    assert got_cfg is cfg
    assert params["layers"]["w_gate"].shape == (8, 4, 64, 64)
    assert params["layers"]["w_gate"].dtype == jnp.bfloat16
    assert params["layers"]["router"].shape == (8, 64, 16)   # all 16 scored
    assert params["dense_layers"]["w_gate"].shape == (1, 64, 128)
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n == cfg.num_params()


# --- the projections' products are fenced from the reshape into heads --------
# (PR 36: tests/test_aot_tpu_compile.py holds what that does to the compiled
# programs; here: it moves no number)

def _fence_model(name):
    from ray_tpu.models import llama
    if name == "llama":
        cfg = llama.tiny(dtype="float32", attn_impl="reference")
        return cfg, llama.init_params(jax.random.PRNGKey(2), cfg)
    cfg = (_cfg() if name == "qk_head_norm" else moe.tiny(
        n_experts=8, experts_per_token=2, norm_topk_prob=False, qk_norm=True,
        dtype="float32", attn_impl="reference"))
    return cfg, moe.init_params(jax.random.PRNGKey(2), cfg)


def _fence_logits(forward, cfg, params, mesh):
    """One forward's logits through a jit of its own (so that what is
    traced is ``lm._qkv`` as it stands now): a 64-token prompt, or 4
    slots mid-request over pools of random rows."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    rng = np.random.default_rng(7)
    if forward == "prefill":
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, 64), jnp.int32)
        return jax.jit(lambda p: lm.prefill.__wrapped__(
            p, tokens, jnp.int32(61), cfg, 64)[0])(params)
    slots, width, bs = 4, 8, 8
    pool = kc.init_pool(cfg, slots * width + 1, bs, jnp.float32,
                        window_blocks=slots * width + 1)
    pool = {k: jnp.asarray(rng.standard_normal(a.shape), a.dtype)
            for k, a in pool.items()}
    if mesh is not None:
        pool = jax.device_put(pool, NamedSharding(
            mesh, P(None, None, "tensor", None, None)))
    table = jnp.asarray(
        1 + np.arange(slots * width).reshape(slots, width), jnp.int32)
    tables = {kind: table for kind in kc.kind_block_bytes(pool)}
    lengths = jnp.asarray([3, 17, 40, 55], jnp.int32)
    kw = dict(mesh=mesh, axis="tensor")
    if forward == "verify":
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (slots, 3)),
                             jnp.int32)
        return jax.jit(lambda p, pool: kc._paged_verify_core(
            p, pool, tables, lengths, tokens, cfg, impl="gather",
            **kw)[0])(params, pool)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, slots), jnp.int32)
    impl = {"decode_gather": "gather", "decode_kernel": "paged_flash"}[forward]
    return jax.jit(lambda p, pool: kc._paged_logits_core(
        p, pool, tables, lengths, tokens, cfg, impl=impl, interpret=True,
        **kw)[0])(params, pool)


_FENCE_CASES = [
    (model, forward, placed)
    for model, placements in (("llama", ("one_device", "tensor_mesh")),
                              ("qk_head_norm", ("one_device",)),
                              ("qk_norm", ("one_device",)))
    for forward in ("decode_gather", "decode_kernel", "prefill", "verify")
    for placed in placements
    # the verify forward attends global layers only
    if (model, forward) != ("qk_head_norm", "verify")]


@pytest.mark.parametrize("model, forward, placed", _FENCE_CASES)
def test_the_fenced_projections_move_no_number(monkeypatch, model, forward,
                                               placed):
    """Prefill, a decode step (the XLA reference and the interpreted
    kernels) and a verify round, for a Llama model, one with q/k-norm
    over the whole width (OLMoE) and one with a norm a head (the hybrid
    model above), on one device and tensor-parallel over two: the
    logits with the products fenced are the logits without."""
    cfg, params = _fence_model(model)
    mesh = None
    if placed == "tensor_mesh":
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("tensor",))
        params = lm.shard_params_for_serving(params, mesh, cfg)
    got = np.asarray(_fence_logits(forward, cfg, params, mesh))
    # ``_qkv`` as it was: the product, then the reshape
    monkeypatch.setattr(lm.lax, "optimization_barrier", lambda x: x)
    want = np.asarray(_fence_logits(forward, cfg, params, mesh))
    assert np.isfinite(want).all() and np.abs(want).max() > 1e-3
    assert np.linalg.norm(got - want) <= 1e-6 * np.linalg.norm(want)


# --- one representation by layer kind (PR 50) --------------------------------
# block tables, write targets and physical ids are dicts by kind from the
# block manager to the kernel for EVERY family; the public entries also take
# the bare array their callers outside the engine hand them

FAMILIES = ("llama", "hybrid")


def _family(name):
    """(cfg, params) of a family's tiny model: the Llama family (global
    layers only) or the hybrid above (window and global layers)."""
    if name == "hybrid":
        cfg = _cfg()
        return cfg, moe.init_params(jax.random.PRNGKey(0), cfg)
    from ray_tpu.models import llama
    cfg = llama.tiny(vocab_size=128, dim=64, n_layers=3, n_heads=4,
                     n_kv_heads=2, ffn_dim=128, dtype="float32",
                     logits_dtype="float32", attn_impl="reference")
    return cfg, llama.init_params(jax.random.PRNGKey(0), cfg)


def _random_pool(cfg, blocks=12, bs=8, seed=0):
    rng = np.random.default_rng(seed)
    pool = kc.init_pool(cfg, blocks, bs, jnp.float32, window_blocks=blocks)
    return {k: jnp.asarray(rng.standard_normal(a.shape), a.dtype)
            for k, a in pool.items()}


def _spellings(cfg, ids: dict):
    """Every way a caller may hand ``ids`` (by kind) and the layout to
    scatter_bucket / gather_table / scatter_table."""
    kinds = kc.pool_kinds(cfg)
    if len(ids) > 1:
        return [(ids, (kinds,))]
    bare = ids[kc.GLOBAL]
    return [(bare, ()), (bare, (kinds,)), (ids, ()), (ids, (kinds,))]


def _blocks(x, nb, bs):
    """Token-order (layers, nb * bs, kvh, hd) as pool blocks."""
    L, _, kvh, hd = x.shape
    return x.reshape(L, nb, bs, kvh, hd).transpose(0, 1, 3, 2, 4)


@pytest.mark.parametrize("op", ["scatter_bucket", "gather_table",
                                "scatter_table"])
@pytest.mark.parametrize("family", FAMILIES)
def test_the_cache_ops_take_every_spelling_of_ids_by_kind(family, op):
    """A bare array, ``{GLOBAL: array}``, with or without the trailing
    layout: the same pool (or accumulator) bit for bit, equal to a numpy
    statement of the op by kind, through ONE compiled callable."""
    cfg, _ = _family(family)
    bs, nb, kvh, hd = 8, 3, cfg.n_kv_heads, cfg.head_dim
    rng = np.random.default_rng(1)
    ids = {kind: jnp.asarray(rng.permutation(np.arange(1, 12))[:nb],
                             jnp.int32)
           for kind, _ in kc.pool_kinds(cfg)}
    kv = {k: jnp.asarray(rng.standard_normal(
        (cfg.n_layers, nb * bs, kvh, hd)), jnp.float32) for k in "kv"}
    start = {k: np.asarray(a) for k, a in _random_pool(cfg).items()}
    want = {k: a.copy() for k, a in start.items()}
    if op == "gather_table":
        want = {k: np.zeros((cfg.n_layers, 40, kvh, hd), np.float32)
                for k in "kv"}
    for kind, layers in kc.pool_kinds(cfg):
        for src, dst in zip("kv", kc.POOL_KEYS[kind]):
            phys = np.asarray(ids[kind])
            if op == "gather_table":
                g = start[dst][:, phys].transpose(0, 1, 3, 2, 4)
                want[src][list(layers), :nb * bs] = g.reshape(
                    len(layers), nb * bs, kvh, hd)
            else:
                want[dst][:, phys] = _blocks(
                    np.asarray(kv[src])[list(layers)], nb, bs)
    kc._JITS.clear()
    for given, layout in _spellings(cfg, ids):
        pool = {k: jnp.asarray(a) for k, a in start.items()}
        if op == "scatter_bucket":
            got = kc.scatter_bucket(pool, kv, given, nb, *layout)
        elif op == "gather_table":
            got = kc.gather_table(pool, given, 40, *layout)
        else:
            got = kc.scatter_table(pool, kv, given, *layout)
        assert sorted(got) == sorted(want)
        for k in want:
            np.testing.assert_array_equal(np.asarray(got[k]), want[k])
    # a bucket's write is scatter_table's program at the bucket's width
    assert [k[0] for k in kc._JITS] == [op.replace("bucket", "table")]


def test_a_layout_that_is_not_the_pools_is_refused():
    llama_cfg, _ = _family("llama")
    hybrid_cfg, _ = _family("hybrid")
    ids = jnp.asarray([1, 2], jnp.int32)
    both = {kc.GLOBAL: ids, kc.WINDOW: ids}
    with pytest.raises(ValueError, match="pool_kinds"):
        # two kinds: the pool alone does not say which layers are whose
        kc.gather_table(_random_pool(hybrid_cfg), both, 16)
    with pytest.raises(ValueError, match="pool_kinds"):
        kc.gather_table(_random_pool(llama_cfg), ids, 16,
                        kc.pool_kinds(hybrid_cfg))


@pytest.mark.parametrize("entry", ["paged_decode_logits",
                                   "paged_decode_steps",
                                   "paged_verify_steps"])
def test_the_paged_forwards_take_a_bare_table_or_a_dict(entry):
    """The Llama family's callers outside the engine hand the tables as
    one array: bitwise what ``{GLOBAL: tables}`` gives, one `_JITS`
    entry."""
    cfg, params = _family("llama")
    slots, width = 3, 4
    table = jnp.asarray(
        1 + np.arange(slots * width).reshape(slots, width), jnp.int32)
    lengths = jnp.asarray([3, 9, 20], jnp.int32)
    tokens = jnp.asarray([5, 6, 7], jnp.int32)

    def run(tables):
        pool = _random_pool(cfg, blocks=slots * width + 1)
        if entry == "paged_decode_logits":
            return kc.paged_decode_logits(params, pool, tables, lengths,
                                          tokens, cfg)
        if entry == "paged_decode_steps":
            return kc.paged_decode_steps(
                params, pool, tables, lengths, tokens,
                jnp.zeros((slots,), jnp.float32), jax.random.PRNGKey(0),
                cfg, 2)
        return kc.paged_verify_steps(
            params, pool, tables, lengths,
            jnp.stack([tokens, tokens + 1], axis=1), cfg)

    kc._JITS.clear()
    bare, by_kind = run(table), run({kc.GLOBAL: table})
    assert jax.tree.structure(bare) == jax.tree.structure(by_kind)
    for a, b in zip(jax.tree.leaves(bare), jax.tree.leaves(by_kind)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert len(kc._JITS) == 1, list(kc._JITS)


@pytest.mark.parametrize("op, gathers", [("scatter_table", 0),
                                         ("gather_table", 2)])
def test_a_model_of_one_kind_is_never_gathered_or_stacked(op, gathers):
    """The lowered text of the Llama family's op: the only gathers are
    gather_table's own, over a pool's BLOCKS (k and v); no layer is
    taken out of the KV, nothing is concatenated or sorted back. (The
    hybrid's text has them: the control.)"""
    def text(family):
        cfg, _ = _family(family)
        pool = _random_pool(cfg)
        ids = {kind: jnp.asarray([1, 2, 3], jnp.int32)
               for kind, _ in kc.pool_kinds(cfg)}
        kinds = kc.pool_kinds(cfg)
        if op == "gather_table":
            return kc._jit(op, pool, kinds).lower(pool, ids, 32).as_text()
        kv = {k: jnp.zeros((cfg.n_layers, 24, cfg.n_kv_heads,
                            cfg.head_dim), jnp.float32) for k in "kv"}
        return kc._jit(op, pool, kinds).lower(pool, kv, ids).as_text()

    def count(text):
        return text.count('"stablehlo.gather"(')

    one = text("llama")
    assert count(one) == gathers
    assert "stablehlo.concatenate" not in one and "stablehlo.sort" not in one
    two = text("hybrid")
    assert count(two) > 2 * gathers


@pytest.mark.parametrize("family", FAMILIES)
def test_the_layout_is_said_once(family):
    """pool_kinds is ``((kind, its layers), ...)`` for every family, and
    the pool, its block bytes and the ops' default agree with it."""
    cfg, _ = _family(family)
    kinds = kc.pool_kinds(cfg)
    assert kinds == tuple(lm.kind_layers(cfg).items())
    assert kinds[0][0] == kc.GLOBAL
    assert sorted(l for _, ls in kinds for l in ls) == list(
        range(cfg.n_layers))
    if family == "llama":
        assert kinds == ((kc.GLOBAL, tuple(range(cfg.n_layers))),)
    pool = kc.init_pool(cfg, 9, 8, jnp.float32, window_blocks=5)
    assert sorted(pool) == sorted(
        key for kind, _ in kinds for key in kc.POOL_KEYS[kind])
    for kind, layers in kinds:
        for key in kc.POOL_KEYS[kind]:
            assert pool[key].shape == (
                len(layers), 9 if kind == kc.GLOBAL else 5,
                cfg.n_kv_heads, 8, cfg.head_dim)
    per_layer = 2 * cfg.n_kv_heads * 8 * cfg.head_dim * 4
    assert kc.kind_block_bytes(pool) == {
        kind: len(layers) * per_layer for kind, layers in kinds}
    assert kc._layout(pool, kinds) == kinds
    if len(kinds) == 1:
        assert kc._layout(pool) == kinds


@pytest.mark.parametrize("family", FAMILIES)
def test_an_engine_reports_tables_and_stats_by_kind(family):
    """Either family through the same code: the engine's tables are a
    dict by the layout's kinds, and what `stats` says is used, by kind,
    is what the manager holds and what the tables name: after an
    admit, with a decode block in flight, and after the release."""
    from ray_tpu.llm.engine import LLMEngine
    cfg, params = _family(family)
    kinds = [kind for kind, _ in kc.pool_kinds(cfg)]
    name = {kind: "blocks_used" + ("" if kind == kc.GLOBAL else "_" + kind)
            for kind in kinds}

    def held(eng):
        stats = eng.stats
        used = eng._kv.used_by_kind()
        assert list(used) == list(eng._tables) == kinds
        assert {kind: stats[name[kind]] for kind in kinds} == used
        assert used[kc.GLOBAL] == eng._kv.used_blocks()
        assert sum(used.values()) == eng._kv.used_blocks() \
            + eng._kv.window_used_blocks()
        # no block is shared here: the tables name each block once
        return used, {kind: int(np.count_nonzero(t))
                      for kind, t in eng._tables.items()}

    async def run():
        eng = LLMEngine(cfg, params, max_slots=2, max_len=128,
                        prefill_buckets=(64,), cache_dtype="float32",
                        kv_block_size=8, steps_per_sync=4,
                        prefix_cache=False)
        assert held(eng)[0] == dict.fromkeys(kinds, 0)
        rng = np.random.default_rng(3)
        prompt = [int(t) for t in rng.integers(1, cfg.vocab_size, 50)]
        task = asyncio.ensure_future(
            eng.generate(prompt, max_new_tokens=24))
        seen = []
        while not task.done():
            if eng._inflight is not None:
                seen.append(held(eng))
            await asyncio.sleep(0)
        await task
        for _ in range(200):
            if eng._inflight is None:
                break
            await asyncio.sleep(0.005)
        after = held(eng)
        stats = eng.stats
        await eng.stop()
        return seen, after, stats

    seen, after, stats = asyncio.run(run())
    assert seen, "no decode block was ever in flight"
    for used, named in seen:
        # the full horizon of the global layers, a ring of a window layer
        assert used[kc.GLOBAL] == -(-(50 + 24) // 8)
        assert used == named
    assert after == (dict.fromkeys(kinds, 0), dict.fromkeys(kinds, 0))
    assert stats["pool_blocks"] == stats["blocks_free"] + 1
    assert sorted(k for k in stats if k.endswith("_blocks_freed")) == [
        kind + "_blocks_freed" for kind in kinds if kind != kc.GLOBAL]
