"""A model whose residual is a stream of four copies a position, mixed by
manifold-constrained hyper-connections (mHC) around latent attention, a
dense lead and sigmoid-routed experts (Xing4.0-29B-A4B), through the
serving forwards, the latent pool and the engine, against the plain
reference benchmarks/families/xing4.py at tiny widths in float32. The
rotary's original length is 16 and its factor 4, so YaRN's ramp is
crossed inside every prompt; a head's keys are 48 wide and its values 32,
as the model's 192 and 128. The forwards run 6 Sinkhorn iterations (a
third of the unrolled chain to compile; program and reference read the
same config), the tests of the mixing itself the model's 20."""
import asyncio
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import kvcache as kc
from ray_tpu.llm import model as lm
from ray_tpu.models import moe

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
BENCH = os.path.join(ROOT, "benchmarks")
CELL = "serve-xing4-rag-open"
CONFIG = "xing4.0-29b-a4b-serve-pp8"
PAIRS = [[2048, 256], [3072, 384], [4096, 512], [5120, 640], [6144, 768],
         [7168, 128], [8192, 256], [2048, 512], [3072, 640], [4096, 768],
         [5120, 128], [6144, 384]]


@pytest.fixture(scope="module")
def spec():
    sys.path.insert(0, BENCH)
    try:
        from harness import spec
        yield spec
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def fam(spec):
    """benchmarks/families/xing4.py: the plain reference."""
    return spec.family("xing4")


def _cfg(**kw):
    base = dict(vocab_size=256, dim=64, n_layers=3, n_heads=4, n_kv_heads=4,
                head_size=32, ffn_dim=32, n_experts=8, experts_per_token=2,
                n_dense_layers=1, dense_ffn_dim=128, q_lora_rank=24,
                kv_lora_rank=16, qk_nope_head_dim=32, qk_rope_head_dim=16,
                v_head_dim=32, rope_factor=4.0, rope_original_len=16,
                hc_sinkhorn_iters=6, max_seq_len=512, dtype="float32",
                attn_impl="reference", gmm_impl="ragged_dot")
    base.update(kw)
    return moe.xing4_0_29b_a4b(**base)


@pytest.fixture(scope="module")
def params():
    return moe.init_params(jax.random.PRNGKey(0), _cfg())


def _prompt(n, seed=0, vocab=256):
    rng = np.random.default_rng(seed)
    return [int(t) for t in rng.integers(1, vocab, n)]


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


# --- the forwards against the reference --------------------------------------

def test_cold_prefill_is_the_reference(fam, params):
    cfg, toks = _cfg(), _prompt(40)
    want = fam.forward(params, jnp.asarray([toks]), cfg)[0]
    for n in (17, 40):      # past the original 16 positions both times
        got, kv = lm.prefill(params, jnp.asarray(lm.pad_prompt(toks[:n], 64)),
                             jnp.int32(n), cfg, 64)
        assert _rel(got, want[n - 1]) < 1e-5
    # the cache is one latent row a token a layer: the mixing keeps no
    # state between positions
    assert kv["k"].shape == (3, 64, 16) and kv["v"].shape == (3, 64, 16)


@pytest.mark.parametrize("kv_impl, interpret", [("gather", False),
                                                ("paged_flash", True)])
def test_chunked_prefill_and_decode_are_the_reference(fam, params, kv_impl,
                                                      interpret):
    """``served``: a 40-token prompt in chunks of 16 (two chunk edges, the
    last chunk once a prefix), its rows through a latent pool, 16 decode
    steps in the absorbed form across two block edges."""
    cfg, toks = _cfg(), _prompt(40, seed=1)
    got = fam.served(params, cfg, toks, buckets=(8, 16), block=8,
                     kv_impl=kv_impl, interpret=interpret,
                     cache_dtype="float32")
    assert len(got["prefills"]) == 8 and len(got["steps"]) == 16
    out = fam.compared(got, params, cfg, 40)
    assert out["finite"]
    assert max(out["prefill_rel_errs"]) < 2e-5, out["prefill_rel_errs"]
    assert max(out["decode_rel_errs"]) < 2e-5, out["decode_rel_errs"]
    assert got["rows"].shape == (3, 56, 32)
    assert np.max(out["rows_rel_errs"]) < 2e-6, out["rows_rel_errs"]
    assert out["coeff_rel_err"] < 2e-6
    assert out["prefill_rel_err"] == max(
        out["prefill_logits_rel_err"],
        fam.ROWS_WEIGHT * out["prefill_rows_rel_err"],
        fam.STREAM_WEIGHT * out["prefill_stream_rel_err"],
        fam.COEFF_WEIGHT * out["coeff_rel_err"])
    # every position's row in the first layer with a router (layer 1)
    assert out["stream_layer"] == 1
    assert len(out["decode_stream_rel_errs"]) == 16
    assert max(out["prefill_stream_rel_err"],
               out["decode_stream_rel_err"]) < 2e-6
    if kv_impl == "gather":
        # the coefficients' own comparison sees their precision: computed
        # in bfloat16 they read a thousand times the sound path's, over
        # the limit a tolerance of 0.03 gives them
        low = fam.compared(got, params, cfg, 40, ("coeff_bfloat16",))
        assert low["coeff_rel_err"] > 1e-3 > 0.03 / fam.COEFF_WEIGHT
        assert low["prefill_rel_err"] \
            == fam.COEFF_WEIGHT * low["coeff_rel_err"] > 0.1
        # a fault that starts at the reply's ninth step: the prompt's set
        # and the first eight steps read sound, a set's lower quartile
        # passes it, and the late positions' own rows do not
        late = fam.compared(got, params, cfg, 40, fam.LATE_FAULTS)
        assert fam.LATE_FROM == 8
        for key in ("prefill_rel_errs", "prefill_stream_rel_err",
                    "prefill_rows_rel_err"):
            assert late[key] == out[key]
        assert max(late["decode_rel_errs"][:8]) < 2e-5
        assert min(late["decode_rel_errs"][8:]) > 1e-2
        assert late["decode_quartile_rel_err"] < 2e-5
        assert max(late["decode_stream_rel_errs"][:8]) < 2e-6
        assert min(late["decode_stream_rel_errs"][8:]) > 1e-2
        assert late["decode_rel_err"] \
            == fam.STREAM_WEIGHT * late["decode_stream_rel_err"] > 0.02


def test_the_flash_chunk_takes_keys_and_values_of_two_widths(fam, params):
    """The flash path of a chunk (static offset) with keys of 48 and
    values of 32: the dynamic path's logits, and the reference's."""
    cfg, flash = _cfg(), _cfg(attn_impl="flash_interpret")
    toks = _prompt(256, seed=2)

    def run(c):
        acc = {"k": jnp.zeros((3, 384, 16)), "v": jnp.zeros((3, 384, 16))}
        for off in (0, 128):
            logits, acc = lm.prefill_chunk(
                params, jnp.asarray(toks[off:off + 128], jnp.int32),
                jnp.int32(128), jnp.int32(off), acc, c)
        return logits
    want, got = run(cfg), run(flash)
    assert _rel(got, want) < 1e-5
    ref = fam.forward(params, jnp.asarray([toks]), cfg)[0, -1]
    assert _rel(got, ref) < 1e-4


def test_a_verify_round_is_sequential_decode(fam, params):
    """Four tokens a slot in one verify forward: each row is the decode
    step's at that position, and the reference's full forward's."""
    cfg, toks = _cfg(), _prompt(44, seed=4)
    _, kv = lm.prefill(params, jnp.asarray(lm.pad_prompt(toks[:40], 64)),
                       jnp.int32(40), cfg, 64)
    table = jnp.arange(1, 9, dtype=jnp.int32)

    def pool():
        return kc.scatter_bucket(kc.init_pool(cfg, 12, 8, jnp.float32), kv,
                                 table, 8, kc.pool_kinds(cfg))
    at = jnp.asarray([40], jnp.int32)
    got, _ = kc.paged_verify_steps(
        params, pool(), table[None], at, jnp.asarray([toks[40:44]]), cfg,
        impl="paged_flash", interpret=True)
    ref = fam.forward(params, jnp.asarray([toks]), cfg)[0]
    p = pool()
    for j in range(4):
        want = kc.paged_decode_logits(
            params, p, table[None], at + j, jnp.asarray([toks[40 + j]]),
            cfg)[0]
        np.testing.assert_allclose(got[0, j], want, atol=1e-5)
        assert _rel(got[0, j], ref[40 + j]) < 2e-5
        _, p = kc.paged_decode_steps(
            params, p, table[None], at + j, jnp.asarray([toks[40 + j]]),
            jnp.zeros((1,)), jax.random.PRNGKey(0), cfg, 1)


def test_the_engine_serves_it(fam, params):
    """Through LLMEngine, unchanged: a chunked prompt (three chunks of 32)
    and a short one; every greedy token is the reference's next token
    after the tokens before it."""
    from ray_tpu.llm.engine import LLMEngine
    cfg, toks = _cfg(), _prompt(70, seed=6)

    async def run():
        eng = LLMEngine(cfg, params, max_slots=2, max_len=128,
                        prefill_buckets=(16, 32), cache_dtype="float32",
                        kv_block_size=8, steps_per_sync=4)
        cold = await eng.generate(toks, max_new_tokens=6)
        short = await eng.generate(_prompt(20, seed=8), max_new_tokens=4)
        await eng.stop()
        return cold, short
    cold, short = asyncio.run(run())
    assert len(cold["tokens"]) == 6 and len(short["tokens"]) == 4
    ref = fam.forward(params, jnp.asarray([toks + cold["tokens"]]), cfg)[0]
    assert cold["tokens"] == [int(t) for t in np.argmax(ref[69:75], -1)]


# --- the mixing -----------------------------------------------------------------

def _stream(seed=0, s=12, n=4, d=64):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, 1, s, d),
                             jnp.float32)


def _layer0(params):
    return jax.tree.map(lambda w: w[0], params["layers"])


@pytest.mark.parametrize("sub", ["attn", "mlp"])
def test_hres_is_doubly_stochastic_and_moves_with_the_position(fam, params,
                                                               sub):
    cfg, lp = _cfg(hc_sinkhorn_iters=20), _layer0(params)
    x = _stream()
    pre, post, res = lm.mhc_coefficients(x, lp, sub, cfg)
    assert pre.shape == post.shape == (4, 1, 12) and res.shape == (4, 4, 1, 12)
    np.testing.assert_allclose(res.sum(1), 1.0, atol=1e-4)     # rows
    np.testing.assert_allclose(res.sum(0), 1.0, atol=1e-4)     # columns
    assert float(res.min()) > 0 and float(res.max()) < 1
    assert 0 < float(pre.min()) and float(pre.max()) < 1
    assert 0 < float(post.min()) and float(post.max()) < 2
    # neither the identity nor uniform, and another matrix a position
    assert float(jnp.abs(res - jnp.eye(4)[:, :, None, None]).max()) > 0.2
    assert float(jnp.abs(res - 0.25).max()) > 0.1
    assert float(jnp.abs(res[..., 0] - res[..., 1]).max()) > 0.05
    # the reference's coefficients, by its written loop
    X = jnp.moveaxis(x[:, 0], 0, 1)                             # (s, n, d)
    rpre, rpost, rres = fam.mixing(X, lp, sub, cfg)
    np.testing.assert_allclose(pre[:, 0].T, rpre, atol=1e-5)
    np.testing.assert_allclose(post[:, 0].T, rpost, atol=1e-5)
    np.testing.assert_allclose(jnp.moveaxis(res[:, :, 0], -1, 0), rres,
                               atol=1e-5)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_equal_rows_come_through_hres_unchanged(params, seed):
    """A stream whose four rows are equal (as after the copy-in) comes
    through Hres @ X unchanged, whatever the leaves: Hres' rows sum to 1."""
    cfg = _cfg(hc_sinkhorn_iters=20)
    lp = jax.tree.map(
        lambda w: w[0] + (3.0 * jax.random.normal(
            jax.random.PRNGKey(seed), w[0].shape, w.dtype)
            if w.dtype == jnp.float32 else 0), params["layers"])
    table = jax.random.normal(jax.random.PRNGKey(seed + 7), (9, 64))
    x = lm._embed({"embed": table}, jnp.arange(9)[None], cfg)   # the copy-in
    assert x.shape == (4, 1, 9, 64)
    _, post, res = lm.mhc_coefficients(x, lp, "attn", cfg)
    out = lm._mhc_write(x, res, jnp.zeros_like(post), jnp.zeros_like(x[0]))
    np.testing.assert_allclose(out, x, atol=1e-4)
    # and the sum-out of the copies is n times the embedding, normed
    got = lm._final_norm(x, {"final_norm": jnp.ones((64,))}, cfg)
    want = lm._rmsnorm(4 * table[None], jnp.ones((64,)), cfg.norm_eps)
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_sinkhorn_is_the_written_loop():
    m = jnp.exp(jax.random.normal(jax.random.PRNGKey(3), (4, 4, 5)) * 2)
    got = lm.mhc_sinkhorn(m, 20, 1e-6)
    want = np.moveaxis(np.asarray(m, np.float64), -1, 0)
    for _ in range(20):
        want = want / (want.sum(-2, keepdims=True) + 1e-6)
        want = want / (want.sum(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(np.moveaxis(np.asarray(got), -1, 0), want,
                               atol=1e-6)
    one = lm.mhc_sinkhorn(m, 1, 1e-6)
    assert float(jnp.abs(one.sum(0) - 1).max()) > 1e-2     # not yet there


def test_a_plain_stream_is_the_plain_add(params):
    """Without ``hc_mult`` the forwards carry (b, s, d) and add."""
    plain = moe.k_exaone_236b_a23b(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        head_size=16, ffn_dim=32, n_experts=4, experts_per_token=2,
        dense_ffn_dim=64, sliding_window=8, max_seq_len=128, dtype="float32",
        attn_impl="reference", gmm_impl="ragged_dot")
    assert lm._hc(plain) == 0 and lm._hc(_cfg()) == 4
    x = jnp.ones((1, 3, 64))
    table = jnp.ones((5, 64))
    assert lm._embed({"embed": table}, jnp.zeros((1, 3), jnp.int32),
                     plain).shape == (1, 3, 64)
    out, aux = lm._residual(x, {}, plain, "attn", lambda y: (2 * y, "aux"))
    np.testing.assert_array_equal(out, 3 * x)
    assert aux == "aux"


@pytest.mark.parametrize("fault", [
    "res_identity", "sinkhorn_1", "post_without_2", "static_coefficients",
    "coeff_bfloat16", "kr_left_out", "yarn_off", "rows_float8", "rows_int8"])
def test_each_fault_moves_the_reference(fam, params, fault):
    assert fault in fam.FAULTS
    cfg, toks = _cfg(), jnp.asarray([_prompt(40, seed=5)])
    want = fam.forward(params, toks, cfg)
    got = fam.forward(params, toks, cfg, faults=(fault,))
    assert _rel(got, want) > 2e-3, fault


# --- the parameters ---------------------------------------------------------------

def test_the_tree_holds_the_mixing_leaves_and_num_params_counts_them(params):
    cfg = _cfg()
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n == cfg.num_params()
    for stack, L in (("dense_layers", 1), ("layers", 2)):
        for sub in ("attn", "mlp"):
            assert params[stack][f"hc_{sub}_phi"].shape == (L, 4 * 64, 24)
            assert params[stack][f"hc_{sub}_phi"].dtype == jnp.float32
            assert params[stack][f"hc_{sub}_b"].shape == (L, 24)
            assert params[stack][f"hc_{sub}_a"].shape == (L, 3)
    # a model without the mixing has none, and its count is what it was
    plain = moe.mistral_small_4_119b(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=4,
        head_size=32, ffn_dim=32, n_experts=4, experts_per_token=2,
        q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=16, v_head_dim=32, dtype="float32")
    tree = jax.eval_shape(lambda: moe.init_params(jax.random.PRNGKey(0),
                                                  plain))
    assert not [k for k in tree["layers"] if k.startswith("hc_")]
    assert sum(x.size for x in jax.tree.leaves(tree)) == plain.num_params()
    # the published model: 29 B parameters, 4 B a token
    full = moe.xing4_0_29b_a4b()
    assert full.num_params() == pytest.approx(29.2e9, rel=0.02)
    assert full._mixing_params() == 2 * (14336 * 24 + 24 + 3)


def test_the_train_forward_refuses_the_mixed_stream(params):
    with pytest.raises(NotImplementedError, match="hyper-connections"):
        moe.forward(params, jnp.zeros((1, 8), jnp.int32), _cfg())
    # by the mechanism alone: a trainable shape plus hc_mult is refused
    assert moe._serving_only(moe.tiny(hc_mult=4))
    assert not moe._serving_only(moe.tiny())


# --- the cell -------------------------------------------------------------------

def test_the_configuration_is_the_published_one_but_for_the_cut(spec):
    cell = spec.cell(CELL)
    m = cell["model"]
    reduced = ["num_hidden_layers", "first_k_dense_replace"]
    entry = next(c for c in spec.benchmark()["configs"]
                 if c["name"] == CONFIG)
    assert cell["config"] == CONFIG and entry["reduced"] == reduced
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):
        row = next(r for r in map(json.loads, open(path))
                   if r["name"] == "Xing4.0-29B-A4B")
        assert sorted(k for k, v in row["config"].items()
                      if m.get(k) != v) == sorted(reduced)
        assert m["source"] == entry["source"] == row["source_url"]
        assert [m["source_" + k] for k in reduced] == [
            row["config"][k] for k in reduced]
    # the cut and the floors: a dense layer and five expert layers of 40,
    # every expert, the whole vocabulary; no width differs
    assert (m["num_hidden_layers"], m["first_k_dense_replace"],
            m["n_routed_experts"], m["vocab_size"]) == (6, 1, 64, 131072)
    assert m["num_hidden_layers"] - m["first_k_dense_replace"] >= 4
    assert (m["hidden_size"], m["num_attention_heads"], m["q_lora_rank"],
            m["kv_lora_rank"], m["qk_nope_head_dim"], m["qk_rope_head_dim"],
            m["v_head_dim"], m["moe_intermediate_size"],
            m["intermediate_size"], m["num_experts_per_tok"], m["hc_mult"],
            m["hc_sinkhorn_iters"]) == (3584, 32, 768, 512, 128, 64, 128,
                                        1024, 9216, 4, 4, 20)
    assert set(m["assumed"]) >= {
        "a_sinkhorn_order", "b_stream_norm", "c_copy_in_sum_out",
        "d_rope_pairs", "e_mscale", "f_dtype", "g_mtp", "row_padding",
        "weights"}
    assert "float32" in m["assumed"]["f_dtype"]
    assert "8 pipeline stages" in m["stands_for"] \
        and "9.59 GB" in m["stands_for"]
    dep = m["deployment"]
    assert (dep["kind"], dep["family"], dep["max_slots"], dep["max_len"],
            dep["parity_prompt_len"], dep["cache_dtype"]) == (
        "serve", "xing4", 32, 9216, 6144, "bfloat16")
    cfg = spec.family("xing4").config(m)
    assert isinstance(cfg, moe.MoEConfig)
    assert lm.layer_kinds(cfg) == ("latent",) * 6
    assert (cfg.n_experts, cfg.n_held, cfg.experts_per_token, cfg.scoring,
            cfg.routed_scaling, cfg.n_shared_experts, cfg.n_dense_layers,
            cfg.dense_ffn_dim) == (64, 64, 4, "sigmoid", 2.0, 1, 1, 9216)
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps,
            cfg.hc_res_clamp_min, cfg.hc_res_clamp_max) == (4, 20, 1e-6,
                                                            -30.0, 30.0)
    assert (cfg.rope_factor, cfg.rope_original_len, cfg.query_scale_beta) \
        == (64.0, 4096, 0.0)
    # the same widths as the preset of the published model
    pre = moe.xing4_0_29b_a4b(n_layers=6, n_dense_layers=1,
                              attn_block_q=512, attn_block_k=512)
    assert cfg == pre
    # the cache's row at this model's width, and the issue's arithmetic
    assert kc.row_bytes(cfg, kc.LATENT, dep["cache_dtype"]) == 1280
    assert cfg.num_params() == pytest.approx(4792.6e6, rel=1e-4)


def _reads(spec, cell) -> set:
    """What a cell's per-layer entries READ: (reader, arguments) of each
    entry's file, whatever the entry is called."""
    import json
    out = set()
    for m in cell["per_layer"]:
        mf = spec.metric_file(m["name"])
        out.add((mf["reader"], json.dumps(mf.get("args", {}),
                                          sort_keys=True)))
    return out


def _read_by(spec, names) -> set:
    """``_reads`` of the metric files ``names``."""
    return _reads(spec, {"per_layer": [{"name": n} for n in names]})


def test_the_cell_its_traffic_and_its_metrics_are_in_the_benchmark(spec):
    bench = spec.benchmark()
    wl = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (wl["config"], wl["traffic"], wl["chips"]) \
        == (CONFIG, "rag-open", 1)
    assert len(bench["workloads"]) >= 8
    cell = spec.cell(CELL)
    assert {m["name"] for m in cell["end_to_end"]} \
        == {"tpot_p50_ms", "setup_s"}
    # held by reader and arguments, not by name: the cell READS what these
    # files read, under whatever name a later PR merges a copy into (and a
    # later PR may add)
    assert _reads(spec, cell) >= _read_by(spec, {
        "engine_queue_mean_ms.rag",
        "decode_batch_mean.rag", "decode_steps_per_block.rag",
        "decode_dev_ms_per_step.rag", "prefill_ms_per_ktok.rag",
        "prefill_chunks_per_prompt.rag", "hbm_peak.rag",
        "kv_bytes_per_live_token.rag", "moe_experts_hit_share.rag",
        "moe_gmm_dev_ms_per_step.rag", "moe_gmm_roofline.rag",
        "latent_decode_dev_ms_per_step.rag", "latent_decode_roofline.rag",
        "latent_write_dev_ms_per_step.rag", "flash_prefill_roofline.rag",
        "engine_host_ms_per_block.rag", "engine_admit_alloc_ms_per_block.rag",
        "engine_yield_ms_per_block.rag",
        "decode_ahead_share.rag", "stream_lag_mean_ms.rag",
        "latent_expand_rows_per_prompt_token.rag", "caller_late_p99_ms.rag",
        "mhc_dev_ms_per_step.rag", "mhc_prefill_dev_ms_per_ktok.rag"})
    for m in cell["per_layer"]:
        assert m["moves"] == "tpot_p50_ms" and CELL in m["workloads"]
        mf = spec.metric_file(m["name"])
        assert callable(spec.reader(mf["reader"]))
        for key in ("unit", "better", "source", "layer"):
            assert mf[key] == m[key], (m["name"], key)
        # per-layer metrics are read in the traced run, whose window the
        # profiler's stop stalls for tens of seconds after the traced
        # ones: a counter is cut at the trace's edges, and no metric of
        # the cell reads the client's records of the window
        if mf["reader"] == "counter_ratio":
            assert mf["args"]["scope"] == "trace", m["name"]
        assert mf["reader"] not in ("client_ttft_quantile",
                                    "client_tpot_quantile",
                                    "served_overhead_mean"), m["name"]
    for name, program, per in (
            ("mhc_dev_ms_per_step.rag", "decode", "step"),
            ("mhc_prefill_dev_ms_per_ktok.rag", "prefill", "ktok")):
        mf = spec.metric_file(name)
        assert mf["reader"] == "scope_dev_ms" and mf["args"] == {
            "scope": "mhc.", "program": program, "per": per}
    t = cell["traffic_params"]
    assert t["pairs"] == PAIRS and t["round"] == 12
    assert (t["kind"], t["order_seed"], t["steady_s"], t["trace_s"]) \
        == ("open", 0, 30.0, 4.0)
    assert t["arrival_gaps"] == round(t["rate_per_s"] * 50)
    # 0.6 x the knee, to the rate's two decimals
    assert abs(t["rate_per_s"] - 0.6 * t["knee_per_s"]) <= 0.0051
    assert f"{t['knee_per_s']:g}" in wl["why"] \
        and f"{t['rate_per_s']:g}" in wl["why"]
    dep = cell["model"]["deployment"]
    chunk = max(dep["prefill_buckets"])
    prompts = sorted({p for p, _ in t["pairs"]})
    # every prompt is chunked or fills a bucket: no padded row
    for p in prompts:
        assert p % chunk in (0, *dep["prefill_buckets"])
    assert max(p + o for p, o in t["pairs"]) <= dep["max_len"]
    assert dep["parity_prompt_len"] > chunk \
        and dep["parity_prompt_len"] > 4096
    # the warm-up reaches every chunk offset and every bucket behind one

    def programs(p):
        if p <= chunk:
            return {("cold", lm.bucket_for(dep["prefill_buckets"], p))}
        return {(off, lm.bucket_for(dep["prefill_buckets"],
                                    min(chunk, p - off)))
                for off in range(0, p, chunk)}
    met = set().union(*(programs(p) for p, _ in t["warm_shapes"]))
    need = set().union(*(programs(p) for p in prompts))
    assert need <= met, sorted(need - met, key=str)


def test_what_the_family_counts(fam, spec):
    m = spec.cell(CELL)["model"]
    # one slot-step over 4,096 positions: 576 values a row a layer, bf16;
    # 32 heads' absorbed queries in (576 wide) and sums out (f32, 512)
    assert fam.latent_decode_required_bytes(m, [4096]) == 6 * (
        4096 * 1152 + 32 * (576 * 2 + 512 * 4))
    assert fam.latent_decode_required_flops(m, [4096]) \
        == 2 * 32 * (576 + 512) * 4096 * 6
    # heads of 192 for the scores and 128 for the sums
    assert fam.flash_prefill_required_flops(m, [8192]) \
        == 2 * (192 + 128) * 32 * (8192 * 8193 // 2) * 6
    assert fam.flash_prefill_required_bytes(m, [8192]) \
        == 2 * (2 * 192 + 2 * 128) * 32 * 8192 * 6
    assert fam.sparse_layers(m) == 5
    # every expert of every sparse layer read in one step: 7.05 GB
    weights = 2 * 5 * 64 * 3 * 3584 * 1024
    assert weights == pytest.approx(7.05e9, rel=1e-3)
    assert fam.gmm_decode_required_bytes(m, 5 * 64, 128) \
        == weights + 2 * 128 * (3 * 3584 + 3 * 1024)
