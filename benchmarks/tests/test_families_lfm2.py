"""The ``lfm2_moe`` family and its cell: the configuration resolves to the
program's config at the published widths and the stated cut (``layer_types``
the source's list, copied whole; the layers served are a slice of it), the
reference's operator agrees with the same convolution written a second way,
what the family counts for the rooflines agrees with a hand count at the
cell's sizes, the cell's entries (a SUPERSET: a later PR may join), metric
files and traffic are what the issue set, and the cell's rehearsal runs end
to end. (The served forwards against the reference and the planted faults
are tier-1: tests/test_zz_lfm2_serving.py.)"""
import json

import numpy as np
import pytest

from harness import model as hmodel, spec

CELL = "serve-lfm2-mixed-open"
CONFIG = "lfm2-24b-a2b-serve-pp5"
JOINED = ["ttft_p90_ms.serve", "tpot_p95_ms.serve", "caller_late_p99_ms.serve",
          "engine_queue_mean_ms.serve", "decode_batch_mean.serve",
          "decode_steps_per_block.serve", "hbm_peak.serve",
          "kv_fetch_per_live.serve", "engine_tpot_unstalled_p50_ms.serve"]
# name -> (reader, its arguments)
NEW = {
    "shortconv_dev_ms_per_step.mixed": (
        "scope_dev_ms_counted", {"scope": "shortconv.", "program": "decode"}),
    "shortconv_dev_ms_per_ktok.mixed": (
        "scope_dev_ms", {"scope": "shortconv.", "program": "prefill",
                         "per": "ktok"}),
    "moe_gmm_roofline.mixed": (
        "named_kernel", {"kernel": "moe_gmm_decode",
                         "per_step": {"sparse_layer": 3},
                         "what": "gmm_roofline"})}
SERVED = ["conv", "full_attention", "conv", "conv", "conv", "full_attention",
          "conv", "conv", "conv"]


@pytest.fixture(scope="module")
def cell():
    return spec.cell(CELL)


@pytest.fixture(scope="module")
def fam():
    return spec.family("lfm2_moe")


def test_the_configuration_is_the_published_one_but_for_the_cut(cell, fam):
    m = cell["model"]
    entry = next(c for c in spec.benchmark()["configs"]
                 if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "num_dense_layers"]
    assert entry["source"] == m["source"] \
        == "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json"
    assert (m["num_hidden_layers"], m["source_num_hidden_layers"],
            m["num_dense_layers"], m["source_num_dense_layers"]) \
        == (9, 40, 1, 2)
    # every published width, whole
    assert (m["hidden_size"], m["num_attention_heads"],
            m["num_key_value_heads"], m["conv_L_cache"],
            m["intermediate_size"], m["moe_intermediate_size"],
            m["num_experts"], m["num_experts_per_tok"], m["vocab_size"],
            m["norm_eps"], m["rope_parameters"]["rope_theta"]) \
        == (2048, 32, 8, 3, 11776, 1536, 64, 4, 65536, 1e-5, 1000000)
    # the source's list, whole; the served layers are a slice of it
    assert len(m["layer_types"]) == 40
    assert m["layer_types"].count("full_attention") == 10
    assert fam.served_types(m) == m["served_layer_types"] == SERVED \
        == m["layer_types"][1:10]
    with pytest.raises(ValueError, match="served_layer_types"):
        fam.served_types({**m, "served_layer_types": SERVED[::-1]})
    for key in ("a_tie_word_embeddings", "b_torch_dtype", "c_head_dim",
                "d_qk_head_norms", "e_conv_operator", "f_gate_eps",
                "g_rope_pairs", "row_padding", "weights"):
        assert m["assumed"][key]
    dep = m["deployment"]
    assert (dep["family"], dep["max_slots"], dep["max_len"],
            dep["cache_dtype"]) == ("lfm2_moe", 128, 17408, "bfloat16")
    chunk = max(dep["prefill_buckets"])
    assert dep["parity_prompt_len"] > 2 * chunk     # two chunk boundaries
    assert dep["parity_prompt_len"] % chunk         # and an odd tail
    cfg = fam.config(m)
    from ray_tpu.models import moe
    assert cfg == moe.lfm2_24b_a2b(
        n_layers=9, n_dense_layers=1, attn_block_q=512, attn_block_k=512,
        layer_types=tuple(fam.KINDS[t] for t in SERVED))
    assert (cfg.head_dim, cfg.kv_row_heads, cfg.tie_embeddings,
            cfg.route_eps) == (64, 2, True, 1e-6)
    # the issue's arithmetic: 5,178M parameters, 10.36 GB in bf16
    assert cfg.num_params() == pytest.approx(5178e6, rel=2e-3)
    from ray_tpu.llm import kvcache as kc
    assert kc.state_slot_bytes(cfg, dep["cache_dtype"]) \
        == fam.state_bytes_per_slot(m) == 57344
    assert 2 * kc.row_bytes(cfg, kc.GLOBAL, dep["cache_dtype"]) == 4096


def test_the_references_operator_is_the_convolution(fam):
    """``short_conv`` against numpy's own convolution of z with the taps,
    and its faults' cuts against rows recomputed from a zero tail."""
    import jax
    import jax.numpy as jnp
    d, s = 16, 12
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    lp = {"w_in": jax.random.normal(ks[0], (d, 3 * d)) * d ** -0.5,
          "conv": jax.random.uniform(ks[1], (d, 3), minval=-0.5, maxval=0.5),
          "w_out": jax.random.normal(ks[2], (d, d)) * d ** -0.5}
    u = jax.random.normal(ks[3], (s, d))
    with jax.default_matmul_precision("highest"):
        out, z = fam.short_conv(u, lp, None)
        B, C, X = np.split(np.asarray(u @ lp["w_in"]), 3, axis=-1)
        np.testing.assert_allclose(z, B * X, rtol=1e-5)
        w = np.asarray(lp["conv"])
        c = np.stack([np.convolve(np.asarray(z)[:, ch], w[ch, ::-1])[:s]
                      for ch in range(d)], axis=1)
        np.testing.assert_allclose(out, (C * c) @ np.asarray(lp["w_out"]),
                                   rtol=2e-4, atol=1e-5)
        cut, _ = fam.short_conv(u, lp, None, ("tail_zero_at_chunk",), (5,))
        again, _ = fam.short_conv(u[5:], lp, None)
    np.testing.assert_allclose(cut[:5], out[:5], rtol=1e-6)
    np.testing.assert_allclose(cut[5:], again, rtol=1e-5, atol=1e-6)
    assert not np.allclose(cut[5:7], out[5:7], rtol=1e-3)
    np.testing.assert_allclose(cut[7:], out[7:], rtol=1e-5, atol=1e-6)
    assert fam.chunk_starts(8492, (128, 4096)) == (4096, 8192)


def test_what_the_kernels_require_is_a_hand_count(cell, fam):
    m = cell["model"]
    assert fam.attention_layers(m) == 2 and fam.sparse_layers(m) == 8
    # 10 experts hit, 40 assignments: 3 matrices of 2,048 x 1,536 an
    # expert, and a row in and out of the three products
    assert fam.gmm_decode_required_bytes(m, 10, 40) \
        == 2 * (10 * 3 * 2048 * 1536 + 40 * (3 * 2048 + 3 * 1536))
    # a slot at 1,000 positions: K and V, 8 heads of 64, two layers
    assert fam.paged_decode_required_bytes(m, [1000]) \
        == 2 * 1000 * 2 * 8 * 64 * 2
    assert fam.train_required_flops_per_token(m, 9, 4096) > 6 * 0.6e9


def test_the_cell_its_entries_and_its_traffic(cell):
    bench = spec.benchmark()
    wl = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (wl["config"], wl["traffic"], wl["chips"]) \
        == (CONFIG, "mixed-open", 1)
    assert {m["name"] for m in cell["end_to_end"]} \
        == {"tpot_p50_ms", "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert names >= set(JOINED) | set(NEW)      # a later PR may join
    assert len(bench["per_layer"]) <= 128
    for m in cell["per_layer"]:
        assert m["moves"] == "tpot_p50_ms" and CELL in m["workloads"]
        mf = spec.metric_file(m["name"])
        assert callable(spec.reader(mf["reader"]))
        for key in ("unit", "better", "source", "layer"):
            assert mf[key] == m[key], (m["name"], key)
        if m["name"] in NEW:
            assert (mf["reader"], mf["args"]) == NEW[m["name"]]
            assert "workloads" not in mf
    t = cell["traffic_params"]
    assert t["pairs"] == [[128, 256], [192, 384], [256, 512], [384, 192],
                          [512, 320], [640, 448], [768, 160], [1024, 256],
                          [896, 384], [8192, 256], [12288, 192],
                          [16384, 320]]
    assert (t["kind"], t["order_seed"], t["round"], t["steady_s"],
            t["trace_s"]) == ("open", 0, 12, 45.0, 4.0)
    assert t["arrival_gaps"] == round(t["rate_per_s"] * 50)
    assert any(t["rate_per_s"] == pytest.approx(share * t["knee_per_s"],
                                                rel=0.02)
               for share in (0.6, 0.5))
    dep = cell["model"]["deployment"]
    from ray_tpu.llm import model as lm
    chunk = max(dep["prefill_buckets"])
    # every bucket a short prompt takes is warmed, and every offset a
    # long prompt's chunks start at; no padded row on the long lengths
    short = [p for p, _ in t["pairs"] if p <= chunk]
    long = [p for p, _ in t["pairs"] if p > chunk]
    assert len(short) == 9 and long == [8192, 12288, 16384]
    assert all(p % chunk == 0 for p in long)
    warm = [p for p, _ in t["warm_shapes"]]
    assert {lm.bucket_for(dep["prefill_buckets"], p) for p in short} \
        <= {lm.bucket_for(dep["prefill_buckets"], p) for p in warm
            if p <= chunk}
    assert max(warm) >= max(long)
    assert max(p + o for p, o in t["pairs"]) <= dep["max_len"]
    assert all(b % dep["kv_block_size"] == 0
               for b in (*dep["prefill_buckets"], dep["max_len"]))


@pytest.mark.skipif(not hmodel.REHEARSAL, reason="BENCH_REHEARSAL=1 only")
def test_the_cells_rehearsal(cell, fam):
    from test_rehearsal import last_line, run_cell
    proc = run_cell(spec.ROOT, CELL, trace=1)
    out = last_line(proc)
    assert out["correct"] is True and out["failed"] == 0
    notes = [json.loads(line) for line in proc.stdout.splitlines()
             if line.startswith("{\"note\"")]
    window = next(n for n in notes if n["note"] == "window")
    assert window["compiles_in_window"] == 0 and window["impl_ok"]
    parity = next(n for n in notes if n["note"] == "parity")
    assert parity["finite"] and parity["idle_tail_max"] == 0.0
    assert len(parity["decode_rel_errs"]) == fam.DECODE_STEPS
    assert {"decode_batch_mean.serve", "engine_queue_mean_ms.serve",
            "decode_steps_per_block.serve"} <= set(out["metrics"])
