"""The ``mistral4`` family and its cell: the configuration resolves to the
program's config at the published widths and the stated cut, the family
meets the protocol (``serve_parity`` included), what it counts for the
rooflines agrees with a hand count at the cell's sizes, the new reader
reads a context with nothing in it as nothing, and the cell's metric files
and readers are found. (The served forwards against the reference, the
share test, the kernels and the pool's latent kind are tier-1:
tests/test_zz_latent_serving.py, tests/test_zz_hybrid_serving.py,
tests/test_aot_tpu_compile.py; the cell's rehearsal is test_rehearsal.py's,
which runs every cell of BENCHMARK.json.)"""
import json
import os

import pytest

from harness import spec

CELL = "serve-mistral4-longdoc-open"
NEW_READERS = {
    "latent_decode_roofline.longdoc": "family_latent_decode_roofline",
    "latent_decode_dev_ms_per_step.longdoc": "named_kernel",
    "latent_write_dev_ms_per_step.longdoc": "named_kernel",
    "latent_expand_rows_per_prompt_token.longdoc": "counter_ratio",
    "prefill_chunks_per_prompt.longdoc": "counter_ratio",
    "decode_dev_ms_per_step.longdoc": "decode_dev_ms_per_step",
    "moe_gmm_roofline.longdoc": "named_kernel",
    "flash_prefill_roofline.longdoc": "family_flash_prefill_roofline"}


@pytest.fixture(scope="module")
def cell():
    return spec.cell(CELL)


def test_the_configuration_is_the_published_one_but_for_the_cut(cell):
    m = cell["model"]
    reduced = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):
        row = next(r for r in map(json.loads, open(path))
                   if r["name"] == "Mistral-Small-4-119B-2603")
        assert {k for k, v in row["config"].items() if m.get(k) != v} \
            == set(reduced)
        assert m["source"] == row["source_url"]
        assert [m["source_" + k] for k in reduced] \
            == [row["config"][k] for k in reduced]
    entry = next(c for c in spec.benchmark()["configs"]
                 if c["name"] == cell["config"])
    assert entry["reduced"] == reduced
    assert set(m["assumed"]) >= {"a_scoring", "b_mscale", "c_query_scale",
                                 "d_vision", "e_torch_dtype", "row_padding",
                                 "weights"}
    assert "8 chips" in m["stands_for"] and "8 x 4" in m["stands_for"]


def test_it_resolves_to_the_programs_config(cell):
    from ray_tpu.models.moe import MoEConfig
    assert cell["family"] == "mistral4"
    cfg = spec.family("mistral4").config(cell["model"])
    assert isinstance(cfg, MoEConfig)
    assert (cfg.dim, cfg.n_heads, cfg.head_dim, cfg.q_lora_rank,
            cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (4096, 32, 128, 1024, 256, 64, 64, 128)
    assert cfg.layer_types == ("latent",) * 9
    assert (cfg.n_experts, cfg.n_held, cfg.first_expert,
            cfg.experts_per_token, cfg.ffn_dim, cfg.scoring,
            cfg.norm_topk_prob, cfg.routed_scaling,
            cfg.n_shared_experts) == (128, 16, 0, 4, 2048, "softmax", True,
                                      1.0, 1)
    assert (cfg.rope_factor, cfg.rope_original_len, cfg.rope_beta_fast,
            cfg.rope_beta_slow, cfg.rope_mscale, cfg.rope_mscale_all_dim,
            cfg.query_scale_beta, cfg.rope_theta) == (
        128.0, 8192, 32.0, 1.0, 1.0, 1.0, 0.1, 10000.0)
    assert cfg.vocab_size == 16384 and cfg.norm_eps == 1e-6
    # the issue's arithmetic: 4.24 B parameters, 8.48 GB in bf16
    assert 2 * cfg.num_params() == pytest.approx(8.48e9, rel=5e-3)
    # a config the family does not describe is refused, not guessed
    for key, bad in (("n_group", 2), ("first_k_dense_replace", 1),
                     ("rope_interleave", False), ("v_head_dim", 64)):
        with pytest.raises(ValueError):
            spec.family("mistral4").config({**cell["model"], key: bad})


def test_the_family_meets_the_protocol():
    fam = spec.family("mistral4")
    for name in ("config", "module", "forward", "logits_and_loss",
                 "train_required_flops_per_token", "serve_parity", "served",
                 "compared", "layer_share", "routed_share"):
        assert callable(getattr(fam, name)), name
    assert fam.module().__name__ == "ray_tpu.models.moe"
    for item in ("(a)", "(b)", "(c)", "(d)", "(e)", "softmax", "THE SHARE",
                 "MATERIALISED", "YaRN"):
        assert item in fam.__doc__
    # the reference imports nothing of the program for its mathematics
    src = open(fam.__file__).read()
    ref = src[src.index("# --- the plain reference"):
              src.index("# --- the serving comparison")]
    assert "ray_tpu" not in ref


def test_what_the_kernels_require_is_a_hand_count(cell):
    fam, m = spec.family("mistral4"), cell["model"]
    # one slot-step at 16,384 positions: a row of 320 values a position a
    # layer, bf16, once; 32 heads' absorbed queries in (bf16, 320 wide)
    # and weighted sums out (f32, 256 wide); 9 layers
    rows = 16384 * 320 * 2
    qo = 32 * (320 * 2 + 256 * 4)
    assert fam.latent_decode_required_bytes(m, [16384]) == 9 * (rows + qo)
    assert fam.latent_decode_required_flops(m, [16384]) \
        == 2 * 32 * (320 + 256) * 16384 * 9
    # 57 operations a byte: under the chip's 240, so bandwidth rules
    assert 50 < fam.latent_decode_required_flops(m, [16384]) \
        / fam.latent_decode_required_bytes(m, [16384]) < 60
    # a 10,240-token prompt on materialised heads of 128, 32 of them
    assert fam.flash_prefill_required_flops(m, [10240]) \
        == 4 * 128 * 32 * (10240 * 10241 // 2) * 9
    assert fam.flash_prefill_required_bytes(m, [10240]) \
        == 2 * 128 * 4 * 32 * 10240 * 9
    assert fam.sparse_layers(m) == 9
    # every held expert of every layer read in one step, 16 rows routed
    weights = 9 * 16 * 3 * 4096 * 2048 * 2
    assert weights == pytest.approx(7.25e9, rel=1e-3)
    assert fam.gmm_decode_required_bytes(m, 9 * 16, 16) \
        == weights + 2 * 16 * (3 * 4096 + 3 * 2048)


def test_the_new_reader_reads_nothing_where_there_is_nothing(cell):
    read = spec.reader("family_latent_decode_roofline")
    assert read({"trace": None}) is None
    assert read({"trace": {"kernels": {}}, "trace_edges": None}) is None
    # a traced run of a program without the kernel (the parent's): no
    # profile of this run is found, so nothing is read and nothing raises
    ctx = {"trace": {"kernels": {}}, "trace_edges": (1e18, 1e18 + 8),
           "cell": cell, "requests": [], "model": cell["model"],
           "info": {"device": {"kind": "TPU v5 lite"}}}
    assert read(ctx) is None


def test_the_cells_metrics_resolve(cell):
    names = [m["name"] for m in cell["per_layer"]]
    assert len(names) == len(set(names)) >= len(NEW_READERS)
    assert set(NEW_READERS) <= set(names)
    for name in names:      # every metric of the cell finds its files
        assert callable(spec.reader(spec.metric_file(name)["reader"]))
    for name, reader in NEW_READERS.items():
        mf = spec.metric_file(name)
        assert mf["reader"] == reader and mf["workloads"] == [CELL]
    e2e = {m["name"] for m in cell["end_to_end"]}
    assert e2e == {"tpot_p50_ms", "setup_s"}
    # the named-kernel metrics name the kernels the program names
    for name, kernel in (("latent_decode_dev_ms_per_step.longdoc",
                          "latent_decode"),
                         ("latent_write_dev_ms_per_step.longdoc",
                          "latent_write")):
        assert spec.metric_file(name)["args"]["kernel"] == kernel
