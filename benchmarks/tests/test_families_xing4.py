"""The ``xing4`` family and its cell: the configuration resolves to the
program's config at the published widths and the stated cut (in depth
alone), the family meets the protocol (``serve_parity`` included), what
it counts for the rooflines agrees with a hand count at the cell's
sizes, the new reader decodes a recorded profile's ``tf_op`` and reads a
context with nothing in it as nothing, and the cell's metric files and
readers are found. (The served forwards against the reference, the
mixing's properties and the planted faults are tier-1:
tests/test_zz_xing4_serving.py; the cell's rehearsal is
test_rehearsal.py's, which runs every cell of BENCHMARK.json.)"""
import json
import os

import pytest

from harness import spec

CELL = "serve-xing4-rag-open"
READERS = {
    "mhc_dev_ms_per_step.rag": "scope_dev_ms",
    "mhc_prefill_dev_ms_per_ktok.rag": "scope_dev_ms",
    "latent_decode_roofline.rag": "family_latent_decode_roofline",
    "latent_decode_dev_ms_per_step.rag": "named_kernel",
    "latent_write_dev_ms_per_step.rag": "named_kernel",
    "prefill_chunks_per_prompt.rag": "counter_ratio",
    "decode_dev_ms_per_step.rag": "decode_dev_ms_per_step",
    "moe_gmm_roofline.rag": "named_kernel",
    "flash_prefill_roofline.rag": "family_flash_prefill_roofline"}
SMALL = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "small.xplane.pb")


@pytest.fixture(scope="module")
def cell():
    return spec.cell(CELL)


def test_the_configuration_is_the_published_one_but_for_the_cut(cell):
    m = cell["model"]
    reduced = ["num_hidden_layers", "first_k_dense_replace"]
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):
        row = next(r for r in map(json.loads, open(path))
                   if r["name"] == "Xing4.0-29B-A4B")
        assert {k for k, v in row["config"].items() if m.get(k) != v} \
            == set(reduced)
        assert m["source"] == row["source_url"]
        assert [m["source_" + k] for k in reduced] \
            == [row["config"][k] for k in reduced]
    entry = next(c for c in spec.benchmark()["configs"]
                 if c["name"] == cell["config"])
    assert entry["reduced"] == reduced
    assert set(m["assumed"]) >= {
        "a_sinkhorn_order", "b_stream_norm", "c_copy_in_sum_out",
        "d_rope_pairs", "e_mscale", "f_dtype", "g_mtp", "h_head_dim",
        "row_padding", "weights"}
    assert "8 pipeline stages" in m["stands_for"] \
        and "no expert parallelism" in m["stands_for"]


def test_it_resolves_to_the_programs_config(cell):
    from ray_tpu.models.moe import MoEConfig
    assert cell["family"] == "xing4"
    fam = spec.family("xing4")
    cfg = fam.config(cell["model"])
    assert isinstance(cfg, MoEConfig)
    assert (cfg.dim, cfg.n_heads, cfg.head_dim, cfg.q_lora_rank,
            cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (3584, 32, 128, 768, 512, 128, 64, 128)
    assert cfg.layer_types == ("latent",) * 6
    assert (cfg.n_experts, cfg.n_held, cfg.experts_per_token, cfg.ffn_dim,
            cfg.scoring, cfg.norm_topk_prob, cfg.routed_scaling,
            cfg.n_shared_experts, cfg.n_dense_layers, cfg.dense_ffn_dim) \
        == (64, 64, 4, 1024, "sigmoid", True, 2.0, 1, 1, 9216)
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps,
            cfg.hc_res_clamp_min, cfg.hc_res_clamp_max) \
        == (4, 20, 1e-6, -30.0, 30.0)
    assert (cfg.rope_factor, cfg.rope_original_len, cfg.rope_mscale,
            cfg.rope_mscale_all_dim, cfg.query_scale_beta, cfg.rope_theta) \
        == (64.0, 4096, 1.0, 1.0, 0.0, 10000.0)
    assert cfg.vocab_size == 131072 and cfg.norm_eps == 1e-6
    # the issue's arithmetic: 4,792.6 M parameters, 9.59 GB in bf16
    # (the float32 mixing leaves and router weigh 4 bytes: 15 MB more)
    assert cfg.num_params() == pytest.approx(4792.6e6, rel=1e-4)
    # a config the family does not describe is refused, not guessed
    for key, bad in (("n_group", 2), ("scoring_func", "softmax"),
                     ("topk_method", "greedy"), ("ep_size", 8)):
        with pytest.raises(ValueError):
            fam.config({**cell["model"], key: bad})


def test_the_family_meets_the_protocol():
    fam = spec.family("xing4")
    for name in ("config", "module", "forward", "logits_and_loss",
                 "train_required_flops_per_token", "serve_parity", "served",
                 "compared", "mixing", "mixed", "routed", "gates"):
        assert callable(getattr(fam, name)), name
    assert fam.module().__name__ == "ray_tpu.models.moe"
    assert set(fam.MIXING_FAULTS) < set(fam.FAULTS) and len(fam.FAULTS) == 9
    for item in ("(a)", "(b)", "(c)", "(d)", "(e)", "(f)", "(g)", "sigmoid",
                 "Sinkhorn", "MATERIALISED", "YaRN", "doubly stochastic",
                 "arXiv:2512.24880"):
        assert item in fam.__doc__, item
    # the reference imports nothing of the program for its mathematics
    src = open(fam.__file__).read()
    ref = src[src.index("# --- the plain reference"):
              src.index("# --- the serving comparison")]
    assert "ray_tpu" not in ref


def test_what_the_kernels_require_is_a_hand_count(cell):
    fam, m = spec.family("xing4"), cell["model"]
    # one slot-step at 6,144 positions: a row of 576 values a position a
    # layer, bf16, once; 32 heads' absorbed queries in (bf16, 576 wide)
    # and weighted sums out (f32, 512 wide); 6 layers
    rows = 6144 * 576 * 2
    qo = 32 * (576 * 2 + 512 * 4)
    assert fam.latent_decode_required_bytes(m, [6144]) == 6 * (rows + qo)
    assert fam.latent_decode_required_flops(m, [6144]) \
        == 2 * 32 * (576 + 512) * 6144 * 6
    # 60 operations a byte: under the chip's 240, so bandwidth rules
    assert 55 < fam.latent_decode_required_flops(m, [6144]) \
        / fam.latent_decode_required_bytes(m, [6144]) < 62
    # a 6,144-token prompt on materialised heads, keys 192, values 128
    assert fam.flash_prefill_required_flops(m, [6144]) \
        == 2 * (192 + 128) * 32 * (6144 * 6145 // 2) * 6
    assert fam.flash_prefill_required_bytes(m, [6144]) \
        == 2 * 2 * (192 + 128) * 32 * 6144 * 6
    assert fam.sparse_layers(m) == 5
    # the experts 24 slots reach in the mean, 1 - (63/64)^96 = 78% of 64
    # a layer, and their 96 x 5 routed rows
    hit = 5 * 64 * (1 - (63 / 64) ** 96)
    need = fam.gmm_decode_required_bytes(m, hit, 5 * 96)
    assert need == 2 * (hit * 3 * 3584 * 1024
                        + 5 * 96 * (3 * 3584 + 3 * 1024))
    assert need == pytest.approx(5.5e9, rel=0.01)


def test_the_scope_reader_reads_the_profiles_op_names():
    """On a profile recorded on a v5e: the installation has the
    profile's protobuf classes, every event metadata's ``tf_op`` is found
    with them (28 of this profile's 65 carry one: a format that drifts
    fails here, where on the chip the metric would only fall silent), and
    an instruction is told by a part of it and by its program's name."""
    from readers import scope_dev_ms as r
    assert r._xplane_pb2() is not None
    ops = r.op_names(SMALL)
    assert len(ops) == 28
    assert all(name.startswith("%") for name in ops)
    assert any(op.startswith("jit(decode_like)/while/body/")
               for op in ops.values())
    found = r.scope_time(SMALL, "squeeze", "decode_like")
    assert found and found[0] > 0 and found[1] > 0
    assert r.scope_time(SMALL, "mhc.", "decode_like") is None
    assert r.scope_time(SMALL, "squeeze", "no_such_program") is None


def test_the_new_reader_reads_nothing_where_there_is_nothing(cell):
    read = spec.reader("scope_dev_ms")
    args = dict(scope="mhc.", program="decode", per="step")
    assert read({"trace": None}, **args) is None
    assert read({"trace": {"kernels": {}}, "trace_edges": None},
                **args) is None
    # a traced run of a program without the scope (the parent's): no
    # profile of this run is found, so nothing is read and nothing raises
    ctx = {"trace": {"kernels": {}}, "trace_edges": (1e18, 1e18 + 8),
           "cell": cell, "requests": [], "model": cell["model"],
           "info": {"device": {"kind": "TPU v5 lite"}}}
    assert read(ctx, **args) is None
    assert read(ctx, scope="mhc.", program="prefill", per="ktok") is None


def test_the_cells_metrics_resolve(cell):
    names = [m["name"] for m in cell["per_layer"]]
    # 24 with the cell (PR 54); entries that list all four serve cells
    # have joined since (PR 56's five, PR 58's one)
    assert len(names) == len(set(names)) >= 24
    assert set(READERS) <= set(names)
    for name in names:      # every metric of the cell finds its files
        assert callable(spec.reader(spec.metric_file(name)["reader"]))
    for name, reader in READERS.items():
        mf = spec.metric_file(name)
        assert mf["reader"] == reader and mf["workloads"] == [CELL]
    e2e = {m["name"] for m in cell["end_to_end"]}
    assert e2e == {"tpot_p50_ms", "setup_s"}
    # the named-kernel metrics name the kernels the program names
    for name, kernel in (("latent_decode_dev_ms_per_step.rag",
                          "latent_decode"),
                         ("latent_write_dev_ms_per_step.rag",
                          "latent_write"),
                         ("moe_gmm_dev_ms_per_step.rag", "moe_gmm_decode")):
        assert spec.metric_file(name)["args"]["kernel"] == kernel
