"""The ``nemotron_h`` family and its cell: the configuration resolves to
the program's config at the published widths and the stated cut, the
reference's token-by-token recurrence agrees with the same rule written a
second way (cumulative products), what the family counts for the rooflines
agrees with a hand count at the cell's sizes, the two new readers read a
made-up context (and one with nothing in it as nothing), the cell's
entries, metric files and traffic are what the issue set, and the cell's
rehearsal runs end to end. (The served forwards against the reference, the
given routing and the planted state fault are tier-1:
tests/test_zz_nemotron_h_serving.py.)"""
import json
import os
import types

import numpy as np
import pytest

from harness import model as hmodel, spec

CELL = "serve-nemotron3-chat-open"
CONFIG = "nemotron-3-nano-30b-a3b-serve-ep8"
JOINED = ["ttft_p90_ms.serve", "tpot_p95_ms.serve", "caller_late_p99_ms.serve",
          "engine_queue_mean_ms.serve", "decode_batch_mean.serve",
          "decode_steps_per_block.serve", "hbm_peak.serve",
          "kv_fetch_per_live.serve", "engine_tpot_unstalled_p50_ms.serve"]
NEW = {
    "ssm_step_dev_ms_per_step.ssm": "scope_dev_ms_counted",
    "ssm_mixer_dev_ms_per_step.ssm": "scope_dev_ms_counted",
    "ssm_scan_dev_ms_per_ktok.ssm": "scope_dev_ms",
    "ssm_step_roofline.ssm": "ssm_roofline",
    "ssm_scan_roofline.ssm": "ssm_roofline",
    "state_bytes_per_slot.ssm": "info_value",
    "moe_gmm_dev_ms_per_step.ssm": "named_kernel",
    "moe_gmm_roofline.ssm": "named_kernel",
    "moe_experts_hit_share.ssm": "counter_ratio",
    "paged_decode_roofline.ssm": "family_paged_decode_roofline",
    "flash_prefill_roofline.ssm": "family_flash_prefill_roofline",
    "decode_around_ms_per_step.ssm": "decode_around_ms_per_step",
    "prefill_own_ms_per_ktok.ssm": "counter_ratio",
    "engine_stall_ms_per_step.ssm": "counter_ratio",
    "engine_host_ms_per_block.ssm": "counter_ratio",
    "engine_gap_ms_per_block.ssm": "counter_ratio",
    "decode_dev_ms_per_step.ssm": "decode_dev_ms_per_counted_step"}


@pytest.fixture(scope="module")
def cell():
    return spec.cell(CELL)


@pytest.fixture(scope="module")
def fam():
    return spec.family("nemotron_h")


def test_the_configuration_is_the_published_one_but_for_the_cut(cell, fam):
    m = cell["model"]
    reduced = ["num_hidden_layers", "hybrid_override_pattern",
               "n_routed_experts", "vocab_size"]
    entry = next(c for c in spec.benchmark()["configs"]
                 if c["name"] == CONFIG)
    assert cell["config"] == CONFIG and entry["reduced"] == reduced
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):
        row = next(r for r in map(json.loads, open(path))
                   if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
        assert sorted(k for k, v in row["config"].items()
                      if k not in m or m[k] != v) == sorted(reduced)
        assert m["source"] == entry["source"] == row["source_url"]
        assert [m["source_" + k] for k in reduced] == [
            row["config"][k] for k in reduced]
    # the cut and the floors: four whole turns of MEMEM*E, 16 of 128
    # experts, an eighth of the vocabulary; no width differs
    assert (m["num_hidden_layers"], m["hybrid_override_pattern"],
            m["n_routed_experts"], m["vocab_size"]) == (
                28, "MEMEM*E" * 4, 16, 16384)
    assert m["n_routed_experts"] >= 8 \
        and m["vocab_size"] * 8 >= m["source_vocab_size"]
    assert (m["hidden_size"], m["mamba_num_heads"], m["mamba_head_dim"],
            m["ssm_state_size"], m["n_groups"], m["conv_kernel"],
            m["chunk_size"], m["num_attention_heads"],
            m["num_key_value_heads"], m["head_dim"],
            m["moe_intermediate_size"], m["num_experts_per_tok"],
            m["moe_shared_expert_intermediate_size"],
            m["routed_scaling_factor"]) == (
                2688, 64, 64, 128, 8, 4, 128, 32, 2, 128, 1856, 6, 3712, 2.5)
    assert set(m["assumed"]) >= {
        "a_no_rope", "b_mamba_init", "c_selection_bias", "d_state_float32",
        "e_torch_dtype", "f_shared_expert", "weights"}
    assert "8 chips" in m["stands_for"] and "pipeline" in m["stands_for"]
    dep = m["deployment"]
    assert (dep["kind"], dep["family"], dep["max_slots"], dep["max_len"],
            dep["cache_dtype"], dep["state_dtype"]) == (
                "serve", "nemotron_h", 64, 2048, "bfloat16", "float32")
    from ray_tpu.llm import kvcache as kc, model as lm
    from ray_tpu.models import moe
    cfg = fam.config(m)
    assert isinstance(cfg, moe.MoEConfig)
    kinds = lm.layer_kinds(cfg)
    assert (kinds.count("state"), kinds.count("experts"),
            kinds.count("global")) == (12, 12, 4)
    assert len(lm._segments(cfg)) == 1 and lm._segments(cfg)[0].repeats == 4
    assert (cfg.n_experts, cfg.n_held, cfg.experts_per_token, cfg.scoring,
            cfg.expert_act, cfg.n_shared_experts * cfg.ffn_dim,
            cfg.rope_layers) == (128, 16, 6, "sigmoid", "relu2", 3712,
                                 "none")
    # 2,806 M parameters on the chip, as the issue counts them; a slot's
    # state and a token's K/V
    assert cfg.num_params() == pytest.approx(2806e6, rel=2e-3)
    assert kc.state_slot_bytes(cfg, dep["cache_dtype"]) \
        == fam.state_bytes_per_slot(m) == 12 * (2097152 + 36864)
    assert 4 * kc.row_bytes(cfg, kc.GLOBAL, dep["cache_dtype"]) == 4096


def test_the_recurrence_written_a_second_way(fam):
    """h_t = P_t sum_{j <= t} dt_j x_j B_j^T / P_j with P_t the cumulative
    product of the decays exp(dt_i A): the same states and outputs as the
    reference's ``lax.scan`` over positions (float64 here; moderate decays,
    so that 1 / P_j stays finite)."""
    import jax
    rng = np.random.default_rng(0)
    s, H, P, G, N = 24, 4, 3, 2, 5
    x = rng.normal(size=(s, H, P))
    dt = rng.uniform(0.01, 0.2, size=(s, H))
    A = -rng.uniform(0.5, 2.0, size=(H,))
    B, C = rng.normal(size=(s, G, N)), rng.normal(size=(s, G, N))
    D = rng.normal(size=(H,))
    Bh, Ch = np.repeat(B, H // G, 1), np.repeat(C, H // G, 1)
    prod = np.cumprod(np.exp(dt * A), axis=0)                   # (s, H)
    add = np.einsum("sh,shp,shn->shpn", dt, x, Bh)
    h = prod[..., None, None] * np.cumsum(
        add / prod[..., None, None], axis=0)
    want = np.einsum("shpn,shn->shp", h, Ch) + D[:, None] * x
    f32 = [np.asarray(v, np.float32) for v in (x, dt, A, B, C, D)]
    with jax.default_matmul_precision("highest"):
        y, state = fam.recurrence(*f32)
    np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(state, h[-1], rtol=2e-4, atol=2e-4)


def test_what_the_family_counts(cell, fam):
    m = cell["model"]
    assert (fam.attention_layers(m), fam.sparse_layers(m)) == (4, 12)
    # a live slot a step a layer: its state in and out, x, y (4,096 each),
    # B, C (1,024 each) and dt (64), float32
    row = (2 * 4096 + 2 * 1024 + 64) * 4
    assert fam.ssm_step_required_bytes(m, 10) \
        == 10 * 12 * (2 * 2097152 + row)
    assert fam.ssm_step_required_flops(m, 10) \
        == 10 * 12 * 5 * 64 * 64 * 128
    assert fam.ssm_scan_required_flops(m, [300, 100]) \
        == 400 * 12 * 5 * 64 * 64 * 128
    assert fam.ssm_scan_required_bytes(m, [300, 100]) \
        == 12 * (400 * row + 2 * 2097152)
    assert fam.paged_decode_required_bytes(m, [500, 100]) \
        == 4 * (2 * 600 * 2 * 128 * 2 + 2 * 32 * 128 * 6)
    assert fam.flash_prefill_required_flops(m, [256]) \
        == 4 * 128 * 32 * 4 * (256 * 257 // 2)
    assert fam.flash_prefill_required_bytes(m, [256]) \
        == 2 * 128 * (64 + 4) * 256 * 4
    # two matrices an expert, at the PUBLISHED width (1,856: what is stored
    # past it is zeros no implementation has to read)
    assert fam.gmm_decode_required_bytes(m, 100, 40) \
        == 2 * (100 * 2 * 2688 * 1856 + 40 * (2 * 2688 + 2 * 1856))
    assert fam.train_required_flops_per_token(m, 28, 4096) > 6 * 4e8


def _ctx(cell, **kw):
    return {"cell": cell, "model": cell["model"], "requests": [],
            "counters": {"window": {}}, "trace": None,
            "info": {"device": {"kind": "TPU v5 lite"}}, **kw}


def test_the_new_readers_on_a_made_up_context(cell, fam, monkeypatch):
    counted = spec._module("readers", "scope_dev_ms_counted")
    roofline = spec.reader("ssm_roofline")
    # nothing traced: nothing to read
    assert counted.read(_ctx(cell), "ssm.step", "decode") is None
    assert roofline(_ctx(cell), "step") is None
    assert roofline(_ctx(cell), "scan") is None
    # 40 calls of the paged kernel are 10 steps: 4 of the 28 layers attend
    trace = {"kernels": {"paged_decode": {"calls": 40, "s": 0.01}},
             "programs": {}}
    ctx = _ctx(cell, trace=trace, trace_edges=(10.0, 14.0))
    assert counted.steps_in_profile(ctx) == 10
    # the scope's seconds come from the run's profile: stand in for it
    seconds = {"ssm.step": 0.01, "ssm.scan": 0.002}
    real = spec._module

    def module(kind, name):
        mod = real(kind, name)
        if name == "scope_dev_ms_counted":
            mod.scope_seconds = lambda ctx, scope, program: seconds[scope]
        return mod
    monkeypatch.setattr(spec, "_module", module)
    # 8 live slots a step (80 slot-steps in 10 steps), at 819 GB/s
    ctx["counters"]["trace"] = {"block_steps_sum": 20, "slot_steps_sum": 160}
    from harness import peaks
    pk = peaks.peaks("TPU v5 lite")
    want = 100.0 * fam.ssm_step_required_bytes(cell["model"], 80) \
        / pk["hbm_bytes_per_s"] / 0.01
    assert spec.reader("ssm_roofline")(ctx, "step") == pytest.approx(want)
    assert 0 < want < 100
    # a prompt of 300 whose first token came inside the edges, one outside
    ctx["requests"] = [
        types.SimpleNamespace(prompt_len=300, t_tokens=[11.0, 11.1]),
        types.SimpleNamespace(prompt_len=500, t_tokens=[15.0])]
    m = cell["model"]
    want = 100.0 * max(
        fam.ssm_scan_required_flops(m, [300]) / pk["bf16_flops"],
        fam.ssm_scan_required_bytes(m, [300]) / pk["hbm_bytes_per_s"]) / 0.002
    assert spec.reader("ssm_roofline")(ctx, "scan") == pytest.approx(want)
    assert spec._module("readers", "scope_dev_ms_counted").read(
        ctx, "ssm.step", "decode") == pytest.approx(1e3 * 0.01 / 10)
    # what the engine's stats say, and nothing where they do not say it
    info = spec.reader("info_value")
    assert info({"info": {"state_bytes_per_slot": 25608192}},
                "state_bytes_per_slot") == 25608192
    assert info({"info": {}}, "state_bytes_per_slot") is None


def test_the_cell_its_entries_and_its_traffic(cell):
    bench = spec.benchmark()
    wl = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (wl["config"], wl["traffic"], wl["chips"]) \
        == (CONFIG, "chat-dense-open", 1)
    assert {m["name"] for m in cell["end_to_end"]} \
        == {"tpot_p50_ms", "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert names >= set(JOINED) | set(NEW)      # a later PR may add
    assert len(bench["per_layer"]) <= 128
    for m in cell["per_layer"]:
        assert m["moves"] == "tpot_p50_ms"
        mf = spec.metric_file(m["name"])
        assert callable(spec.reader(mf["reader"]))
        if m["name"] in NEW:
            assert mf["reader"] == NEW[m["name"]]
            assert m["workloads"] == [CELL] and "workloads" not in mf
    # the accepted reader counts a step by num_hidden_layers calls of the
    # paged kernel; here 4 of 28 layers attend, so the cell reads the
    # step's device time by the engine's count instead
    assert "decode_dev_ms_per_step.serve" not in names
    t = cell["traffic_params"]
    assert t["pairs"] == [[64, 128], [128, 256], [192, 384], [256, 512],
                          [384, 192], [512, 320], [640, 448], [768, 160],
                          [96, 448], [320, 256], [448, 384], [576, 224]]
    assert (t["kind"], t["order_seed"], t["round"], t["steady_s"]) \
        == ("open", 0, 12, 45.0)
    assert t["arrival_gaps"] == round(t["rate_per_s"] * 50)
    assert t["rate_per_s"] == pytest.approx(0.6 * t["knee_per_s"], rel=0.02)
    dep = cell["model"]["deployment"]
    from ray_tpu.llm import model as lm
    need = {lm.bucket_for(dep["prefill_buckets"], p)
            for p, _ in t["pairs"]} | {lm.bucket_for(
                dep["prefill_buckets"], dep["parity_prompt_len"] + 16)}
    assert need <= {lm.bucket_for(dep["prefill_buckets"], p)
                    for p, _ in t["warm_shapes"]}
    assert max(p + o for p, o in t["pairs"]) <= dep["max_len"]
    assert max(p for p, _ in t["pairs"]) <= max(dep["prefill_buckets"])


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
    assert parity["misrouted_positions"] == 0 \
        and parity["idle_state_max"] == 0.0
    got = out["metrics"]
    assert got["state_bytes_per_slot.ssm"]["value"] \
        == fam.state_bytes_per_slot(hmodel.resolved(cell["model"]))
    assert {"decode_batch_mean.serve", "engine_queue_mean_ms.serve",
            "moe_experts_hit_share.ssm", "engine_stall_ms_per_step.ssm"} \
        <= set(got)
