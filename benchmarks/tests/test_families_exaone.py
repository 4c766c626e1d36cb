"""The ``exaone_moe`` family and its cell: the configuration resolves to
the program's config at the published widths and the stated share, the
family meets the protocol (``serve_parity`` included), what it counts for
the rooflines agrees with a hand count at the cell's sizes, and the cell's
metric files and readers are found. (The served forwards against the
reference, the share test and the kernels are tier-1:
tests/test_zz_hybrid_serving.py, tests/test_zz_window_kernels.py.)"""
import json
import os
import time
import types

import pytest

from harness import spec

CELL = "serve-exaone-reason-open"
NEW_READERS = {"moe_gmm_dev_ms_per_step.reason": "named_kernel",
               "moe_gmm_roofline.reason": "named_kernel",
               "kv_write_dev_ms_per_step.reason": "named_kernel",
               "paged_decode_roofline.reason": "family_paged_decode_roofline",
               "flash_prefill_roofline.reason":
                   "family_flash_prefill_roofline"}


@pytest.fixture(scope="module")
def cell():
    return spec.cell(CELL)


def test_the_configuration_is_the_published_one_but_for_the_share(cell):
    m = cell["model"]
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):
        row = next(r for r in map(json.loads, open(path))
                   if r["name"] == "K-EXAONE-236B-A23B")
        reduced = {"num_hidden_layers", "num_experts", "vocab_size"}
        assert {k for k, v in row["config"].items() if m.get(k) != v} \
            == reduced
        assert m["source"] == row["source_url"]
        assert (m["source_num_hidden_layers"], m["source_num_experts"],
                m["source_vocab_size"]) == tuple(
            row["config"][k] for k in sorted(reduced, key=[
                "num_hidden_layers", "num_experts", "vocab_size"].index))
    entry = next(c for c in spec.benchmark()["configs"]
                 if c["name"] == cell["config"])
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert set(m["assumed"]) >= {"a_selection_bias", "b_norms", "c_rope",
                                 "d_mtp", "weights"}
    assert "8-chip" in m["stands_for"]


def test_it_resolves_to_the_programs_config(cell):
    from ray_tpu.models.moe import MoEConfig
    assert cell["family"] == "exaone_moe"
    cfg = spec.family("exaone_moe").config(cell["model"])
    assert isinstance(cfg, MoEConfig)
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) \
        == (6144, 64, 8, 128)           # head_dim given, not 6144 / 64
    assert (cfg.n_experts, cfg.n_held, cfg.first_expert,
            cfg.experts_per_token, cfg.ffn_dim) == (128, 16, 0, 8, 2048)
    assert (cfg.n_dense_layers, cfg.dense_ffn_dim) == (1, 18432)
    assert cfg.layer_types == ("window", "window", "window", "global") * 2
    assert (cfg.sliding_window, cfg.scoring, cfg.routed_scaling,
            cfg.n_shared_experts) == (128, "sigmoid", 2.5, 1)
    assert cfg.vocab_size == 19200 and cfg.rope_theta == 1e6
    # the issue's arithmetic: 11.96 GB of bf16 weights
    assert 2 * cfg.num_params() == pytest.approx(11.96e9, rel=1e-3)


def test_the_family_meets_the_protocol():
    fam = spec.family("exaone_moe")
    for name in ("config", "module", "forward", "logits_and_loss",
                 "train_required_flops_per_token", "serve_parity"):
        assert callable(getattr(fam, name)), name
    assert fam.module().__name__ == "ray_tpu.models.moe"
    for item in ("(a)", "(b)", "(c)", "(d)", "sigmoid", "THE SHARE"):
        assert item in fam.__doc__


def test_what_the_kernels_require_is_a_hand_count(cell):
    fam, m = spec.family("exaone_moe"), cell["model"]
    # one slot-step at 1,000 positions: 2 global layers read 1,000
    # positions, 6 window layers 128; K and V, 8 heads x 128, bf16;
    # queries in bf16, outputs in f32, 64 heads x 128, 8 layers
    kv = 2 * (2 * 1000 + 6 * 128) * 8 * 128 * 2
    qo = 8 * 64 * 128 * (2 + 4)
    assert fam.paged_decode_required_bytes(m, [1000]) == kv + qo
    # below the window both kinds read the whole context
    assert fam.paged_decode_required_bytes(m, [100]) \
        == 2 * 8 * 100 * 8 * 128 * 2 + qo
    # a 1,000-token prompt: n (n + 1) / 2 pairs on a global layer; on a
    # window layer 128 a row once the window is full
    band = 128 * 129 // 2 + (1000 - 128) * 128
    pairs = 2 * (1000 * 1001 // 2) + 6 * band
    assert fam.flash_prefill_required_flops(m, [1000]) \
        == 4 * 128 * 64 * pairs
    assert fam.flash_prefill_required_bytes(m, [1000]) \
        == 2 * 128 * (2 * 64 + 2 * 8) * 1000 * 8
    assert fam.sparse_layers(m) == 7
    # every held expert of every sparse layer read in one step, 32 rows
    # routed here: 7 x 16 experts x 3 matrices of 6144 x 2048, bf16
    weights = 7 * 16 * 3 * 6144 * 2048 * 2
    assert weights == pytest.approx(8.46e9, rel=1e-3)   # the issue's 8.5 GB
    assert fam.gmm_decode_required_bytes(m, 7 * 16, 32) \
        == weights + 2 * 32 * (3 * 6144 + 3 * 2048)


def test_the_cells_metrics_resolve(cell):
    names = [m["name"] for m in cell["per_layer"]]
    assert len(names) == len(set(names)) >= len(NEW_READERS)
    assert set(NEW_READERS) <= set(names)
    for name in names:      # every metric of the cell finds its files
        assert callable(spec.reader(spec.metric_file(name)["reader"]))
    for name, reader in NEW_READERS.items():
        mf = spec.metric_file(name)
        assert mf["reader"] == reader and mf["workloads"] == [CELL]
        assert callable(spec.reader(reader))
    e2e = {m["name"] for m in cell["end_to_end"]}
    assert e2e == {"tpot_p50_ms", "setup_s"}
    # no accepted reader that takes hidden / heads for the head size
    # reads this cell
    for m in cell["per_layer"]:
        assert spec.metric_file(m["name"])["reader"] not in (
            "paged_decode_roofline", "flash_prefill_roofline")
    # the cell's own (``.reason``: what it shares with the chat cell is
    # one ``.serve`` entry for both since PR 58, at the chat cell's
    # place) were appended together, in the order the cell lists them;
    # later PRs' entries come after
    own = [n for n in names if n.endswith(".reason")]
    assert set(NEW_READERS) <= set(own)
    every = [m["name"] for m in spec.benchmark()["per_layer"]]
    at = every.index(own[0])
    assert every[at:at + len(own)] == own


def test_the_traffic_is_the_issues(cell):
    t = cell["traffic_params"]
    prompts = sorted({p for p, _ in t["pairs"]})
    outputs = sorted({o for _, o in t["pairs"]})
    assert len(t["pairs"]) == len({tuple(p) for p in t["pairs"]}) == 96
    assert (len(prompts), prompts[0], prompts[-1]) == (12, 192, 2048)
    assert (len(outputs), outputs[0], outputs[-1]) == (8, 96, 640)
    assert (prompts[5] + prompts[6]) / 2 == 640
    assert (outputs[3] + outputs[4]) / 2 == 320
    window = cell["model"]["sliding_window"]
    assert min(prompts) > window        # every context past the window
    assert t["kind"] == "open" and t["round"] == 12
    # the issue's cycle: a fixed multiset of rate x 50 s gaps
    assert t["arrival_gaps"] == round(t["rate_per_s"] * 50)
    # half the knee (down to 0.35 x it), to a whole number of gaps
    assert 0.35 * t["knee_per_s"] - 0.5 / 50 <= t["rate_per_s"] \
        <= 0.5 * t["knee_per_s"] + 0.5 / 50
    # every round of 12 carries every prompt length once; the outputs
    # rotate through the rounds, so each prompt meets each output once
    outs = [[o for _, o in t["pairs"][i:i + 12]] for i in range(0, 96, 12)]
    for i in range(0, 96, 12):
        assert [p for p, _ in t["pairs"][i:i + 12]] == prompts
    for r, row in enumerate(outs):
        assert row == [outputs[(i + r) % 8] for i in range(12)]
    # every prefill bucket the mix uses is warmed
    buckets = (64, 128, 256, 512, 1024, 2048)

    def bucket(n):
        return next(b for b in buckets if n <= b)
    assert {bucket(p) for p in prompts} \
        == {bucket(p) for p, _ in t["warm_shapes"]}


def _request(prompt, stamps):
    return types.SimpleNamespace(prompt_len=prompt, t_tokens=stamps)


def test_the_family_rooflines_read_the_trace(cell):
    ctx = {"cell": cell, "model": cell["model"],
           "info": {"device": {"kind": "TPU v5 lite"}},
           "trace_edges": (10.0, 18.0),
           "requests": [_request(1000, [9.0, 11.0, 12.0, 19.0]),
                        _request(300, [10.5, 11.5])],
           "trace": {"kernels": {"paged_decode": {"s": 0.001, "calls": 24},
                                 "flash_fwd": {"s": 0.001, "calls": 8}}}}
    fam = spec.family("exaone_moe")
    # decode steps inside the edges: contexts 1001, 1002 and 301
    need = fam.paged_decode_required_bytes(cell["model"], [1001, 1002, 301])
    got = spec.reader("family_paged_decode_roofline")(ctx)
    assert got == pytest.approx(100 * need / 819e9 / 0.001)
    # prompts whose first token arrived inside the edges: the 300's
    flops = fam.flash_prefill_required_flops(cell["model"], [300])
    nbytes = fam.flash_prefill_required_bytes(cell["model"], [300])
    got = spec.reader("family_flash_prefill_roofline")(ctx)
    assert got == pytest.approx(
        100 * max(flops / 197e12, nbytes / 819e9) / 0.001)
    for reader in ("family_paged_decode_roofline",
                   "family_flash_prefill_roofline"):
        assert spec.reader(reader)({**ctx, "trace": None}) is None
        assert spec.reader(reader)({**ctx, "requests": []}) is None
    # another family's cell: nothing to read, nothing raised
    other = {**ctx, "cell": spec.cell("serve-chat-open")}
    assert spec.reader("family_paged_decode_roofline")(other) is None
    gmm = spec.metric_file("moe_gmm_roofline.reason")["args"]
    assert spec.reader("named_kernel")(other, **gmm) is None
    assert spec.reader("named_kernel")({**ctx, "trace": None}, **gmm) is None
    # no profile written since this run's trace began: nothing to read
    assert spec.reader("named_kernel")(
        {**ctx, "trace_edges": (time.monotonic() + 60.0,) * 2}, **gmm) is None
    assert spec.reader("named_kernel")(
        {**ctx, "trace_edges": None},
        **spec.metric_file("kv_write_dev_ms_per_step.reason")["args"]) is None


def test_a_named_kernels_time_is_brought_to_the_profiles_steps(
        cell, monkeypatch):
    """Counters between the trace's edges give the mean a step; the
    profile gives the steps its kernel time covers."""
    read = spec.reader("named_kernel")
    monkeypatch.setitem(read.__globals__, "_profile", lambda ctx: "p")
    monkeypatch.setitem(   # 10 steps of 7 sparse layers x 3 products
        read.__globals__, "kernel_time", lambda path, name: (0.063, 210))
    ctx = {"cell": cell, "model": cell["model"], "trace": {"kernels": {}},
           "info": {"device": {"kind": "TPU v5 lite"}},
           "counters": {"trace": {"block_steps_sum": 20.0,
                                  "moe_experts_hit_sum": 20 * 80.0,
                                  "moe_local_sum": 20 * 30.0}}}
    fam = spec.family("exaone_moe")
    need = fam.gmm_decode_required_bytes(cell["model"], 10 * 80, 10 * 30)
    gmm = spec.metric_file("moe_gmm_roofline.reason")["args"]
    assert read(ctx, **gmm) == pytest.approx(100 * need / 819e9 / 0.063)
    assert read(ctx, **{**gmm, "what": "dev_ms_per_step"}) \
        == pytest.approx(6.3)
    # the writer: once a layer a step, 8 layers
    monkeypatch.setitem(
        read.__globals__, "kernel_time", lambda path, name: (0.004, 80))
    assert read(ctx, **spec.metric_file(
        "kv_write_dev_ms_per_step.reason")["args"]) == pytest.approx(0.4)
    assert read({**ctx, "counters": {}}, **gmm) is None
