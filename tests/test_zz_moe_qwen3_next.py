"""A model of linear (gated delta rule) and gated full-attention layers
with a held slice of softmax-routed experts and a gated shared expert,
TRAINED: ``models/moe.py`` through ``make_train_step`` against the plain
reference of ``benchmarks/families/qwen3_next.py``, float32 on the CPU at
tiny widths with seeded weights."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from ray_tpu.models import moe
from ray_tpu.parallel import MeshSpec, make_mesh, make_train_step

BENCH = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                     "benchmarks")


@pytest.fixture(scope="module")
def fam():
    """benchmarks/families/qwen3_next.py: the plain reference."""
    sys.path.insert(0, BENCH)
    try:
        from harness import spec
        yield spec.family("qwen3_next")
    finally:
        sys.path.remove(BENCH)


def _cfg(**kw):
    """One period (linear, linear, linear, full) at the published ratios:
    head 32 beside hidden 64 / 4 heads, 2 key and 4 value heads of 16,
    rotary on a quarter of a head, experts 4-8 of 16 held, 4 a token."""
    base = dict(vocab_size=256, dim=64, n_layers=4, n_heads=4, n_kv_heads=2,
                head_size=32, rotary_dim=8, ffn_dim=32, n_experts=16,
                experts_per_token=4, experts_held=4, first_expert=4,
                linear_key_heads=2, linear_value_heads=4, linear_key_dim=16,
                linear_value_dim=16, aux_loss_weight=0.01,
                max_seq_len=256, dtype="float32", attn_impl="reference",
                gmm_impl="ragged_dot")
    base.update(kw)
    return moe.qwen3_next_80b_a3b(**base)


def _params(cfg, seed=0):
    """Seeded weights with the norm weights (zero-centred: from 0) and the
    other small leaves moved off their start, so that 1 + w, the plain
    gated norm and the gates all show."""
    params = moe.init_params(jax.random.PRNGKey(seed), cfg)
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return tree.unflatten([
        a + 0.1 * jax.random.normal(k, a.shape, a.dtype)
        if a.ndim <= 2 and a.shape[-1] <= 64 else a
        for a, k in zip(leaves, keys)])


def _batch(cfg, rows=2, seq=128, seed=1):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (rows, seq + 1), 0,
                              cfg.vocab_size)
    return {"tokens": toks[:, :-1], "targets": toks[:, 1:]}


def test_the_preset_is_the_published_config():
    cfg = moe.qwen3_next_80b_a3b()
    assert cfg.layer_types[:8] == ("linear",) * 3 + ("full",) \
        + ("linear",) * 3 + ("full",)
    assert (cfg.dim, cfg.head_dim, cfg.n_heads, cfg.n_kv_heads) == (
        2048, 256, 16, 2)
    assert cfg.linear_widths == (2048, 4096) and cfg.rotary_dim == 64
    # the issue's arithmetic: a linear and a full mixer, one layer's rest
    assert cfg._linear_params() == 25_165_824 + 131_072 + 32_768 \
        + 8_388_608 + 2 * 2048 + 2 * 32 + 128
    assert 78e9 < cfg.num_params() < 82e9
    assert not moe._serving_only(cfg)
    assert moe._serving_only(moe.k_exaone_236b_a23b())
    assert not moe._serving_only(moe.olmoe_1b_7b())


def test_the_cell_its_configuration_and_its_metrics(fam):
    """``train-qwen3next-ep16`` as BENCHMARK.json has it: the published
    widths kept, three keys cut, the accepted train metrics."""
    from harness import spec
    bench = spec.benchmark()
    cell = spec.cell("train-qwen3next-ep16", bench)
    assert (cell["config"], cell["traffic"], cell["chips"],
            cell["family"]) == ("qwen3-next-80b-a3b-train-ep16",
                                "pretrain-4k", 1, "qwen3_next")
    assert {m["name"] for m in cell["end_to_end"]} \
        == {"train_tok_s_chip", "setup_s"}
    # at least these: a `benchmark` PR may add a per-layer metric
    assert {m["name"] for m in cell["per_layer"]} >= {
        "flash_fwd_roofline.train", "flash_bwd_roofline.train",
        "train_step_dev_ms", "train_mfu_required", "hbm_peak.train",
        "moe_gmm_dev_ms.train", "moe_gmm_roofline.train"}
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    model, dep = cell["model"], cell["model"]["deployment"]
    assert [(model[k], model["source_" + k]) for k in entry["reduced"]] \
        == [(4, 48), (32, 512), (18992, 151936)]
    cfg = fam.config(model, **dep["model_overrides"])
    assert cfg == moe.qwen3_next_80b_a3b(
        n_layers=4, vocab_size=18992, experts_held=32,
        **dep["model_overrides"])
    assert cfg.layer_types == ("linear", "linear", "linear", "full")
    assert fam.train_required_flops_per_token(model, 4, 4096) \
        == pytest.approx(1.2976e9, rel=1e-3)
    assert not hasattr(fam, "serve_parity")


def test_init_draws_decays_that_outlive_a_chunk_and_some_that_do_not():
    cfg = _cfg(linear_value_heads=64, linear_key_heads=32)
    p = jax.jit(lambda k: moe.init_params(k, cfg))(jax.random.PRNGKey(3))
    lin = p["linear_layers"]
    assert lin["A_log"].dtype == lin["dt_bias"].dtype == jnp.float32
    a, dt = jnp.exp(lin["A_log"]), jax.nn.softplus(lin["dt_bias"])
    assert 0 < float(a.min()) and float(a.max()) <= 16
    assert 1e-3 * 0.99 <= float(dt.min()) and float(dt.max()) <= 0.1 * 1.01
    per_chunk = 64 * a * dt        # -log of what a chunk leaves of a state
    assert float(per_chunk.min()) < 0.5 and float(per_chunk.max()) > 5
    for name in ("attn_norm", "mlp_norm"):
        np.testing.assert_array_equal(p["layers"][name], 0.0)
    np.testing.assert_array_equal(p["final_norm"], 0.0)
    np.testing.assert_array_equal(lin["gdn_norm"], 1.0)
    np.testing.assert_array_equal(p["full_layers"]["q_norm"], 0.0)


@pytest.mark.parametrize("layers", [4, 8], ids=["one_period", "two_periods"])
def test_logits_and_loss_against_the_reference(fam, layers):
    """Both layer kinds, top-4 of 16 with experts 4-8 held, the gated
    shared expert, the pooled load-balancing term; over two periods (two
    turns of the scan) the logits alone."""
    cfg = _cfg(n_layers=layers)
    params, batch = _params(cfg), _batch(cfg)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, t: moe.forward(p, t, cfg))(
            params, batch["tokens"])
        want, want_loss = fam.logits_and_loss(params, batch, cfg)
    np.testing.assert_allclose(got, want, atol=2e-4)
    # float32 rounding, a layer's on the last: 2.4e-6 and 6.7e-6 here
    assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) \
        < 6e-6 * layers / 4
    if layers > 4:
        return
    with jax.default_matmul_precision("highest"):
        loss, stats = jax.jit(lambda p, b: moe.loss_and_metrics(p, b, cfg))(
            params, batch)
    assert abs(float(loss) - float(want_loss)) < 2e-5
    assert 0.0 < float(stats["moe_local_share"]) < 1.0


def test_a_row_that_is_no_multiple_of_the_chunk_is_refused():
    cfg = _cfg()
    params, batch = _params(cfg), _batch(cfg, seq=100)
    with pytest.raises(AssertionError):
        jax.eval_shape(lambda p, t: moe.forward(p, t, cfg), params,
                       batch["tokens"])


def test_the_grouped_matmuls_count_is_the_expected_share(fam):
    """``moe_gmm_roofline.train`` in the new cell: the family's count at
    k * held / E experts a token, through the accepted reader."""
    from harness import spec
    cell = spec.cell("train-qwen3next-ep16")
    step = fam.gmm_required_flops_per_step(cell["model"], 4, 4 * 4096)
    # 10 * 32 / 512 experts a token, three matmuls of 2048 x 512 each,
    # forward, the remat's forward, d_lhs, d_rhs, four layers
    assert step == pytest.approx(2 * 0.625 * 3 * 2048 * 512 * 16384 * 4 * 4)
    trace = {"kernels": {"unknown_kernel": {"s": 0.15, "calls": 96}},
             "programs": {"train": {"s": 2.4, "calls": 3}}}
    ctx = {"cell": cell, "model": cell["model"], "trace": trace,
           "train": {"tokens_per_step": 16384, "chips": 1},
           "info": {"device": {"kind": "TPU v5 lite"}}}
    assert spec.reader("moe_gmm")(ctx, "roofline") == pytest.approx(
        100.0 * step / 197e12 / 0.05)


@pytest.fixture(scope="module")
def undamaged(fam):
    """(cfg, params, batch, the reference's logits) of the faults' test."""
    cfg = _cfg()
    params, batch = _params(cfg), _batch(cfg, rows=1, seq=192)
    with jax.default_matmul_precision("highest"):
        want, _ = fam.logits_and_loss(params, batch, cfg)
    return cfg, params, batch, want


@pytest.mark.parametrize("fault,least", [
    ("g_zero", 1e-3), ("beta_one", 1e-3), ("no_conv", 1e-3),
    ("no_output_gate", 1e-3), ("rope_all", 1e-3), ("top_k_less_one", 1e-3),
    ("no_shared_gate", 1e-3), ("state_reset_64", 1e-3),
    ("all_experts", 1e-3), ("state_bf16_64", 1e-3), ("gates_bf16", 1e-3)])
def test_each_fault_of_the_sensitivity_tool_moves_the_reference(
        fam, undamaged, fault, least):
    """The controls of ``benchmarks/tools/linear_attn_parity_sensitivity.py``
    are real: each changes the float32 logits by more than the program
    differs from the undamaged reference here (6e-6 relative: the test
    above holds the program to it), the bf16 roundings of the rule's state
    and gates too."""
    assert fault in fam.FAULTS and len(fam.FAULTS) == 11
    cfg, params, batch, want = undamaged
    with jax.default_matmul_precision("highest"):
        bad, _ = fam.logits_and_loss(params, batch, cfg, faults=(fault,))
    err = float(jnp.linalg.norm(bad - want) / jnp.linalg.norm(want))
    assert err > least, err


def test_the_shares_add_up_to_the_uncut_layer(fam):
    """The guide's share test: the four shares' routed parts (experts
    0-4, 4-8, 8-12, 12-16) plus the gated shared expert counted once are
    the uncut reference's layer output."""
    whole = _cfg(experts_held=0, first_expert=0)
    lp = jax.tree.map(lambda a: a[1], _params(whole)["layers"])
    y = jax.random.normal(jax.random.PRNGKey(5), (2, 24, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        routed, shared, _, _ = fam.expert_layer(y, lp, whole)
        want = routed + shared
        program, reference = moe._shared(y, lp), shared
        for first in range(0, 16, 4):
            cfg = _cfg(first_expert=first)
            mine = {**lp, **{k: lp[k][first:first + 4]
                             for k in ("w_gate", "w_up", "w_down")}}
            program = program + moe._moe_block(y, mine, cfg, None,
                                               moe.MeshAxes())[0]
            reference = reference + fam.expert_layer(y, mine, cfg)[0]
    np.testing.assert_allclose(reference, want, atol=2e-5)
    np.testing.assert_allclose(program, want, atol=5e-5)


def test_a_held_slice_and_an_expert_mesh_axis_are_refused_together():
    mesh = make_mesh(MeshSpec(data=1, fsdp=1, tensor=1, context=1, expert=2),
                     devices=jax.devices()[:2])
    cfg = _cfg()
    lp = jax.tree.map(lambda a: a[0], _params(cfg)["layers"])
    with pytest.raises(ValueError, match="held slice"):
        moe._moe_block(jnp.zeros((2, 16, 64)), lp, cfg, mesh, moe.MeshAxes())


def test_a_train_step_learns_and_reports_its_local_share():
    """Through ``make_train_step`` on a mesh of one device: the loss of a
    learnable next token falls, and the step's metrics carry the share of
    the assignments that reached the held experts."""
    mesh = make_mesh(MeshSpec(data=1, fsdp=1, tensor=1, context=1),
                     devices=jax.devices()[:1])
    cfg = _cfg(vocab_size=64)
    init_fn, step_fn = make_train_step(cfg, mesh, model=moe)
    first = jax.random.randint(jax.random.PRNGKey(2), (4, 1), 0, 64)
    toks = (first + 5 * jnp.arange(65)[None]) % 64
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
    with mesh:
        state = init_fn(jax.random.PRNGKey(0))
        # ``step_fn`` consumes the state it is handed: keep a copy
        start = jax.tree.map(jnp.copy, state.params)
        losses = []
        for _ in range(10):
            state, met = step_fn(state, batch)
            losses.append(float(met["loss"]))
    assert all(np.isfinite(losses)) and losses[-1] < losses[1] - 0.05, losses
    assert {"moe_local_share", "moe_aux_loss",
            "moe_load_max_over_mean"} <= set(met)
    assert 0.05 < float(met["moe_local_share"]) < 0.6   # 4 of 16 held
    # every leaf of the new kinds is trained
    moved = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                         state.params, start)
    for kind in ("linear_layers", "full_layers", "layers"):
        for name, by in moved[kind].items():
            assert by > 0, (kind, name)


# --- the linear mixer's two fused stages against the plain expressions -------
# token-major, as the mixer had them before it went head-major: the reference

def _plain_conv_stage(mixed, conv, hk, hv, dk, dv):
    """mixed (b, s, C) with C = [q | k | v], conv (C, K) -> q, k
    (b, s, hk, dk), v (b, s, hv, dv): causal depthwise conv, silu, L2 norm
    of q and k a head, q scaled."""
    b, s, _ = mixed.shape
    taps = conv.shape[1]
    x = jnp.pad(mixed, ((0, 0), (taps - 1, 0), (0, 0)))
    act = jax.nn.silu(sum(x[:, j:j + s] * conv[:, j] for j in range(taps)))
    q, k, v = jnp.split(act, [hk * dk, 2 * hk * dk], axis=-1)

    def l2norm(x):
        return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
    return (l2norm(q.reshape(b, s, hk, dk)) * dk ** -0.5,
            l2norm(k.reshape(b, s, hk, dk)), v.reshape(b, s, hv, dv))


def _fused_conv_stage(mixed, conv, hk, hv, dk, dv):
    """The same through ``moe._conv_silu_norm``, a kind at a time on
    head-major views, returned token-major."""
    b, s, _ = mixed.shape
    kw = hk * dk
    out = []
    for lo, hi, h, d, scale in ((0, kw, hk, dk, dk ** -0.5),
                                (kw, 2 * kw, hk, dk, 1.0),
                                (2 * kw, 2 * kw + hv * dv, hv, dv, None)):
        x = mixed[..., lo:hi].reshape(b, s, h, d).transpose(0, 2, 1, 3)
        y = moe._conv_silu_norm(x, conv[lo:hi].reshape(h, d, -1), scale)
        out.append(y.transpose(0, 2, 1, 3))
    return tuple(out)


def _plain_gated_norm(o, z, w, eps):
    """o, z (b, s, h, d): rmsnorm(o) * w * silu(z)."""
    var = jnp.mean(o * o, axis=-1, keepdims=True)
    return o * lax.rsqrt(var + eps) * w * jax.nn.silu(z)


def _fused_gated_norm(o, z, w, eps):
    return moe._gated_norm(o.transpose(0, 2, 1, 3), z.transpose(0, 2, 1, 3),
                           w, eps).transpose(0, 2, 1, 3)


_HK, _HV, _DK, _DV = 2, 4, 16, 16      # hv != hk


@pytest.fixture(scope="module")
def stages():
    """{stage: (outputs, gradients of a weighted sum of them by argument)}
    for the plain and the fused expressions: two batch rows of 8 tokens, a
    conv of 4 taps (the first three tokens' taps reach before the row)."""
    ks = jax.random.split(jax.random.PRNGKey(7), 8)
    c = 2 * _HK * _DK + _HV * _DV
    mixed = jax.random.normal(ks[0], (2, 8, c))
    conv = jax.random.uniform(ks[1], (c, 4), minval=-0.5, maxval=0.5)
    cot = [jax.random.normal(k, (2, 8, h, d)) for k, h, d in (
        (ks[2], _HK, _DK), (ks[3], _HK, _DK), (ks[4], _HV, _DV))]
    o = jax.random.normal(ks[5], (2, 8, _HV, _DV))
    z = jax.random.normal(ks[6], (2, 8, _HV, _DV))
    w = 1.0 + 0.1 * jax.random.normal(ks[7], (_DV,))
    got = {}
    for name, conv_stage, gated in (
            ("plain", _plain_conv_stage, _plain_gated_norm),
            ("fused", _fused_conv_stage, _fused_gated_norm)):
        def conv_out(mixed, conv):
            return conv_stage(mixed, conv, _HK, _HV, _DK, _DV)

        def conv_obj(mixed, conv):
            return sum(jnp.sum(a * g) for a, g in zip(conv_out(mixed, conv),
                                                      cot))

        def norm_obj(o, z, w):
            return jnp.sum(gated(o, z, w, 1e-6) * cot[2])
        got[name] = {
            "conv": dict(zip(("q", "k", "v"), conv_out(mixed, conv))),
            "conv_grad": dict(zip(("mixed", "conv"), jax.grad(
                conv_obj, argnums=(0, 1))(mixed, conv))),
            "gated_norm": {"out": gated(o, z, w, 1e-6)},
            "gated_norm_grad": dict(zip(("o", "z", "weight"), jax.grad(
                norm_obj, argnums=(0, 1, 2))(o, z, w)))}
    return got


@pytest.mark.parametrize("stage,which", [
    ("conv", "q"), ("conv", "k"), ("conv", "v"), ("conv_grad", "mixed"),
    ("conv_grad", "conv"), ("gated_norm", "out"), ("gated_norm_grad", "o"),
    ("gated_norm_grad", "z"), ("gated_norm_grad", "weight")])
def test_the_fused_stages_against_the_plain_expressions(stages, stage, which):
    """conv + silu + L2 norm and the gated norm, each one function with a
    hand-written backward on head-major arrays: outputs and every gradient
    (inputs, conv weights, norm weight) are the plain token-major
    expressions' under autodiff."""
    want, got = stages["plain"][stage][which], stages["fused"][stage][which]
    assert float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
