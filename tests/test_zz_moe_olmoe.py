"""OLMoE on the train path: the dropless routing of ``models/moe.py``
against the plain reference of ``benchmarks/families/moe.py`` (logits,
loss, gradients), the grouped-matmul kernel against ``lax.ragged_dot``,
the routing statistics in a step's metrics, and the benchmark's new
entries with the cell's CPU rehearsal.
"""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama, moe
from ray_tpu.ops.pallas import grouped_matmul as gm
from ray_tpu.parallel import MeshSpec, make_mesh
from ray_tpu.parallel.mesh import make_train_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmarks")


@pytest.fixture(scope="module")
def spec():
    """benchmarks/harness/spec.py, as the benchmark's own tests import
    it: with benchmarks/ on the path."""
    if BENCH_DIR not in sys.path:
        sys.path.insert(0, BENCH_DIR)
    from harness import spec
    return spec


@pytest.fixture(scope="module")
def fam(spec):
    return spec.family("moe")


def _olmoe_tiny(**kw):
    """OLMoE's shape at toy widths: q/k-norm, unrenormalised top-2 of 8."""
    return moe.tiny(**{**dict(n_experts=8, experts_per_token=2,
                              norm_topk_prob=False, qk_norm=True,
                              attn_impl="reference"), **kw})


def _setup(cfg, seed=0):
    params = moe.init_params(jax.random.PRNGKey(seed), cfg)
    # learned norm weights away from 1, so that leaving a norm out shows
    for name in ("q_norm", "k_norm", "attn_norm", "mlp_norm"):
        if name in params["layers"]:
            w = params["layers"][name]
            params["layers"][name] = (w.astype(jnp.float32) + 0.3 * jnp.cos(
                jnp.arange(w.size, dtype=jnp.float32).reshape(w.shape))
            ).astype(w.dtype)
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (2, 65), 0,
                                cfg.vocab_size)
    return params, {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _against_reference(fam, cfg, ref_cfg=None, seed=0, damage=None):
    """Relative errors (logits, loss, worst gradient leaf) of the program
    under ``cfg``, on weights that went through ``damage``, against the
    reference under ``ref_cfg`` on the true weights."""
    ref_cfg = ref_cfg or cfg
    params, batch = _setup(ref_cfg, seed)
    mine = damage(params) if damage else params
    with jax.default_matmul_precision("highest"):
        logits = moe.forward(mine, batch["tokens"], cfg)
        loss, grads = jax.value_and_grad(moe.loss_fn)(mine, batch, cfg)
    want_logits, want_loss = fam.logits_and_loss(params, batch, ref_cfg)
    with jax.default_matmul_precision("highest"):
        want_grads = jax.grad(
            lambda p: fam.logits_and_loss(p, batch, ref_cfg)[1])(params)
    leaves = jax.tree.leaves(jax.tree.map(_rel, grads, want_grads))
    return (_rel(logits, want_logits),
            abs(float(loss) - float(want_loss)) / abs(float(want_loss)),
            max(leaves))


# float32 on both sides at "highest": what is left is the order of
# summation (sorted rows and k-sums against a sum over all experts):
# measured 2.4e-7 on the logits, 6.9e-7 on the worst gradient leaf; a
# renormalised gate, a missing q/k-norm or a dropped assignment moves the
# logits by 0.31-0.44
F32_TOL = 2e-5


@pytest.mark.parametrize("impl", ["ragged_dot", "pallas_interpret"])
def test_program_matches_reference_float32(fam, impl):
    cfg = _olmoe_tiny(dtype="float32", gmm_impl=impl)
    logits, loss, grad = _against_reference(fam, cfg)
    assert logits < F32_TOL and loss < F32_TOL and grad < 10 * F32_TOL, \
        (logits, loss, grad)


def test_mixtral_setting_matches_reference_float32(fam):
    """The other setting of the same config: renormalised gates, no
    q/k-norm."""
    cfg = moe.tiny(n_experts=8, norm_topk_prob=True, qk_norm=False,
                   attn_impl="reference", dtype="float32")
    logits, loss, grad = _against_reference(fam, cfg)
    assert logits < F32_TOL and loss < F32_TOL and grad < 10 * F32_TOL


@pytest.mark.parametrize("wrong", [dict(norm_topk_prob=True),
                                   dict(qk_norm=False),
                                   dict(experts_per_token=1)])
def test_a_wrong_layer_equation_fails_the_float32_check(fam, wrong):
    """The tolerance above is tight enough to mean something: a program
    that renormalises the gates, leaves q/k-norm out or reaches one
    expert of a token's two (a dropped assignment) is far outside it."""
    right = _olmoe_tiny(dtype="float32")
    cfg = _olmoe_tiny(dtype="float32", **wrong)
    logits, _, _ = _against_reference(fam, cfg, ref_cfg=right)
    assert logits > 100 * F32_TOL, (wrong, logits)


def _int8_experts(params):
    def int8(w):    # one scale per output channel
        scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
        return jnp.round(w / scale) * scale
    layers = {k: int8(v) if k in ("w_gate", "w_up", "w_down") else v
              for k, v in params["layers"].items()}
    return {**params, "layers": layers}


def _one_expert_zeroed(params):
    w = params["layers"]["w_down"]
    return {**params, "layers": {**params["layers"],
                                 "w_down": w.at[-1, 0].set(0.0)}}


@pytest.mark.parametrize("damage", [_int8_experts, _one_expert_zeroed])
def test_damaged_expert_weights_fail_the_float32_check(fam, damage):
    """The two controls that the chip's bf16 logits check cannot see at
    the published widths (PERF.md, PR 28: with gates left as the softmax
    gave them, the expert branch of a randomly initialised OLMoE is a
    hundredth of the residual stream's variance) are seen here: int8
    expert weights read 1.7e-2 on the logits, a zeroed expert 0.24, against
    2.4e-7 for the program as it is."""
    logits, _, _ = _against_reference(
        fam, _olmoe_tiny(dtype="float32"), damage=damage)
    assert logits > 20 * F32_TOL, (damage.__name__, logits)


def test_program_matches_reference_bfloat16_kernel_interpreted(fam):
    """bf16 weights and activations, the Pallas kernel interpreted. The
    errors are bf16's: 2^-8 a rounding, a few roundings deep, and now and
    then a token whose second and third router probabilities lie within
    one, which at these toy widths (2 experts of 8 a token, d 64) is a
    large share of that token. Measured at this seed: 2.9e-2 logits, 3e-5
    loss, 7.4e-2 on the worst gradient leaf (2.9e-2 to 7.9e-2 on the
    logits over five seeds); the tolerances are twice that. Float32 reads
    1e5 times less, a wrong equation 0.31-0.44 on the logits."""
    cfg = _olmoe_tiny(dtype="bfloat16", gmm_impl="pallas_interpret")
    logits, loss, grad = _against_reference(fam, cfg)
    assert logits < 6e-2 and loss < 2e-3 and grad < 0.15, \
        (logits, loss, grad)


def test_dropless_when_every_token_goes_to_the_same_experts(fam):
    """A router of zeros gives every expert the same probability, and
    top-k then takes experts 0 and 1 for EVERY token (equal values go to
    the lower index, in the program's ``lax.top_k`` and in the
    reference's): two groups of T rows, six empty ones. The program still
    equals the reference, which computes every expert for every token:
    nothing was dropped. A capacity of 1.25 * k * T / E rows an expert
    would have dropped 69% of the assignments."""
    cfg = _olmoe_tiny(dtype="float32", gmm_impl="pallas_interpret")
    params, batch = _setup(cfg)
    params["layers"]["router"] = jnp.zeros_like(params["layers"]["router"])
    with jax.default_matmul_precision("highest"):
        logits = moe.forward(params, batch["tokens"], cfg)
        loss, stats = moe.loss_and_metrics(params, batch, cfg)
    want_logits, want_loss = fam.logits_and_loss(params, batch, cfg)
    assert _rel(logits, want_logits) < F32_TOL
    assert abs(float(loss) - float(want_loss)) < F32_TOL * float(want_loss)
    # the largest group over the mean group: T rows over k * T / E
    assert float(stats["moe_load_max_over_mean"]) == pytest.approx(
        cfg.n_experts / cfg.experts_per_token)
    # f = (1, 1, 0, ...), p = 1/E: E * sum_e f_e p_e = k
    assert float(stats["moe_aux_loss"]) == pytest.approx(
        cfg.experts_per_token, rel=1e-5)


# --- the grouped matmul ----------------------------------------------------

def _by_group(lhs, rhs, sizes):
    """The definition, one group at a time."""
    out = np.zeros((lhs.shape[0], rhs.shape[2]), np.float32)
    start = 0
    for e, size in enumerate(sizes):
        out[start:start + size] = np.einsum(
            "mk,kn->mn", np.asarray(lhs[start:start + size], np.float32),
            np.asarray(rhs[e], np.float32))
        start += size
    return out


GROUPS = {
    "uneven_off_the_tile_grid": ([10, 37, 1, 16], 64),
    "empty_groups": ([0, 40, 0, 24], 64),
    "one_group_holds_all_rows": ([0, 0, 64, 0], 64),
    "all_empty": ([0, 0, 0, 0], 64),
    "rows_past_the_last_group": ([3, 5, 7, 9], 32),
    "boundaries_on_the_tile_grid": ([16, 32, 0, 16], 64),
}


@pytest.mark.parametrize("tiles", [(16, 128, 128), (8, 64, 128)],
                         ids=["k_in_one_block", "k_in_two_blocks"])
@pytest.mark.parametrize("case", sorted(GROUPS))
def test_gmm_forward_and_both_gradients(case, tiles):
    """``gmm`` against ``lax.ragged_dot`` and the per-group definition,
    and its two backward products (``gmm`` on the transposed weights,
    ``tgmm``) against ragged_dot's gradients; float32 operands, so only
    the order of summation differs (1e-5 of the values' scale)."""
    sizes, m = GROUPS[case]
    k, n = 128, 256
    key = jax.random.PRNGKey(len(case))
    lhs = jax.random.normal(key, (m, k), jnp.float32)
    rhs = jax.random.normal(jax.random.fold_in(key, 1), (len(sizes), k, n),
                            jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    weight = jnp.cos(jnp.arange(m * n, dtype=jnp.float32)).reshape(m, n)

    got = gm._gmm_call(lhs, rhs, gs, tiles=tiles, interpret=True)
    with jax.default_matmul_precision("highest"):
        want = jax.lax.ragged_dot(lhs, rhs, gs)
        want_grads = jax.grad(lambda l, r: jnp.sum(
            jax.lax.ragged_dot(l, r, gs) * weight), (0, 1))(lhs, rhs)
    np.testing.assert_allclose(got, want, atol=2e-4)
    np.testing.assert_allclose(got, _by_group(lhs, rhs, sizes), atol=2e-4)
    d_lhs = gm._gmm_call(weight, rhs, gs, transpose_rhs=True,
                         tiles=(tiles[0], 128, 128), interpret=True)
    d_rhs = gm.tgmm(lhs, weight, gs, tiles=tiles, interpret=True)
    np.testing.assert_allclose(d_lhs, want_grads[0], atol=2e-4)
    np.testing.assert_allclose(d_rhs, want_grads[1], atol=2e-4)
    # an empty group's gradient is written, as zeros
    for e, size in enumerate(sizes):
        if size == 0:
            assert not np.asarray(d_rhs[e]).any()


def test_gmm_custom_vjp_in_bfloat16():
    """The differentiable entry point, bf16 in and float32 accumulate:
    against ragged_dot on the same bf16 operands, both round their
    float32 sums to bf16 once (2^-8 relative)."""
    sizes, m, k, n = [50, 0, 200, 6], 256, 128, 256
    key = jax.random.PRNGKey(3)
    lhs = jax.random.normal(key, (m, k), jnp.bfloat16)
    rhs = jax.random.normal(jax.random.fold_in(key, 1), (4, k, n),
                            jnp.bfloat16)
    gs = jnp.asarray(sizes, jnp.int32)

    def loss(f):
        return lambda l, r: jnp.sum(f(l, r).astype(jnp.float32) ** 2)
    got = jax.grad(loss(lambda l, r: gm.gmm(l, r, gs, True)), (0, 1))(
        lhs, rhs)
    want = jax.grad(loss(lambda l, r: jax.lax.ragged_dot(l, r, gs)),
                    (0, 1))(lhs, rhs)
    for g, w in zip(got, want):
        assert g.dtype == jnp.bfloat16
        assert _rel(g, w) < 2e-2


def test_gmm_refuses_tiles_that_do_not_divide():
    with pytest.raises(ValueError, match="do not divide"):
        gm._gmm_call(jnp.zeros((60, 128)), jnp.zeros((2, 128, 128)),
                     jnp.asarray([30, 30], jnp.int32), tiles=(16, 128, 128),
                     interpret=True)


# --- rows read by id (PR 43) -----------------------------------------------

# (group sizes, tokens T, assignments a token k, rows a tile): every id
# repeats k times, as ``order // k`` does
ROWS = {
    "a_tile_straddles_groups": ([10, 23, 7, 24], 16, 4, 16),
    "empty_groups": ([0, 40, 0, 24], 16, 4, 16),
    "tail_rows_past_the_last_group": ([10, 0, 23, 7, 0, 8], 16, 4, 16),
    "whole_tiles_in_the_tail": ([5, 9], 16, 4, 16),
    "every_row_in_the_tail": ([0, 0, 0], 8, 4, 16),
    "one_tile": ([5, 27], 8, 4, 32),
}
# a fetched row is whole (8, 128) tiles of 32-bit words
WIDE = {"float32": (jnp.float32, 1024), "bfloat16": (jnp.bfloat16, 2048)}


def _rows_case(case, dtype, k, n=128):
    sizes, tokens, per, tm = ROWS[case]
    m = tokens * per
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 6)
    src = jax.random.normal(keys[0], (tokens, k), jnp.float32).astype(dtype)
    rhs = tuple((jax.random.normal(key, (len(sizes), k, n), jnp.float32)
                 * k ** -0.5).astype(dtype) for key in keys[1:3])
    douts = tuple(jax.random.normal(key, (m, n), jnp.float32).astype(dtype)
                  for key in keys[3:5])
    rows = jax.random.permutation(
        keys[5], jnp.repeat(jnp.arange(tokens, dtype=jnp.int32), per))
    return src, rows, rhs, douts, jnp.asarray(sizes, jnp.int32), tm


@pytest.mark.parametrize("wide", sorted(WIDE))
@pytest.mark.parametrize("case", sorted(ROWS))
def test_rows_fetched_by_id_equal_the_gathered_call(case, wide):
    """``gmm_rows`` and ``tgmm(rows=)`` against a gather and the id-less
    kernels: the same products of the same operands, so bit for bit; one
    rhs and two, one dout and two (the fused calls against two calls)."""
    dtype, k = WIDE[wide]
    src, rows, rhs, douts, gs, tm = _rows_case(case, dtype, k)
    assert gm.row_words(k, dtype) == 8
    x = src[rows]
    want = [gm._gmm_call(x, r, gs, tiles=(tm, k, 128), interpret=True)
            for r in rhs]
    got = gm.gmm_rows(src, rows, rhs, gs, tiles=(tm, 128), interpret=True)
    one, = gm.gmm_rows(src, rows, rhs[:1], gs, tiles=(tm, 128),
                       interpret=True)
    for g, w in zip((*got, one), (*want, want[0])):
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))
    # rows past the last group are zeros, whatever was fetched
    assert not np.asarray(got[0][int(gs.sum()):], np.float32).any()
    want = [gm.tgmm(x, d, gs, tiles=(tm, k, 128), interpret=True)
            for d in douts]
    got = gm.tgmm(src, douts, gs, rows=rows, tiles=(tm, k, 128),
                  interpret=True)
    one = gm.tgmm(src, douts[0], gs, rows=rows, tiles=(tm, k, 128),
                  interpret=True)
    for g, w in zip((*got, one), (*want, want[0])):
        assert np.isfinite(np.asarray(g, np.float32)).all()
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))


def test_a_row_that_is_no_whole_tile_is_gathered_first():
    """Where a DMA cannot take a row (``row_words`` 0) the rows are
    gathered and the id-less kernels run: the same values."""
    assert gm.row_words(128, jnp.float32) == 0
    assert gm.row_words(2048, jnp.float16) == 0     # only bf16 unpacks
    src, rows, rhs, douts, gs, tm = _rows_case(
        "tail_rows_past_the_last_group", jnp.float32, 128)
    x = src[rows]
    got = gm.gmm_rows(src, rows, rhs, gs, interpret=True)
    for g, r in zip(got, rhs):
        np.testing.assert_array_equal(g, gm._gmm_call(x, r, gs,
                                                      interpret=True))
    np.testing.assert_array_equal(
        gm.tgmm(src, douts[0], gs, rows=rows, interpret=True),
        gm.tgmm(x, douts[0], gs, interpret=True))


def test_gmm_t_sums_its_pairs_before_the_one_rounding():
    src, rows, rhs, douts, gs, tm = _rows_case(
        "tail_rows_past_the_last_group", jnp.float32, 256)
    both = gm.gmm_t(douts, rhs, gs, True)
    each = sum(gm.gmm_t(d, r, gs, True) for d, r in zip(douts, rhs))
    np.testing.assert_allclose(both, each, atol=1e-4)
    assert not np.asarray(both[int(gs.sum()):]).any()
    with pytest.raises(ValueError, match="does not contract"):
        gm.gmm_t(douts, rhs[:1], gs, True)


@pytest.mark.parametrize("first_expert", [0, 4], ids=["all_held", "a_slice"])
def test_gate_up_by_id_values_and_every_gradient(first_expert):
    """``_gate_up`` (the kernels, y's rows by id) against ``_dispatch`` and
    two differentiable ``gmm`` calls (the gathered copy): values, and the
    gradients of y and of both weights; float32, the same products in
    another order of summation. With a slice of the experts held, half
    the assignments sort into the tail."""
    tokens, k, d, f, held = 16, 4, 1024, 128, 4
    keys = jax.random.split(jax.random.PRNGKey(11), 5)
    y = jax.random.normal(keys[0], (tokens, d), jnp.float32)
    w_gate, w_up = ((jax.random.normal(key, (held, d, f), jnp.float32)
                     * d ** -0.5) for key in keys[1:3])
    experts = jax.random.randint(keys[3], (tokens, k), 0, 8)
    _, order, inverse, sizes = moe._sort_by_expert(experts, first_expert,
                                                   held)
    weight = jax.random.normal(keys[4], (2, tokens * k, f), jnp.float32)

    def by_id(y, w_gate, w_up):
        gate, up = moe._gate_up(y, w_gate, w_up, order, inverse, sizes, True)
        return jnp.sum(gate * weight[0]) + jnp.sum(jnp.sin(up) * weight[1])

    def gathered(y, w_gate, w_up):
        x = moe._dispatch(y, order, inverse)
        gate, up = (gm.gmm(x, w, sizes, True) for w in (w_gate, w_up))
        return jnp.sum(gate * weight[0]) + jnp.sum(jnp.sin(up) * weight[1])

    got = jax.value_and_grad(by_id, (0, 1, 2))(y, w_gate, w_up)
    want = jax.value_and_grad(gathered, (0, 1, 2))(y, w_gate, w_up)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for g, w in zip(got[1], want[1]):
        assert _rel(g, w) < 1e-5


def test_combine_backward_gathers_once_and_matches_the_einsum():
    """``_combine``'s ``d_rows`` and ``d_gates`` against ``jax.grad`` of
    the plain form (rows back in token order, an einsum with the gates),
    in float32: the gates' gradient is taken in sorted order from the one
    gather of the cotangent's rows."""
    tokens, k, d = 24, 4, 64
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    rows = jax.random.normal(keys[0], (tokens * k, d), jnp.float32)
    gates = jax.nn.softmax(jax.random.normal(keys[1], (tokens, k)), -1)
    experts = jax.random.randint(keys[2], (tokens, k), 0, 8)
    _, order, inverse, _ = moe._sort_by_expert(experts, 0, 8)
    weight = jax.random.normal(keys[3], (tokens, d), jnp.float32)

    def plain(rows, gates):
        mine = rows[inverse].reshape(tokens, k, d)
        with jax.default_matmul_precision("highest"):
            return jnp.sum(jnp.einsum("tkd,tk->td", mine, gates) * weight)

    got = jax.grad(lambda r, g: jnp.sum(
        moe._combine(r, g, order, inverse) * weight), (0, 1))(rows, gates)
    want = jax.grad(plain, (0, 1))(rows, gates)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape", ["olmoe", "mixtral"])
def test_loss_gradient_by_id_matches_ragged_dot(shape):
    """The whole loss's gradient at toy depth and a width whose rows the
    kernels fetch (1024 float32 values), ``pallas_interpret`` against
    ``ragged_dot``: every leaf."""
    kw = dict(dim=1024, n_heads=4, n_kv_heads=2, ffn_dim=128,
              dtype="float32", attn_impl="reference", remat_policy="full")
    cfg = _olmoe_tiny(**kw) if shape == "olmoe" else moe.tiny(**kw)
    params, batch = _setup(cfg)
    # 64 assignments: a tile's starts are written out, 256 of them at the
    # default tile, which the interpreter traces one by one
    batch = {k: v[:1, :32] for k, v in batch.items()}

    def grads(impl):
        c = dataclasses.replace(cfg, gmm_impl=impl)
        return jax.value_and_grad(lambda p: moe.loss_fn(p, batch, c))(params)

    (loss, got), (want_loss, want) = grads("pallas_interpret"), \
        grads("ragged_dot")
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    errs = jax.tree.map(_rel, got, want)
    assert max(jax.tree.leaves(errs)) < 2e-4, errs


# --- the step's metrics ----------------------------------------------------

def test_routing_statistics_in_the_steps_metrics():
    """``make_train_step`` adds what the family reports: device scalars
    from values the routing already has. The dense family reports none,
    and its step is built as before."""
    mesh = make_mesh(MeshSpec(data=1, fsdp=1, tensor=1, context=1, expert=1),
                     devices=jax.devices()[:1])
    cfg = _olmoe_tiny()
    init_fn, step_fn = make_train_step(cfg, mesh, model=moe)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "targets": tokens}
    with mesh:
        state, met = step_fn(init_fn(jax.random.PRNGKey(0)), batch)
    assert {"loss", "grad_norm", "step", "moe_aux_loss",
            "moe_load_max_over_mean"} == set(met)
    # aux is E * sum f p with sum f = k, sum p = 1: k when balanced, more
    # when f and p lean the same way; the largest group is at least the
    # mean one
    assert cfg.experts_per_token * 0.9 < float(met["moe_aux_loss"]) \
        < cfg.n_experts
    assert 1.0 <= float(met["moe_load_max_over_mean"]) \
        <= cfg.n_experts / cfg.experts_per_token
    dense = llama.tiny(attn_impl="reference")
    init_fn, step_fn = make_train_step(dense, mesh)
    tokens = tokens % dense.vocab_size
    with mesh:
        _, met = step_fn(init_fn(jax.random.PRNGKey(0)),
                         {"tokens": tokens, "targets": tokens})
    assert set(met) == {"loss", "grad_norm", "step"}


def test_configs_of_one_family():
    """Mixtral and OLMoE are settings of one config; the counts follow
    the experts a token is routed to."""
    mixtral, olmoe = moe.mixtral_8x7b(), moe.olmoe_1b_7b()
    assert mixtral.norm_topk_prob and not mixtral.qk_norm
    assert olmoe.qk_norm and not olmoe.norm_topk_prob
    assert not hasattr(olmoe, "capacity_factor")
    # 6.9 B parameters, 1.3 B of them active (the model's name)
    assert 6.8e9 < olmoe.num_params() < 7.0e9
    assert 1.2e9 < olmoe.num_active_params() < 1.4e9
    shapes = jax.eval_shape(lambda: moe.init_params(
        jax.random.PRNGKey(0), moe.olmoe_1b_7b(n_layers=2)))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert n == moe.olmoe_1b_7b(n_layers=2).num_params()


# --- the benchmark's new entries -------------------------------------------

# allenai/OLMoE-1B-7B-0125-Instruct config.json, as the catalog has it
PUBLISHED = {
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 1024,
    "max_position_embeddings": 4096, "model_type": "olmoe",
    "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 16,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304}


def test_the_configuration_keeps_every_published_width(spec, fam):
    bench = spec.benchmark()
    entry = next(c for c in bench["configs"]
                 if c["name"] == "olmoe-1b-7b-train")
    assert entry["reduced"] == ["num_hidden_layers"]
    with open(os.path.join(ROOT, entry["file"])) as f:
        model = json.load(f)
    for key, value in PUBLISHED.items():
        if key == "num_hidden_layers":
            assert model[key] == 2 and model["source_" + key] == value
        else:
            assert model[key] == value, key
    assert {"router_aux_loss_coef", "torch_dtype", "weights"} \
        <= set(model["assumed"])
    dep = model["deployment"]
    assert dep["family"] == "moe" and dep["kind"] == "train"
    cfg = fam.config(model, **dep["model_overrides"])
    assert cfg == moe.olmoe_1b_7b(n_layers=2, **dep["model_overrides"])
    # the rehearsal routes too: several experts, more than one a token
    assert model["rehearsal"]["num_experts_per_tok"] > 1


def test_the_cell_and_its_metrics(spec):
    bench = spec.benchmark()
    cell = spec.cell("train-olmoe", bench)
    assert (cell["config"], cell["traffic"], cell["chips"],
            cell["family"]) == ("olmoe-1b-7b-train", "pretrain-4k", 1, "moe")
    assert {m["name"] for m in cell["end_to_end"]} \
        == {"train_tok_s_chip", "setup_s"}
    assert {m["name"] for m in cell["per_layer"]} == {
        "flash_fwd_roofline.train", "flash_bwd_roofline.train",
        "train_step_dev_ms", "train_mfu_required", "hbm_peak.train",
        "moe_gmm_dev_ms.train", "moe_gmm_roofline.train"}
    for name in ("moe_gmm_dev_ms.train", "moe_gmm_roofline.train"):
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        # the first cell they were written for; later cells are appended
        assert entry["workloads"][0] == "train-olmoe"
        mf = spec.metric_file(name)
        assert mf["reader"] == "moe_gmm"
        for key in ("unit", "better", "source", "layer", "moves"):
            assert mf[key] == entry[key]
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)


def test_required_operations(fam, spec):
    model = spec.cell("train-olmoe")["model"]
    per_token = fam.train_required_flops_per_token(model, 2, 4096)
    attention = 4 * 2048 * 2048
    routed = 8 * 3 * 2048 * 1024
    matmul = 2 * (attention + 2048 * 64 + routed) + 2048 * 50304
    causal = 14 * 128 * 16 * (4096 + 1) / 2 * 2
    assert per_token == pytest.approx(6 * matmul + causal)
    assert per_token == pytest.approx(1.54e9, rel=0.01)
    # forward, the remat's second forward, d_lhs and d_rhs: four passes
    step = fam.gmm_required_flops_per_step(model, 2, 4 * 4096)
    assert step == pytest.approx(
        3 * 2 * (4 * 4096 * 8) * 2048 * 1024 * 4 * 2)


def test_the_reader_reads_nothing_where_nothing_is(spec):
    """On a program without the kernel (the parent commit), on a CPU run
    and without a trace, the reader returns None and does not raise."""
    read = spec.reader("moe_gmm")
    cell = spec.cell("train-olmoe")
    base = {"cell": cell, "model": cell["model"],
            "train": {"tokens_per_step": 16384, "chips": 1},
            "info": {"device": {"kind": "TPU v5 lite"}}}
    for what in ("dev_ms", "roofline"):
        assert read({**base, "trace": None}, what) is None
        assert read({**base, "trace": {"kernels": {}, "programs": {}}},
                    what) is None
        no_kernel = {"kernels": {"flash_fwd": {"s": 1.0, "calls": 4}},
                     "programs": {"train": {"s": 3.0, "calls": 3}}}
        assert read({**base, "trace": no_kernel}, what) is None
    trace = {"kernels": {"unknown_kernel": {"s": 0.3, "calls": 72}},
             "programs": {"train": {"s": 1.5, "calls": 3}}}
    assert read({**base, "trace": trace}, "dev_ms") == pytest.approx(100.0)
    flops = spec.family("moe").gmm_required_flops_per_step(
        cell["model"], 2, 16384)
    assert read({**base, "trace": trace}, "roofline") == pytest.approx(
        100.0 * flops / 197e12 / 0.1)


def test_the_cell_rehearses_on_the_cpu():
    """``BENCH_REHEARSAL=1``: tiny widths, kernels interpreted, the whole
    control flow of a run (parity against the reference, steps, the
    result line). Its numbers mean nothing."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_REHEARSAL="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("JAX_ENABLE_COMPILATION_CACHE", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         "train-olmoe", "--seed", "3000000001", "--seconds", "2",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(ln) for ln in proc.stdout.strip().splitlines()]
    parity = next(ln for ln in lines if ln.get("note") == "parity")
    assert parity["ok"], parity
    out = lines[-1]
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"train_tok_s_chip", "setup_s"}
    assert out["device"]["platform"] == "cpu"
