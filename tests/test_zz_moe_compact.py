"""A device that holds a slice of the experts passes over its own rows
only (``models/moe.py _held_sum``): the work list (``_local_fwd``: the
first ``_local_bound`` sorted assignments) against the full path
(``_gated_sum``), values and every gradient, at routings under, at and over
the bound; nothing dropped when a routing overflows it; the step's
``moe_compact_share``; and the programs that must not have changed (all
experts held, serving) hold no branch. Float32 on the CPU, ``ragged_dot``
and the kernels in the interpreter."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import moe
from test_zz_moe_qwen3_next import _batch, _cfg, fam  # noqa: F401 (fixture)

# 128 tokens x 4 of 16 experts, 4-8 held: 512 assignments, a bound of
# 2 x 512 x 4 / 16 = 256 rows (one row tile). 1024 float32 values are a
# row the kernels fetch by id
TOKENS, K, E, HELD, FIRST, D, F = 128, 4, 16, 4, 4, 1024, 128
# held picks a token -> local assignments of the 512
ROUTINGS = {"none_local": 0, "random": None, "exactly_the_bound": 2,
            "over_the_bound": 3, "all_local": 4}


def _rel(a, b):
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def _picks(key, held_a_token):
    """(TOKENS, K) expert ids, distinct within a token: ``held_a_token`` of
    them among the held experts (None: K of all E at random)."""
    held = jnp.arange(FIRST, FIRST + HELD)
    others = jnp.setdiff1d(jnp.arange(E), held)

    def one(key):
        a, b, c = jax.random.split(key, 3)
        if held_a_token is None:
            return jax.random.permutation(a, E)[:K]
        return jax.random.permutation(c, jnp.concatenate([
            jax.random.permutation(a, held)[:held_a_token],
            jax.random.permutation(b, others)[:K - held_a_token]]))

    return jax.vmap(one)(jax.random.split(key, TOKENS)).astype(jnp.int32)


def _operands(seed=3):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    y = jax.random.normal(keys[0], (TOKENS, D), jnp.float32)
    w = {name: jax.random.normal(key, shape, jnp.float32) * shape[1] ** -0.5
         for name, key, shape in (("w_gate", keys[1], (HELD, D, F)),
                                  ("w_up", keys[2], (HELD, D, F)),
                                  ("w_down", keys[3], (HELD, F, D)))}
    gates = jax.nn.softmax(jax.random.normal(keys[4], (TOKENS, K)), -1)
    weight = jax.random.normal(keys[5], (TOKENS, D), jnp.float32)
    return y, gates, w, weight


def test_the_bound_is_twice_the_expected_share_in_whole_tiles():
    assert moe._local_bound(TOKENS * K, HELD, E) == 256
    # the cell: 16,384 tokens x 10 of 512 experts, 32 held
    assert moe._local_bound(163840, 32, 512) == 20480
    assert moe._local_bound(163840, 33, 512) == 21248       # rounded up
    assert moe._local_bound(1000, 1, 512) == 256


@pytest.mark.parametrize("impl", ["ragged_dot", "pallas_interpret"])
@pytest.mark.parametrize("routing", list(ROUTINGS))
def test_the_work_list_against_the_full_path(routing, impl):
    """``_held_sum`` against ``_gated_sum`` on one sort: the output and the
    gradients of y, the gates and the three expert weights; the flag says
    which path ran (the work list up to and AT the bound, the full path
    over it)."""
    cfg = moe.tiny(n_experts=E, experts_per_token=K, gmm_impl=impl)
    y, gates, w, weight = _operands()
    experts = _picks(jax.random.PRNGKey(7), ROUTINGS[routing])
    _, order, inverse, sizes = moe._sort_by_expert(experts, FIRST, HELD)
    local = int(sizes.sum())
    if ROUTINGS[routing] is not None:
        assert local == ROUTINGS[routing] * TOKENS
    assert (local <= 256) == (routing not in ("over_the_bound", "all_local"))

    def held(y, gates, w):
        out, fits = moe._held_sum(y, gates, order, inverse, sizes, w, cfg)
        return jnp.sum(out * weight), (out, fits)

    def full(y, gates, w):
        out = moe._gated_sum(y, gates, order, inverse, sizes, w, cfg)
        return jnp.sum(out * weight), out

    with jax.default_matmul_precision("highest"):
        (_, (out, fits)), got = jax.jit(jax.value_and_grad(
            held, (0, 1, 2), has_aux=True))(y, gates, w)
        (_, want_out), want = jax.jit(jax.value_and_grad(
            full, (0, 1, 2), has_aux=True))(y, gates, w)
    assert float(fits) == float(local <= 256)
    np.testing.assert_allclose(out, want_out, atol=2e-5)
    errs = jax.tree.map(_rel, got, want)
    assert max(jax.tree.leaves(errs)) < 1e-5, errs
    if local == 0:
        assert not np.asarray(out).any()
        assert not any(np.asarray(g).any() for g in jax.tree.leaves(got))


@pytest.mark.parametrize("routing", ["random", "to_the_held_experts"])
def test_the_layer_with_its_router(routing):
    """``_experts`` (route, sort, the held experts' sum) against the same
    route and sort through the full path: output, counts and the gradients
    of y, the ROUTER and the weights. With the router's held columns
    raised along a direction every row has, every token picks the four
    held experts: 512 local assignments of a bound of 256, the full path,
    nothing dropped."""
    cfg = moe.tiny(n_experts=E, experts_per_token=K, gmm_impl="ragged_dot")
    y, _, w, weight = _operands(seed=5)
    router = jax.random.normal(jax.random.PRNGKey(9), (D, E)) * D ** -0.5
    if routing == "to_the_held_experts":
        y = y + 2.0
        router = router.at[:, FIRST:FIRST + HELD].add(4.0 / D)

    def layer(y, router, w):
        out, counts, _, fits = moe._experts(
            y, router, w["w_gate"], w["w_up"], w["w_down"], cfg, FIRST)
        return jnp.sum(out * weight), (out, counts, fits)

    def full(y, router, w):
        gates, experts, _ = moe._route(y, router, None, cfg)
        _, order, inverse, sizes = moe._sort_by_expert(experts, FIRST, HELD)
        out = moe._gated_sum(y, gates, order, inverse, sizes, w, cfg)
        return jnp.sum(out * weight), out

    with jax.default_matmul_precision("highest"):
        (_, (out, counts, fits)), got = jax.jit(jax.value_and_grad(
            layer, (0, 1, 2), has_aux=True))(y, router, w)
        (_, want_out), want = jax.jit(jax.value_and_grad(
            full, (0, 1, 2), has_aux=True))(y, router, w)
    local = float(counts[FIRST:FIRST + HELD].sum())
    if routing == "random":
        assert 0 < local <= 256 and float(fits) == 1.0
    else:
        assert local == TOKENS * K and float(fits) == 0.0
    np.testing.assert_allclose(out, want_out, atol=2e-5)
    errs = jax.tree.map(_rel, got, want)
    assert max(jax.tree.leaves(errs)) < 1e-5, errs
    assert float(jnp.abs(got[1]).max()) > 0            # the router's


@pytest.mark.parametrize("routing,share", [("random", 1.0),
                                           ("to_the_held_experts", 0.0)])
def test_the_step_against_the_reference_and_its_compact_share(
        fam, routing, share):
    """The whole model against the plain reference restricted to the
    slice, logits, loss and the router's gradient, and the step's
    ``moe_compact_share``: 1 at a random routing; 0 where every layer's
    router sends every token to the held experts (embeddings that share a
    large component, the held experts' router columns along it), with
    ``moe_local_share`` 1: every assignment is computed, none dropped."""
    # one period, experts 4-8 of 16 held, 4 a token; 256 tokens a step make
    # 1,024 assignments and a bound of 512
    cfg = _cfg()
    params, batch = moe.init_params(jax.random.PRNGKey(0), cfg), _batch(cfg)
    if routing == "to_the_held_experts":
        params["embed"] = params["embed"] + 8.0
        params["layers"]["router"] = params["layers"]["router"].at[
            :, :, 4:8].add(1.0)

    def loss(p):
        value, stats = moe.loss_and_metrics(p, batch, cfg)
        return value, stats

    with jax.default_matmul_precision("highest"):
        (got_loss, stats), grads = jax.jit(jax.value_and_grad(
            loss, has_aux=True))(params)
        got = jax.jit(lambda p, t: moe.forward(p, t, cfg))(
            params, batch["tokens"])
        want, want_loss = fam.logits_and_loss(params, batch, cfg)
        want_grads = jax.grad(
            lambda p: fam.logits_and_loss(p, batch, cfg)[1])(params)
    assert float(stats["moe_compact_share"]) == share
    if share:
        assert 0.1 < float(stats["moe_local_share"]) < 0.45
    else:
        assert float(stats["moe_local_share"]) == 1.0
    assert _rel(got, want) < 1e-5
    assert abs(float(got_loss) - float(want_loss)) < 2e-5
    for name in ("router", "w_gate", "w_up", "w_down"):
        assert _rel(grads["layers"][name], want_grads["layers"][name]) \
            < 2e-4, name


def _lowered(f, *args):
    return jax.jit(f).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()


def _kernels(text):
    names = re.findall(r'kernel_name = "(\w+)"', text)
    return {name: names.count(name) for name in set(names)}


def _shapes(cfg):
    params = jax.eval_shape(lambda k: moe.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    return params, {"tokens": tokens, "targets": tokens}


def _grad_text(cfg):
    params, batch = _shapes(cfg)
    return _lowered(lambda p, b: jax.value_and_grad(
        lambda p: moe.loss_and_metrics(p, b, cfg)[0])(p), params, batch)


@pytest.mark.parametrize("program", [
    "train_all_experts_held", "serve_block_own_weights", "serve_block_stack",
    "train_a_held_slice"])
def test_only_the_held_slices_train_step_holds_a_branch(program):
    """Lowered for the TPU with the kernels. All experts held (OLMoE's
    shape) and ``serve_block`` on a held slice: no branch, no scatter-add
    of rows, the custom calls the parent's programs hold (their lowered
    texts were the parent's byte for byte when this PR was written,
    CHANGES.md). A held slice's train step: one branch a layer pass, whose
    work-list side calls the same five kernels."""
    if program.startswith("train"):
        held = dict(n_experts=64, experts_held=8, first_expert=8) \
            if program == "train_a_held_slice" else {}
        text = _grad_text(moe.olmoe_1b_7b(
            n_layers=2, vocab_size=512, gmm_impl="pallas",
            attn_impl="reference", max_seq_len=256, **held))
        want = {"moe_gmm_rows": 2, "moe_gmm": 2, "moe_gmm_t": 2,
                "moe_gmm_drhs_rows": 1, "moe_gmm_drhs": 1}
    else:
        cfg = moe.k_exaone_236b_a23b(
            n_layers=4, vocab_size=512, dim=2048, n_heads=16, n_kv_heads=2,
            head_size=128, ffn_dim=512, dense_ffn_dim=1024, n_experts=32,
            experts_held=4, gmm_impl="pallas")
        layers = _shapes(cfg)[0]["layers"]
        y = jax.ShapeDtypeStruct((1024, 2048), jnp.bfloat16)
        if program == "serve_block_stack":
            text = _lowered(lambda y, layers: moe.serve_block(
                y, jax.tree.map(lambda a: a[1], layers), cfg, stack=layers,
                row=1)[0], y, layers)
            want = {"moe_gmm": 3}
        else:
            text = _lowered(lambda y, lp: moe.serve_block(y, lp, cfg)[0], y,
                            jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                                a.shape[1:], a.dtype), layers))
            want = {"moe_gmm_rows": 1, "moe_gmm": 1}
    branches = len(re.findall(r"stablehlo\.(?:case|if)\b", text))
    if program == "train_a_held_slice":
        # forward, the remat's forward and the backward of the scanned layer
        assert branches == 3
        assert _kernels(text) == {k: 2 * v for k, v in want.items()}
        return
    assert branches == 0
    assert _kernels(text) == want
