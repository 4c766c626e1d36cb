"""MoE family: shapes, gradients, expert-parallel sharding, training.

Expert parallelism is native here (a mesh axis) where the reference only
forwards EP flags to vLLM (SURVEY.md section 2.3). Routing against the
plain reference, dropless behaviour and the grouped matmul are in
tests/test_zz_moe_olmoe.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import moe
from ray_tpu.parallel import MeshSpec, make_mesh
from ray_tpu.parallel.mesh import make_train_step


def _cfg(**kw):
    return moe.tiny(attn_impl="reference", **kw)


def test_forward_shapes_and_aux():
    cfg = _cfg()
    params = moe.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                cfg.vocab_size)
    logits = moe.forward(params, tokens, cfg)      # logits, as llama's
    assert logits.shape == (2, 32, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    _, stats = moe.loss_and_metrics(
        params, {"tokens": tokens, "targets": tokens}, cfg)
    aux = float(stats["moe_aux_loss"])
    assert np.isfinite(aux) and aux > 0.0


def test_grads_flow_to_experts_and_router():
    cfg = _cfg(n_layers=1)
    params = moe.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "targets": tokens}
    grads = jax.grad(lambda p: moe.loss_fn(p, batch, cfg))(params)
    for name in ("router", "w_gate", "w_up", "w_down"):
        g = np.asarray(grads["layers"][name], np.float32)
        assert np.isfinite(g).all(), name
        assert np.abs(g).max() > 0, f"no gradient reached {name}"


# the kernels read y's rows by id where a row is whole tiles (1024 float32
# values); on the expert mesh half of a device's assignments are foreign
# and sort into the tail, which is neither fetched nor multiplied
BY_ID = dict(gmm_impl="pallas_interpret", dim=1024, n_heads=4, n_kv_heads=2,
             dtype="float32")


@pytest.mark.parametrize("kw", [{}, BY_ID], ids=["ragged_dot", "rows_by_id"])
def test_expert_parallel_matches_single_device(mesh8, kw):
    del mesh8  # ensure the session platform is initialized
    cfg = _cfg(**kw)
    params = moe.init_params(jax.random.PRNGKey(0), cfg)
    # by id a tile's starts are written out and traced one by one: 64
    # assignments, not 256
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 8 if kw else 32),
                                0, cfg.vocab_size)
    batch = {"tokens": tokens, "targets": tokens}

    ref = float(moe.loss_fn(params, batch, cfg))

    mesh = make_mesh(MeshSpec(data=2, fsdp=2, tensor=1, context=1, expert=2))
    with mesh:
        sharded = float(moe.loss_fn(params, batch, cfg, mesh))
    np.testing.assert_allclose(sharded, ref, rtol=2e-2)
    if kw:
        want = jax.grad(lambda p: moe.loss_fn(p, batch, cfg))(params)
        with mesh:
            got = jax.jit(jax.grad(lambda p: moe.loss_fn(
                p, batch, cfg, mesh)))(params)
        for name in ("w_gate", "w_up", "w_down", "router", "mlp_norm"):
            g, w = (np.asarray(t["layers"][name], np.float32)
                    for t in (got, want))
            assert np.linalg.norm(g - w) < 1e-3 * np.linalg.norm(w), name


def test_moe_train_step_on_expert_mesh():
    mesh = make_mesh(MeshSpec(data=2, fsdp=1, tensor=1, context=1, expert=4))
    import optax
    cfg = _cfg()
    init_fn, step_fn = make_train_step(cfg, mesh, model=moe,
                                       optimizer=optax.adam(1e-2))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "targets": tokens}
    with mesh:
        state = init_fn(jax.random.PRNGKey(0))
        losses = []
        for _ in range(3):
            state, metrics = step_fn(state, batch)
            losses.append(float(metrics["loss"]))
    assert int(state.step) == 3
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]  # optimizer is actually learning


def test_active_params_smaller_than_total():
    cfg = moe.mixtral_8x7b()
    assert cfg.num_active_params() < 0.5 * cfg.num_params()
    assert cfg.flops_per_token(2048) < 6.5 * cfg.num_active_params()
