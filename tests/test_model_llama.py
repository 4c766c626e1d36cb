"""Flagship model: forward/loss correctness and sharded training step."""

import re
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama, moe
from ray_tpu.parallel import MeshSpec, make_mesh, make_train_step
from ray_tpu.parallel.mesh import make_eval_step


def _batch(key, cfg, b=2, s=64):
    tokens = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
    return {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)}


def test_forward_shapes():
    cfg = llama.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    batch = _batch(jax.random.PRNGKey(1), cfg)
    logits = llama.forward(params, batch["tokens"], cfg)
    assert logits.shape == (2, 64, cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_loss_decreases_single_device():
    cfg = llama.tiny(remat=False, dtype="float32")
    mesh = make_mesh(MeshSpec(data=1, fsdp=1, tensor=1, context=1),
                     devices=jax.devices()[:1])
    init_fn, step_fn = make_train_step(cfg, mesh)
    state = init_fn(jax.random.PRNGKey(0))
    batch = _batch(jax.random.PRNGKey(1), cfg)
    losses = []
    for _ in range(8):
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] - 0.1, losses


def test_sharded_train_step_matches_single_device(mesh8):
    """dp*fsdp*tp*cp sharded step computes the same loss as 1 device."""
    cfg = llama.tiny(dtype="float32", n_kv_heads=2, n_heads=4)
    batch = _batch(jax.random.PRNGKey(1), cfg, b=4, s=64)

    mesh1 = make_mesh(MeshSpec(data=1, fsdp=1, tensor=1, context=1),
                      devices=jax.devices()[:1])
    init1, step1 = make_train_step(cfg, mesh1)
    s1 = init1(jax.random.PRNGKey(0))
    _, m1 = step1(s1, batch)

    init8, step8 = make_train_step(cfg, mesh8)
    s8 = init8(jax.random.PRNGKey(0))
    _, m8 = step8(s8, batch)

    np.testing.assert_allclose(float(m1["loss"]), float(m8["loss"]),
                               rtol=2e-4, atol=2e-4)


def test_ring_attention_in_model(mesh8):
    """attn_impl='ring' over the context axis agrees with reference attn."""
    cfg_ref = llama.tiny(dtype="float32", attn_impl="reference")
    cfg_ring = llama.tiny(dtype="float32", attn_impl="ring")
    params = llama.init_params(jax.random.PRNGKey(0), cfg_ref)
    batch = _batch(jax.random.PRNGKey(1), cfg_ref, b=2, s=128)

    ref = llama.loss_fn(params, batch, cfg_ref, mesh8)
    ring = llama.loss_fn(params, batch, cfg_ring, mesh8)
    np.testing.assert_allclose(float(ref), float(ring), rtol=1e-4, atol=1e-4)


def test_param_count_7b():
    cfg = llama.llama2_7b()
    n = cfg.num_params()
    assert 6.5e9 < n < 7.0e9, n


def test_fused_ce_matches_classic_loss_and_grads():
    """ce_chunk > 0 must be a pure memory optimization: identical loss
    AND gradients to the materialized-logits path (f32, CPU exact-ish)."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import llama

    base = dict(dtype="float32", logits_dtype="float32",
                attn_impl="reference", remat=False)
    cfg_classic = llama.tiny(**base)
    cfg_fused = llama.tiny(**base, ce_chunk=32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg_classic)
    rng = jax.random.PRNGKey(1)
    tokens = jax.random.randint(rng, (2, 128), 0, cfg_classic.vocab_size)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1),
             "mask": (tokens % 5 != 0).astype(jnp.float32)}

    l0, g0 = jax.value_and_grad(
        lambda p: llama.loss_fn(p, batch, cfg_classic))(params)
    l1, g1 = jax.value_and_grad(
        lambda p: llama.loss_fn(p, batch, cfg_fused))(params)
    assert jnp.allclose(l0, l1, rtol=1e-6), (l0, l1)
    flat0 = jax.tree_util.tree_leaves(g0)
    flat1 = jax.tree_util.tree_leaves(g1)
    for a, b in zip(flat0, flat1):
        assert jnp.allclose(a, b, rtol=1e-5, atol=1e-6)


def test_fused_ce_sharded_matches(mesh8):
    """Fused CE under a dp/fsdp/tp/cp mesh: GSPMD inserts the vocab
    psums; the sharded fused loss equals the single-device classic."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import llama
    from ray_tpu.parallel.mesh import MeshAxes

    base = dict(dtype="float32", logits_dtype="float32",
                attn_impl="reference", remat=False)
    cfg = llama.tiny(**base, ce_chunk=64)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(2), (2, 256), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)}
    l_single = llama.loss_fn(params, batch, llama.tiny(**base))
    l_sharded = jax.jit(
        lambda p, b: llama.loss_fn(p, b, cfg, mesh8, MeshAxes()))(
        params, batch)
    assert jnp.allclose(l_single, l_sharded, rtol=1e-5), \
        (l_single, l_sharded)


def _family(name):
    """(model module, a tiny float32 config) of a model family."""
    if name == "moe":
        return moe, moe.tiny(attn_impl="reference", dtype="float32")
    return llama, llama.tiny(dtype="float32", n_kv_heads=2, n_heads=4)


@pytest.mark.parametrize("family", ["dense", "moe"])
def test_step_fn_consumes_the_state_it_is_handed(family):
    """``step_fn`` donates its ``TrainState``: after a step every leaf
    of the state handed in is deleted, the returned state carries on,
    and the step's numbers are the ones an undonated step computes: its
    first loss is ``make_eval_step``'s on a COPY of the same params."""
    model, cfg = _family(family)
    mesh = make_mesh(MeshSpec(data=1, fsdp=1, tensor=1, context=1),
                     devices=jax.devices()[:1])
    init_fn, step_fn = make_train_step(cfg, mesh, model=model)
    batch = _batch(jax.random.PRNGKey(1), cfg, b=2, s=32)
    with mesh:
        state = init_fn(jax.random.PRNGKey(0))
        kept = jax.tree.map(jnp.copy, state.params)
        want = float(make_eval_step(cfg, mesh, model=model)(kept, batch))
        state2, met = step_fn(state, batch)
        assert all(leaf.is_deleted() for leaf in jax.tree.leaves(state))
        assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(kept))
        np.testing.assert_allclose(float(met["loss"]), want, rtol=1e-6)
        state3, met3 = step_fn(state2, batch)
    assert int(state3.step) == 2 and np.isfinite(float(met3["loss"]))
    moved = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                         kept, state3.params)
    assert max(jax.tree.leaves(moved)) > 0


@pytest.mark.parametrize("family", ["dense", "moe"])
def test_sharded_step_donates_every_leaf(mesh8, family):
    """On the eight-device mesh (fsdp 2 x tensor 2 x context 2) the
    returned state has the shardings of the one handed in (``init_fn``
    ties the moments to the params'), so every donated buffer is used:
    JAX's "Some donated buffers were not usable" warning, the run-time
    sign of a leaf that did not alias, is absent, and the old state is
    gone from every device."""
    model, cfg = _family(family)
    init_fn, step_fn = make_train_step(cfg, mesh8, model=model)
    batch = _batch(jax.random.PRNGKey(1), cfg, b=4, s=64)
    with mesh8:
        state = init_fn(jax.random.PRNGKey(0))
        shardings = jax.tree.map(lambda a: a.sharding, state)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            state2, met = step_fn(state, batch)
            state3, _ = step_fn(state2, batch)
    assert not [str(w.message) for w in caught
                if "donated" in str(w.message).lower()]
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(state))
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(state2))
    assert jax.tree.map(lambda a: a.sharding, state3) == shardings
    assert np.isfinite(float(met["loss"])) and int(state3.step) == 2


# --- a layer's sums over a ``tensor`` pair (PR 55) ---------------------------

def _permutes(text: str) -> int:
    """``collective-permute`` instructions of a compiled program."""
    return len(re.findall(r" collective-permute(?:-start)?\(", text))


def _instructions(text: str) -> list:
    """A compiled program's instructions without their source lines."""
    return [re.sub(r", metadata=\{[^}]*\}", "", ln)
            for ln in text.splitlines() if " = " in ln]


def _loss_and_grads(cfg, mesh, s, monkeypatch=None):
    """(loss, grads, the compiled text) of ``llama.loss_fn`` on ``mesh``
    over ``s`` positions, the params laid out by ``param_shardings``.
    With ``monkeypatch`` the sums are left to GSPMD's own all-reduces
    (``_tp_chunks`` 0: the parent's path, what the exchange is held
    to)."""
    from jax.sharding import NamedSharding
    if monkeypatch is not None:
        monkeypatch.setattr(llama, "_tp_chunks", lambda rows: 0)
    params = jax.tree.map(
        lambda a, spec: jax.device_put(a, NamedSharding(mesh, spec)),
        llama.init_params(jax.random.PRNGKey(0), cfg),
        llama.param_shardings(cfg))
    batch = _batch(jax.random.PRNGKey(1), cfg, b=4, s=s)
    fn = jax.value_and_grad(
        lambda p, b: llama.loss_fn(p, b, cfg, mesh))
    with mesh:
        compiled = jax.jit(fn).lower(params, batch).compile()
        loss, grads = compiled(params, batch)
    return loss, grads, compiled.as_text()


def _tp_mesh(fsdp, tensor):
    return make_mesh(MeshSpec(data=1, fsdp=fsdp, tensor=tensor, context=1),
                     devices=jax.devices()[:fsdp * tensor])


@pytest.mark.parametrize("remat", ["full", "none", "dots", "attn"])
@pytest.mark.parametrize("chunks", [2, 4])
@pytest.mark.parametrize("fsdp", [1, 2])
def test_a_tensor_pair_exchanges_what_gspmd_all_reduces(
        fsdp, chunks, remat, monkeypatch):
    """With a ``tensor`` axis of two a layer's sums over it are chunked
    ``ppermute`` exchanges under hand-written conjugate operators
    (``check_vma`` is off, so neither JAX's convention for the
    transpose of a replicated value is trusted): loss and every
    gradient leaf are those of the same step through GSPMD's
    all-reduces, and the compiled step exchanges five times a chunk
    where the backward recomputes the products (``full``, ``attn``:
    attention forward, its remat, MLP forward; two backward: the MLP's
    remat is dead code) and four where it keeps them (``none``;
    ``dots``, which saves an exchanged sum by its name as it saves
    GSPMD's product)."""
    cfg = llama.tiny(dtype="float32", n_heads=4, n_kv_heads=2,
                     remat_policy=remat)
    mesh = _tp_mesh(fsdp, 2)
    s = {4: 64, 2: 66}[chunks]      # four divides the rows, or only two
    assert llama._tp_chunks(s) == chunks
    loss, grads, text = _loss_and_grads(cfg, mesh, s)
    want_loss, want, plain = _loss_and_grads(cfg, mesh, s, monkeypatch)
    # a scan's body is compiled once; GSPMD has permutes of its own
    # where fsdp shards the batch
    assert _permutes(text) - _permutes(plain) == chunks * (
        5 if remat in ("full", "attn") else 4)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-6)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want)):
        assert g.sharding == w.sharding, path
        scale = float(jnp.max(jnp.abs(w)))
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=2e-5 * scale, err_msg=str(path))


@pytest.mark.parametrize("tensor,s", [(1, 64), (4, 64), (2, 63)])
def test_other_tensor_axes_keep_the_plain_text(tensor, s, monkeypatch):
    """A ``tensor`` axis of one has no sum to make, one of four is
    GSPMD's (a ring of exchanges is what would extend the pair's) and
    so is a pair's over rows that two does not divide: what is compiled
    is what is compiled with the exchange forced off."""
    cfg = llama.tiny(dtype="float32", n_heads=4, n_kv_heads=4)
    mesh = _tp_mesh(2, tensor)
    loss, _, text = _loss_and_grads(cfg, mesh, s)
    want_loss, _, plain = _loss_and_grads(cfg, mesh, s, monkeypatch)
    assert _instructions(text) == _instructions(plain)
    assert float(loss) == float(want_loss)


def test_the_chunks_are_read_from_the_rows():
    """Four chunks where four divides the rows, else two, else GSPMD's
    all-reduce: the harness's parity slice (1,024 rows) and these
    tests' 64 take the exchange as the cell's 4,096 do."""
    assert [llama._tp_chunks(r) for r in (4096, 1024, 64, 2050, 66, 63,
                                          1)] == [4, 4, 4, 2, 2, 0, 0]
