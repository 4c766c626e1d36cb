"""The decode step's state-space kernel (``ops/pallas/ssm_step.py``) under
the Pallas interpreter against its reference, ``ops/ssm.py ssd_step`` +
``where(live, new, old)``: the live slots' rows of y and their new states of
the layer to float32 rounding, an idle slot's state and every other layer's
states bit for bit, an idle slot's row of y zeros; at the rehearsal widths
of ``tests/test_zz_nemotron_h_serving.py`` and at the published widths of
the served configuration (64 heads of 64 with a state of 128 in 8 groups)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import ssm
from ray_tpu.ops.pallas import ssm_step as sk

WIDTHS = {"rehearsal": dict(layers=3, slots=5, h=4, p=16, g=2, n=16),
          "published": dict(layers=2, slots=8, h=64, p=64, g=8, n=128)}
LIVE = {"nobody": (), "one": (3,), "scattered": (0, 2, 4), "everybody": None}


def _case(layers, slots, h, p, g, n, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return dict(
        states=jax.random.normal(ks[0], (layers, slots, h, p, n)),
        x=jax.random.normal(ks[1], (slots, h, p)),
        dt=jax.nn.softplus(jax.random.normal(ks[2], (slots, h)) - 2.0),
        A=-jnp.exp(jax.random.uniform(ks[3], (h,), minval=0.0, maxval=2.5)),
        B=jax.random.normal(ks[4], (slots, g, n)),
        C=jax.random.normal(ks[5], (slots, g, n)),
        D=jax.random.normal(ks[6], (h,)))


def _both(c, live, layer, **kw):
    """(y, stack) of the kernel and of the reference for the slots ``live``
    (bool) at layer ``layer``."""
    rows = [c[k] for k in ("x", "dt", "A", "B", "C", "D")]
    ids, count = sk.live_slots(live)
    got = jax.jit(lambda st, l: sk.ssm_step(
        st, l, ids, count, *rows, interpret=True, **kw))(
        c["states"], jnp.int32(layer))
    y, new = ssm.ssd_step(*rows, c["states"][layer])
    new = jnp.where(live[:, None, None, None], new, c["states"][layer])
    return got, (y, c["states"].at[layer].set(new))


@pytest.mark.parametrize("layer", [0, 1])
@pytest.mark.parametrize("who", sorted(LIVE))
@pytest.mark.parametrize("widths", sorted(WIDTHS))
def test_the_kernel_is_the_rule_for_the_live_slots_and_nothing_for_the_others(
        widths, who, layer):
    w = WIDTHS[widths]
    c = _case(**w)
    lv = np.zeros((w["slots"],), bool)
    lv[list(range(w["slots"])) if LIVE[who] is None else list(LIVE[who])] = 1
    (y, new), (y0, want) = _both(c, jnp.asarray(lv), layer)
    y, new, y0, want = (np.asarray(a) for a in (y, new, y0, want))
    np.testing.assert_allclose(y[lv], y0[lv], rtol=2e-5, atol=2e-5)
    assert (y[~lv] == 0).all()                  # zeros, not what was there
    np.testing.assert_allclose(new[layer][lv], want[layer][lv], rtol=1e-6,
                               atol=1e-6)
    before = np.asarray(c["states"])
    assert (new[layer][~lv] == before[layer][~lv]).all()        # bit for bit
    others = [l for l in range(w["layers"]) if l != layer]
    assert (new[others] == before[others]).all()


@pytest.mark.parametrize("chunk_bytes,buffers,ahead", [
    (16 * 128 * 4, 2, 1),           # a head a chunk, the smallest ring
    (2 * 16 * 128 * 4, 3, 2),       # a write-back waited for at once
    (8 * 16 * 128 * 4, 4, 2)])      # a slot a chunk: the ring spans slots
def test_the_walks_ring_of_buffers_moves_every_chunk_once(chunk_bytes,
                                                          buffers, ahead):
    """However a slot's state is cut and however many chunks are in
    flight: three live slots of four through eight heads."""
    c = _case(layers=2, slots=4, h=8, p=16, g=2, n=128, seed=1)
    lv = np.asarray([True, True, False, True])
    assert sk.chunk_heads(8, 16, 128, chunk_bytes) * 16 * 128 * 4 \
        == chunk_bytes
    (y, new), (y0, want) = _both(c, jnp.asarray(lv), 1,
                                 chunk_bytes=chunk_bytes, buffers=buffers,
                                 ahead=ahead)
    np.testing.assert_allclose(np.asarray(y)[lv], np.asarray(y0)[lv],
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(new[1][lv], want[1][lv], rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(new[0], want[0])
    np.testing.assert_array_equal(new[1][~lv], want[1][~lv])


def test_the_chunk_follows_from_the_configurations_widths():
    assert sk.chunk_heads(64, 64, 128) == 8         # 256 KB a chunk
    assert sk.chunk_heads(4, 16, 16) == 4           # a slot's whole state
    assert sk.chunk_heads(6, 64, 128, 5 * 64 * 128 * 4) == 3    # a divisor
    assert sk.chunk_heads(8, 512, 512) == 1         # a head that does not fit
    ids, count = sk.live_slots(jnp.asarray([False, True, True, False, True]))
    assert list(np.asarray(ids)[:3]) == [1, 2, 4] and int(count) == 3


def test_the_kernel_refuses_what_it_would_get_wrong():
    c = _case(**WIDTHS["rehearsal"])
    rows = [c[k] for k in ("x", "dt", "A", "B", "C", "D")]
    ids, count = sk.live_slots(jnp.ones((5,), bool))
    with pytest.raises(ValueError, match="float32"):
        sk.ssm_step(c["states"].astype(jnp.bfloat16), 0, ids, count, *rows)
    with pytest.raises(ValueError, match="do not take"):
        sk.ssm_step(c["states"][:, :4], 0, ids, count, *rows)
    with pytest.raises(ValueError, match="ahead"):
        sk.ssm_step(c["states"], 0, ids, count, *rows, buffers=2, ahead=2)
