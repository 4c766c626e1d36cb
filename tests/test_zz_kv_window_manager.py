"""KVBlockManager for a model with window layers: a second id space,
blocks freed as a sequence passes them, gauges per kind, exhaustion and
release. Pure python: no jax."""
import numpy as np
import pytest

from ray_tpu.llm import kvcache as kc

BS, WINDOW, STEPS = 16, 128, 8
RING = kc.window_ring_blocks(WINDOW, BS, STEPS)


def _mgr(seqs=2, blocks=512, **kw):
    return kc.KVBlockManager(blocks, BS, table_width=256, prefix_cache=False,
                             window=(1 + seqs * RING, WINDOW, STEPS), **kw)


def test_the_ring_is_the_window_plus_a_dispatch_in_blocks():
    assert kc.window_ring_blocks(128, 16, 1) == 9   # ceil(128 / 16) + 1
    assert RING == 10                               # + 7 positions ahead
    assert kc.window_ring_blocks(32, 8, 4) == 6


@pytest.mark.parametrize("prompt, first, last", [
    (20, 0, 1),         # below the window: every block of the prompt
    (128, 0, 7),        # exactly the window
    (129, 0, 8),        # position 129 - 128 = 1 is still in block 0
    (300, 10, 18),      # (301 - 128) // 16 = 10
    (2048, 120, 127),   # a long prompt keeps 8 blocks of 128
])
def test_admission_holds_only_what_the_first_step_reaches(prompt, first,
                                                          last):
    m = _mgr()
    a = m.alloc_seq(1, list(range(prompt)), 64)
    held = np.flatnonzero(a["window_table"])
    assert (held[0], held[-1]) == (first, last)
    assert len(held) == last - first + 1 <= RING
    # the global layers hold the full horizon, as before
    assert np.count_nonzero(a["table"]) == -(-(prompt + 64) // BS)
    assert m.window_used_blocks() == len(held)


def test_blocks_are_freed_as_the_sequence_passes_them():
    m = _mgr()
    m.alloc_seq(1, list(range(300)), 400)
    used, freed, seen = [], [], set()
    for length in range(300, 700, STEPS):
        row = m.advance_window(1, length, STEPS)
        held = np.flatnonzero(row)
        # what the dispatch writes and what its first query reaches
        assert held[0] == (length + 1 - WINDOW) // BS
        assert held[-1] == (length + STEPS - 1) // BS
        assert len(held) <= RING
        # a freed block's id may come back, but never at a passed place
        assert not {(b, row[b]) for b in held} & seen - {
            (b, row[b]) for b in held}
        seen |= {(b, int(row[b])) for b in held}
        used.append(m.window_used_blocks())
        freed.append(m.window_freed_total)
    assert max(used) <= RING and min(used) < max(used)  # the gauge falls
    assert freed[-1] == (699 + 1 - WINDOW) // BS - 10   # one a block passed
    assert freed == sorted(freed)
    m.free_seq(1)
    assert m.window_used_blocks() == 0
    assert len(m.wfree) == 2 * RING


def test_gauges_by_kind():
    class Gauge:
        def __init__(self):
            self.v = 0

        def set(self, v):
            self.v = v

        def inc(self, n=1, **kw):
            self.v += n
    metrics = {k: Gauge() for k in (
        "used", "cached", "evicted", "hit_tokens", "window_used",
        "window_freed")}
    m = _mgr(metrics=metrics)
    m.alloc_seq(1, list(range(300)), 100)
    assert metrics["window_used"].v == 9
    assert metrics["used"].v == 25
    for length in range(300, 364, STEPS):
        m.advance_window(1, length, STEPS)
    assert metrics["window_freed"].v == m.window_freed_total == 4
    assert metrics["window_used"].v == m.window_used_blocks()
    m.free_seq(1)
    assert metrics["window_used"].v == 0 and metrics["used"].v == 0


def test_exhaustion_parks_the_admit_and_release_lets_it_in():
    m = _mgr(seqs=2)
    assert m.alloc_seq(1, list(range(300)), 10) is not None
    assert m.alloc_seq(2, list(range(40)), 10) is not None
    # every ring is out: the third waits, whatever it would need now
    assert m.alloc_seq(3, [1, 2, 3], 4) is None
    assert 3 not in m.seqs and 3 not in m.wseqs
    # an admitted sequence never fails mid-flight: both decode on
    for length in range(300, 500, STEPS):
        m.advance_window(1, length, STEPS)
    m.free_seq(2)
    assert m.alloc_seq(3, [1, 2, 3], 4) is not None


def test_prefix_reuse_is_refused_with_window_layers():
    with pytest.raises(ValueError, match="prefix caching"):
        kc.KVBlockManager(32, BS, table_width=8, prefix_cache=True,
                          window=(1 + RING, WINDOW, STEPS))
    with pytest.raises(ValueError, match="blocks a"):
        kc.KVBlockManager(32, BS, table_width=8, prefix_cache=False,
                          window=(RING, WINDOW, STEPS))


def test_a_model_of_global_layers_has_no_second_pool():
    m = kc.KVBlockManager(32, BS, table_width=8)
    a = m.alloc_seq(1, list(range(20)), 10)
    assert "window_table" not in a and m.window is None
    assert m.window_used_blocks() == 0
    m.free_seq(1)
