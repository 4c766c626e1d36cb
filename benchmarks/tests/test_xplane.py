"""The trace reduction on a small trace recorded on a TPU v5e (PR 23):
two runs of a 2-layer paged-decode scan at 4 slots, a flash forward +
backward at (8 heads, 512, 128), and a flash forward alone, with sleeps
between them."""
import os

import pytest

from harness import kernels, xplane

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "small.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return xplane.reduce_file(TRACE)


def test_busy_and_idle(reduced):
    assert reduced["n_devices"] == 1
    assert reduced["window_s"] == pytest.approx(0.069063301, rel=1e-6)
    # the while loop CONTAINS its body's ops: busy is a union, 1.0 ms,
    # not the 1.6 ms the events sum to
    assert reduced["busy_s"] == pytest.approx(0.0010042, rel=1e-4)
    assert reduced["idle_share"] == pytest.approx(0.98546, abs=1e-4)
    # the sleeps between the three programs are the longest gaps
    assert [round(g, 3) for _, g in reduced["idle_gaps"][:4]] == [
        0.022, 0.022, 0.011, 0.011]


def test_kernels_are_found_by_signature(reduced):
    k = reduced["kernels"]
    # 2 layers x 2 runs of the decode scan, 135.17 us a call
    assert k["paged_decode"]["calls"] == 4
    assert k["paged_decode"]["s"] == pytest.approx(540.675e-6, rel=1e-5)
    assert k["paged_decode"]["q_shape"] == [4, 8, 4, 128]
    assert k["flash_fwd"]["calls"] == 4        # 2 in grad, 2 alone
    assert k["flash_fwd"]["q_shape"] == [8, 512, 128]
    assert k["flash_bwd_dq"]["calls"] == 2
    assert k["flash_bwd_dkv"]["calls"] == 2
    assert "unknown_kernel" not in k


def test_programs_are_classified_by_the_kernel_inside(reduced):
    p = reduced["programs"]
    assert p["decode"]["calls"] == 2 and p["train"]["calls"] == 2
    assert p["prefill"]["calls"] == 2
    assert p["decode"]["s"] == pytest.approx(580.974e-6, rel=1e-4)
    top = reduced["device_ops"][0]
    assert top[0].startswith("paged_decode:closed_call")
    assert top[1] == pytest.approx(540.675e-6, rel=1e-5)


def test_parse_op():
    op = kernels.parse_op(
        '%closed_call.4 = f32[4,8,4,128]{3,2,1,0:T(4,128)S(1)} custom-call('
        's32[4,16]{1,0:T(4,128)S(1)} %a, s32[4]{0:T(128)S(1)} %b, '
        'bf16[4,8,4,128]{3,2,1,0} %c, bf16[65,8,16,128]{3,2,1,0} %d, '
        'bf16[65,8,16,128]{3,2,1,0} %e), custom_call_target='
        '"tpu_custom_call", operand_layout_constraints={s32[4,16]{1,0}}')
    assert op["name"] == "closed_call.4" and op["opcode"] == "custom-call"
    assert op["result"] == [("f32", (4, 8, 4, 128))]
    assert len(op["operands"]) == 5
    assert kernels.classify(op) == "paged_decode"
    assert kernels.short_name(op) == \
        "closed_call.4_custom-call_f32_4_8_4_128"
    tup = kernels.parse_op(
        '%t.2 = (bf16[8,512,128]{2,1,0}, bf16[8,512,128]{2,1,0}) '
        'custom-call(' + ", ".join(["bf16[8,512,128]{2,1,0} %x"] * 6)
        + '), custom_call_target="tpu_custom_call"')
    assert kernels.classify(tup) == "flash_bwd_dkv"
    ar = kernels.parse_op("%all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} "
                          "%p), replica_groups={}")
    assert ar["opcode"] == "all-reduce" and kernels.classify(ar) is None


def test_required_ops_and_bytes_by_hand():
    # causal pairs: 512 queries over themselves; 2048 queries that are
    # the last rows of a 4096-token context
    assert kernels.causal_pairs(512) == 512 * 513 // 2 == 131328
    assert kernels.causal_pairs(2048, 4096) == 2048 * 2048 + 2098176
    # flash forward, 8 heads of 128: QK^T and PV, 2*128 flops a pair each
    assert kernels.flash_fwd_flops(131328, 8, 128) == 537_919_488
    assert kernels.flash_bwd_flops(131328, 8, 128) == 1_344_798_720
    # Q and O at 32 heads, K and V at 8, bf16, 1000 tokens
    assert kernels.flash_fwd_bytes(1000, 1000, 32, 8, 128) == \
        2 * 128 * (2 * 1000 * 32 + 2 * 1000 * 8)
    # paged decode, one slot, one step, 100 live positions, 8 kv heads
    # of 128, group 4: K and V 2*100*8*128*2 B; q bf16 + o f32
    assert kernels.paged_decode_bytes(100, 1, 8, 4, 128) == \
        409_600 + 24_576
    assert kernels.paged_decode_flops(100, 8, 4, 128) == 1_638_400


def test_interval_arithmetic():
    u = xplane._union([(0, 5), (3, 8), (10, 12), (12, 13)])
    assert u == [[0, 8], [10, 13]]
    assert xplane._subtract([[0, 10]], [[2, 3], [5, 20]]) == \
        [[0, 2], [3, 5]]
    assert xplane._subtract([[0, 4], [6, 9]], []) == [[0, 4], [6, 9]]


def test_readers_that_count_from_the_trace_and_the_client(reduced):
    """Decode steps come from the trace itself (kernel calls / layers);
    what the engine's public counters lack comes from the client's
    token stamps cut at the trace's edges."""
    from types import SimpleNamespace as R

    from harness import spec
    from harness.window import client_counts
    # the recording: 2 runs of a 2-layer scan of ONE step each -> 4
    # kernel calls are 2 steps; 580.974 us of decode programs
    ctx = {"trace": reduced, "model": {"num_hidden_layers": 2}}
    assert spec.reader("decode_dev_ms_per_step")(ctx) == \
        pytest.approx(0.580974 / 2, rel=1e-4)
    assert spec.reader("decode_dev_ms_per_step")({"trace": None}) is None
    # a 10-token prompt whose first token falls inside the edges counts
    # 10 tokens and 55 causal pairs; its 2nd token (index 1) is one
    # slot-step over 11 positions; the 3rd falls outside
    reqs = [R(prompt_len=10, t_tokens=[1.0, 2.0, 3.0]),
            R(prompt_len=7, t_tokens=[0.1, 2.2])]
    assert client_counts(reqs, (0.5, 2.5)) == dict(
        prefill_tokens=10, prefill_pairs=55, decode_slot_steps=2,
        decode_ctx_tokens=11 + 8)
    ratio = spec.reader("counter_ratio")
    c = {"counters": {"window": {"tokens_generated": 110, "ttft_count": 10,
                                 "batch_sum": 50, "batch_count": 0}}}
    assert ratio(c, num="tokens_generated", minus="ttft_count",
                 den="batch_sum") == 2.0
    assert ratio(c, num="batch_sum", den="batch_count") is None


def test_public_histogram_totals():
    from harness.server import _histogram_totals
    from ray_tpu.util import metrics
    h = metrics.Histogram("bench_test_hist_s", "test", boundaries=(1, 2))
    h.observe(0.5)
    h.observe(1.5, tags=None)
    h.observe(4.0)
    assert _histogram_totals(h) == (6.0, 3.0)
