"""One decode block in flight ahead of the one the loop reads back
(PR 39): the engine enqueues block n + 1 before it reads back block n.
What must not change: every greedy reply, token for token, whatever
ends a request and wherever in a block it ends; what a finished
request's KV blocks may be handed to, and when; the speculative path;
stop(). Pinned against the training-side full forward, one request at
a time, like tests/test_llm.py."""

import asyncio
import time

import pytest

import jax
import jax.numpy as jnp

from ray_tpu.llm import LLMEngine
from ray_tpu.llm.engine import engine_metrics
from ray_tpu.models import llama


@pytest.fixture(scope="module")
def tiny_model():
    cfg = llama.tiny(vocab_size=128, dim=64, n_layers=2, n_heads=4,
                     n_kv_heads=2, ffn_dim=128, dtype="float32",
                     logits_dtype="float32", attn_impl="reference")
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


_REFS: dict = {}
_REF_LEN = 96


def _ref_greedy(cfg, params, prompt, n):
    """The reply one request alone gets from the training model's full
    forward (memoised: the cases share prompts)."""
    key = (tuple(prompt), n)
    if key not in _REFS:
        toks = list(prompt)
        for _ in range(n):
            # one shape for every length: the forward is causal, so
            # what follows a position does not reach it
            padded = toks + [0] * (_REF_LEN - len(toks))
            logits = llama.forward(params, jnp.array([padded], jnp.int32),
                                   cfg)
            toks.append(int(jnp.argmax(logits[0, len(toks) - 1])))
        _REFS[key] = toks[len(prompt):]
    return _REFS[key]


def _engine(tiny_model, **kw):
    cfg, params = tiny_model
    kw = {"max_slots": 3, "max_len": 96, "prefill_buckets": (8, 16),
          "cache_dtype": "float32", "steps_per_sync": 4,
          "kv_block_size": 4, "prefix_cache": False, **kw}
    return LLMEngine(cfg, params, **kw)


def _ahead() -> tuple:
    h = engine_metrics()["decode_ahead"]
    return (sum(h._sums.values()),
            sum(sum(c) for c in h._counts.values()))


def _mid_block(ref, frm, ok):
    """The first reply index >= ``frm`` that is not a block's last step
    (reply index 1-4 is the first block of four, 5-8 the second, ...)
    and where ``ok(i)``: where a request can end with the rest of its
    block wasted and the next block already enqueued for its slot."""
    return next(i for i in range(frm, len(ref)) if i % 4 and ok(i))


PROMPTS = [[3, 7, 11, 19, 2], [5, 6, 7], [9, 9, 4, 1, 8, 2, 6],
           [2, 4, 6, 8, 10, 12, 14, 16, 18], [1, 3]]


def _cases(cfg, params):
    """(prompt, generate kwargs, the reply the reference gives): budgets
    that are no power of two, an eos and a stop sequence that land in
    the middle of a block of four (reply index 1-4 is the first block,
    5-8 the second, ...), and a plain long one that keeps blocks in
    flight under the others."""
    out = []
    for p, new in zip(PROMPTS, (11, 7, 13, 30, 5)):
        out.append((p, {"max_new_tokens": new},
                    _ref_greedy(cfg, params, p, new)))
    # an eos inside a block
    for p, frm in ((PROMPTS[0], 2), (PROMPTS[2], 6), (PROMPTS[3], 3)):
        ref = _ref_greedy(cfg, params, p, 30)
        at = _mid_block(ref, frm, lambda i: ref.index(ref[i]) == i)
        out.append((p, {"max_new_tokens": 30, "eos_id": ref[at]},
                    ref[:at + 1]))
    # a two-token stop sequence that ends inside a block
    for p in (PROMPTS[1], PROMPTS[4]):
        ref = _ref_greedy(cfg, params, p, 30)
        at = _mid_block(ref, 6, lambda i: all(
            ref[j - 1:j + 1] != ref[i - 1:i + 1] for j in range(1, i)))
        out.append((p, {"max_new_tokens": 30, "stop": [ref[at - 1:at + 1]]},
                    ref[:at - 1]))
    return out


@pytest.mark.parametrize("stagger_s", [0.0, 0.004, 0.03])
def test_greedy_replies_are_the_references_with_a_block_in_flight(
        tiny_model, stagger_s):
    """Ten requests through three slots, arriving together or a few
    milliseconds apart (so some join while a block is in flight):
    every reply is what the request gets alone."""
    cfg, params = tiny_model
    cases = _cases(cfg, params)

    async def one(eng, i, prompt, kw):
        await asyncio.sleep(i * stagger_s)
        return await eng.generate(prompt, **kw)

    async def go():
        eng = _engine(tiny_model)
        s0, n0 = _ahead()
        outs = await asyncio.gather(*[
            one(eng, i, p, kw) for i, (p, kw, _) in enumerate(cases)])
        await eng.stop()
        s1, n1 = _ahead()
        return outs, s1 - s0, n1 - n0, eng

    outs, ahead, blocks, eng = asyncio.run(go())
    for (p, kw, want), o in zip(cases, outs):
        assert o["tokens"] == want, (p, kw)
    # the mechanism engaged: most blocks went out ahead of a read-back
    assert blocks > 0 and ahead / blocks > 0.5, (ahead, blocks)
    # ... and every block was given back
    assert eng._inflight is None
    assert eng._kv.used_blocks() == 0 and not eng._kv.seqs


def test_a_deadline_expiring_with_a_block_in_flight(tiny_model):
    """The expired request fails typed, at a block boundary, while its
    neighbour's reply stays the reference's; its slot and blocks come
    back and serve the next request."""
    from ray_tpu.serve.fault import DeadlineExceeded
    cfg, params = tiny_model
    long_ref = _ref_greedy(cfg, params, PROMPTS[3], 60)

    async def go():
        eng = _engine(tiny_model, max_slots=2, max_len=512)
        # warm the shapes: the deadline is to fall between two blocks,
        # not inside a compile
        for p in (PROMPTS[3], PROMPTS[0]):
            await eng.generate(p, max_new_tokens=9)
        other = asyncio.ensure_future(
            eng.generate(PROMPTS[3], max_new_tokens=60))
        with pytest.raises(DeadlineExceeded) as exc:
            await eng.generate(PROMPTS[0], max_new_tokens=480,
                               deadline_ts=time.time() + 0.2)
        # the slot is free again while the neighbour still decodes
        nxt = await eng.generate(PROMPTS[1], max_new_tokens=7)
        got = await other
        await eng.stop()
        return got, nxt, eng, str(exc.value)

    got, nxt, eng, said = asyncio.run(go())
    # cancelled mid-generation, where a block is always in flight
    assert 1 <= int(said.split("after ")[1].split()[0]) < 480, said
    assert got["tokens"] == long_ref
    assert nxt["tokens"] == _ref_greedy(cfg, params, PROMPTS[1], 7)
    assert eng._kv.used_blocks() == 0 and not eng._kv.seqs


def test_blocks_of_a_request_that_ended_mid_block_are_held(tiny_model):
    """Point 3 of the issue. A request ends on an eos inside block n
    while block n + 1, enqueued with its tables, is in flight: its
    blocks stay its own (not free, not in the prefix index) until that
    block has been read back; then its chain is cached, and a
    follow-up turn that shares the prefix is served the right KV."""
    cfg, params = tiny_model
    prompt = PROMPTS[3]                     # 9 tokens: 2 full blocks of 4
    ref = _ref_greedy(cfg, params, prompt, 30)
    at = _mid_block(ref, 2, lambda i: ref.index(ref[i]) == i)

    async def go():
        eng = _engine(tiny_model, prefix_cache=True)
        first = await eng.generate(prompt, max_new_tokens=30,
                                   eos_id=ref[at])
        # the reply is out; the block enqueued before the host saw the
        # eos is still in flight and holds the release
        fl = eng._inflight
        held_at_finish = (fl is not None and len(fl.held),
                          len(eng._kv.seqs), eng._kv.used_blocks(),
                          eng._kv.cached_blocks())
        for _ in range(200):
            if eng._inflight is None and not eng._kv.seqs:
                break
            await asyncio.sleep(0.005)
        after = (len(eng._kv.seqs), eng._kv.used_blocks(),
                 eng._kv.cached_blocks())
        turn2 = prompt + first["tokens"] + [5, 17, 33]
        second = await eng.generate(turn2, max_new_tokens=9)
        await eng.stop()
        return first, held_at_finish, after, turn2, second

    first, held, after, turn2, second = asyncio.run(go())
    assert first["tokens"] == ref[:at + 1]
    assert held[0] == 1 and held[1] == 1 and held[2] > 0 and held[3] == 0
    # released once the block was read back: nothing used, the chain of
    # full blocks (prompt + reply, less the last token) cached
    assert after[0] == 0 and after[1] == 0
    assert after[2] == (len(prompt) + at) // 4
    assert second["prefix_hit_tokens"] == after[2] * 4
    assert second["tokens"] == _ref_greedy(cfg, params, turn2, 9)


def test_a_held_release_keeps_the_blocks_from_the_next_admit(tiny_model):
    """A pool with room for ONE request's horizon: the request that
    follows one ended mid-block is admitted only once the block in
    flight has been read back and the blocks released, and its reply
    is the reference's (no block was written under it)."""
    cfg, params = tiny_model
    prompt = PROMPTS[2]
    ref = _ref_greedy(cfg, params, prompt, 30)
    at = _mid_block(ref, 2, lambda i: ref.index(ref[i]) == i)
    want = _ref_greedy(cfg, params, PROMPTS[0], 30)

    async def go():
        # 7 + 30 positions = 10 blocks of 4, and the trash block
        eng = _engine(tiny_model, kv_pool_blocks=11)
        a = asyncio.ensure_future(eng.generate(
            prompt, max_new_tokens=30, eos_id=ref[at]))
        b = asyncio.ensure_future(eng.generate(
            PROMPTS[0], max_new_tokens=30))
        outs = await asyncio.gather(a, b)
        await eng.stop()
        return outs, eng

    (a, b), eng = asyncio.run(go())
    assert a["tokens"] == ref[:at + 1]
    assert b["tokens"] == want
    assert eng._kv.used_blocks() == 0


def test_the_pd_decode_path_with_a_block_in_flight(tiny_model):
    """Shipped KV is written at admission, before any block names the
    slot: prefilled requests joining a decoding one reply as the
    reference does."""
    from ray_tpu.llm.pd import PrefillEngine
    cfg, params = tiny_model
    cases = [(PROMPTS[0], 11), (PROMPTS[2], 13), (PROMPTS[3], 7)]

    async def go():
        pre = PrefillEngine(cfg, params, prefill_buckets=(8, 16),
                            max_len=96, cache_dtype="float32",
                            block_size=4)
        eng = _engine(tiny_model)
        s0, n0 = _ahead()
        long = asyncio.ensure_future(
            eng.generate(PROMPTS[1], max_new_tokens=40))
        outs = []
        for p, new in cases:
            await asyncio.sleep(0.01)
            outs.append(asyncio.ensure_future(eng.generate_prefilled(
                p, pre.prefill(p), max_new_tokens=new)))
        outs = await asyncio.gather(*outs)
        long = await long
        await eng.stop()
        s1, n1 = _ahead()
        return outs, long, s1 - s0, n1 - n0

    outs, long, ahead, blocks = asyncio.run(go())
    for (p, new), o in zip(cases, outs):
        assert o["tokens"] == _ref_greedy(cfg, params, p, new)
    assert long["tokens"] == _ref_greedy(cfg, params, PROMPTS[1], 40)
    assert ahead > 0 and blocks > ahead


def test_speculative_rounds_stay_synchronous(tiny_model):
    """A drafter reads the host's tokens: an engine whose slots hold
    drafters never leaves a block in flight, verify rounds or plain
    blocks alike, and replies as the plain engine does."""
    cfg, params = tiny_model
    pat = [7, 3, 9, 4, 11, 2, 8, 5]
    prompts = [pat * 3, PROMPTS[3]]

    async def run(spec):
        eng = _engine(tiny_model, spec=spec, max_len=128,
                      prefill_buckets=(8, 16, 32))
        s0, n0 = _ahead()
        outs = await asyncio.gather(*[
            eng.generate(p, max_new_tokens=24) for p in prompts])
        seen = eng._inflight
        await eng.stop()
        s1, n1 = _ahead()
        return [o["tokens"] for o in outs], s1 - s0, n1 - n0, seen

    plain, ahead, blocks, _ = asyncio.run(run(False))
    assert ahead > 0
    spec, ahead, blocks, seen = asyncio.run(run(True))
    assert spec == plain
    assert ahead == 0 and seen is None      # however many plain blocks


def test_stop_with_a_block_in_flight_returns(tiny_model):
    async def go():
        eng = _engine(tiny_model)
        task = asyncio.ensure_future(
            eng.generate(PROMPTS[0], max_new_tokens=80))
        for _ in range(400):                # until blocks are in flight
            if eng._inflight is not None:
                break
            await asyncio.sleep(0.002)
        assert eng._inflight is not None
        await asyncio.wait_for(eng.stop(), timeout=30)
        # the request in flight fails, as it did before this PR
        with pytest.raises(RuntimeError):
            await asyncio.wait_for(task, timeout=30)
        return eng

    eng = asyncio.run(go())
    # the block in flight was dropped, and nothing is held after it
    assert eng._inflight is None
    assert eng._kv.used_blocks() == 0 and not eng._kv.seqs
    with pytest.raises(RuntimeError):
        eng._submit(PROMPTS[0], 4, 0.0, None)
