"""The serving engine's loop on the profiler's clock: the one span
primitive (util/tracing.phase), the flat engine.* leaf spans, and the
work the loop counts where it is done (llm_decode_*, llm_prefill_tokens,
llm_decode_gap_*, a finished request's stalled share). Counts are exact
on the CPU; times are not speed results."""

import asyncio
import glob
import os
import subprocess
import sys
import time

import pytest

from ray_tpu.util import events, metrics as M, tracing


@pytest.fixture(scope="module")
def tiny_model():
    import jax

    from ray_tpu.models import llama
    cfg = llama.tiny(vocab_size=64, dim=32, n_layers=2, n_heads=2,
                     n_kv_heads=2, ffn_dim=64, dtype="float32",
                     logits_dtype="float32", attn_impl="reference")
    return cfg, llama.init_params(jax.random.PRNGKey(0), cfg)


def _engine(tiny_model, **kw):
    from ray_tpu.llm import LLMEngine
    cfg, params = tiny_model
    kw = {"max_slots": 2, "max_len": 64, "prefill_buckets": (8, 16),
          "cache_dtype": "float32", "steps_per_sync": 4,
          "kv_block_size": 8, **kw}
    return LLMEngine(cfg, params, **kw)


def _totals() -> dict:
    """{key_sum, key_count} of every engine histogram, the way the
    benchmark's server reads them, plus the paged step counter."""
    from ray_tpu.llm.engine import engine_metrics
    out = {}
    for key, h in engine_metrics().items():
        if hasattr(h, "boundaries"):
            out[key + "_sum"] = sum(h._sums.values())
            out[key + "_count"] = sum(sum(c) for c in h._counts.values())
    steps = M._REGISTRY.get("llm_paged_attn_steps_total")
    out["attn_steps"] = sum(steps._values.values()) if steps else 0
    return out


def _delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in b}


# --- the primitive -----------------------------------------------------


def test_phase_feeds_histogram_and_records_from_one_stamp():
    h = M.Histogram("test_phase_s", "a phase", boundaries=(1, 2))
    n0, s0 = sum(sum(c) for c in h._counts.values()), \
        sum(h._sums.values())
    before = time.time()
    with tracing.phase("engine.test", h) as ph:
        time.sleep(0.01)
    after = time.time()
    assert ph.t1 - ph.t0 == ph.dur >= 0.01
    # the histogram saw exactly the stamped interval
    assert sum(sum(c) for c in h._counts.values()) == n0 + 1
    assert sum(h._sums.values()) - s0 == pytest.approx(ph.dur, abs=1e-12)
    # ... and a wall-clock record made from the same stamps has the
    # same duration and sits where the wall clock says the phase ran
    w0, w1 = tracing.wall(ph.t0), tracing.wall(ph.t1)
    assert w1 - w0 == pytest.approx(ph.dur, abs=1e-6)
    assert before - 0.05 <= w0 <= w1 <= after + 0.05
    with tracing.phase("engine.bare") as bare:     # no histogram: fine
        pass
    assert bare.dur >= 0


def test_phase_passes_exceptions_and_still_observes():
    h = M.Histogram("test_phase_exc_s", "a phase", boundaries=(1,))
    n0 = sum(sum(c) for c in h._counts.values())
    with pytest.raises(KeyError):
        with tracing.phase("engine.test", h):
            raise KeyError("x")
    assert sum(sum(c) for c in h._counts.values()) == n0 + 1


def test_phase_does_not_import_jax():
    code = ("import sys\n"
            "from ray_tpu.util import tracing\n"
            "with tracing.phase('engine.x') as ph:\n"
            "    pass\n"
            "assert ph.dur >= 0 and ph._ann is None\n"
            "assert 'jax' not in sys.modules, 'phase imported jax'\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120,
                   cwd=os.path.join(os.path.dirname(__file__), os.pardir))


# --- work counted where it is done ---------------------------------------


def test_decode_counts_match_their_closed_forms(tiny_model):
    """Two known prompts, nothing stops early: every count is exact."""
    prompts = [[3, 5, 7, 11, 13], [2, 4, 6, 8, 10, 12, 14, 16, 18]]
    new = 11

    async def go():
        eng = _engine(tiny_model, prefix_cache=False)
        before = _totals()
        outs = await asyncio.gather(*[
            eng.generate(p, max_new_tokens=new) for p in prompts])
        await eng.stop()
        return outs, _delta(before, _totals())

    outs, d = asyncio.run(go())
    assert [len(o["tokens"]) for o in outs] == [new, new]
    generated = sum(len(o["tokens"]) for o in outs)
    # a block's steps are the paged path's steps, one for one
    assert d["block_steps_sum"] == d["attn_steps"] > 0
    assert d["block_steps_count"] == d["batch_count"]
    assert d["block_steps_sum"] / d["block_steps_count"] <= 4
    # every token but a request's first is one step of one slot
    assert d["slot_steps_sum"] == generated - len(prompts)
    # output token i >= 1 of a P-token prompt attends P + i positions
    assert d["ctx_tokens_sum"] == sum(
        len(p) + i for p in prompts for i in range(1, new))
    # both prompts went through a prefill forward, whole
    assert d["prefill_tokens_sum"] == sum(len(p) for p in prompts)
    assert d["prefill_tokens_count"] == len(prompts)
    # the gap between blocks holds the admissions made in it
    assert d["gap_count"] > 0
    assert 0 <= d["gap_admit_sum"] <= d["gap_sum"]
    assert d["gap_admit_count"] == d["gap_count"]


def test_prefill_tokens_exclude_prefix_hits(tiny_model):
    prompt = list(range(1, 21))     # 20 tokens: two full blocks of 8

    async def go():
        eng = _engine(tiny_model, prefix_cache=True)
        before = _totals()
        cold = await eng.generate(prompt, max_new_tokens=4)
        mid = _totals()
        warm = await eng.generate(prompt, max_new_tokens=4)
        await eng.stop()
        return cold, warm, _delta(before, mid), _delta(mid, _totals())

    cold, warm, d_cold, d_warm = asyncio.run(go())
    assert cold["prefix_hit_tokens"] == 0
    hit = warm["prefix_hit_tokens"]
    assert hit == 16 and warm["tokens"] == cold["tokens"]
    assert d_cold["prefill_tokens_sum"] == len(prompt)
    assert d_warm["prefill_tokens_sum"] == len(prompt) - hit


def test_every_loop_phase_is_observed_and_stream_lag_counts_tokens(
        tiny_model):
    from ray_tpu.llm.engine import PHASES

    async def go():
        eng = _engine(tiny_model, prefix_cache=False)
        before = _totals()
        t0 = time.monotonic()
        got = [t async for t in eng.generate_stream(
            [5, 6, 7], max_new_tokens=9)]
        await asyncio.sleep(0.05)       # the loop parks: engine.idle
        await eng.stop()
        return got, _delta(before, _totals()), time.monotonic() - t0

    got, d, wall = asyncio.run(go())
    assert len(got) == 9
    assert d["stream_lag_count"] == 9 and d["stream_lag_sum"] >= 0
    for p in PHASES:
        key = "loop_" + p.replace(".", "_")
        # no speculative decoding here, and the one admission found no
        # decode block in flight to wait behind
        if p.startswith("verify.") or p == "prefill.behind":
            assert d[key + "_count"] == 0
        else:
            assert d[key + "_count"] > 0, p
    # leaf spans do not overlap, so together they fit in the wall time
    spent = sum(v for k, v in d.items()
                if k.startswith("loop_") and k.endswith("_sum"))
    assert 0 < spent <= wall


@pytest.mark.parametrize("spec", [False, True])
def test_a_block_in_flight_is_counted_once_a_block(tiny_model, spec,
                                                   monkeypatch):
    """PR 39. ``decode_ahead`` is observed once a block: 0 for the
    first block after idle (nothing is in flight), 1 for a block
    enqueued before its predecessor was read back. ``gap`` is still
    observed once a block that carries a request on and is never
    negative: 0 when the block went out ahead. No two blocks' windows
    (``batch`` spans, device windows) overlap. An engine whose slots
    hold drafters (``spec``) leaves nothing in flight: every block 0,
    every gap the stall it was before."""
    seen = {"llm_decode_ahead_size": [], "llm_decode_gap_s": [],
            "llm_decode_gap_admit_s": [], "llm_tpot_s": []}
    real = M.Histogram.observe

    def observe(self, value, *a, **kw):
        if self.name in seen:
            seen[self.name].append(value)
        return real(self, value, *a, **kw)
    monkeypatch.setattr(M.Histogram, "observe", observe)
    tid = "3a" * 16

    async def go():
        eng = _engine(tiny_model, prefix_cache=False, spec=spec)
        before = _totals()
        tok = tracing.set_request_context(
            tracing.TraceContext(tid, tracing.new_span_id()))
        try:
            # 12 steps in blocks of 4, 4, 4; the loop parks; 5 in 4, 1
            await eng.generate([3, 5, 7, 11], max_new_tokens=13)
            await asyncio.sleep(0.05)
            await eng.generate([2, 9, 4], max_new_tokens=6)
        finally:
            tracing.reset_request_context(tok)
        await eng.stop()
        return _delta(before, _totals())

    events.clear()
    d = asyncio.run(go())
    ahead = seen["llm_decode_ahead_size"]
    gaps, admits = seen["llm_decode_gap_s"], seen["llm_decode_gap_admit_s"]
    assert d["decode_ahead_count"] == d["block_steps_count"] == len(ahead)
    assert len(gaps) == len(admits)
    assert all(0 <= a <= g for a, g in zip(admits, gaps))
    spans = sorted((e["ts"], e["dur"], e["block"]) for e in events.dump()
                   if e.get("name") == "batch" and tid in e["links"])
    wins = sorted((e["ts"], e["dur"]) for e in events.dump()
                  if e.get("cat") == "device_window"
                  and e.get("seg") == "decode")
    # the blocks' (and verify rounds') windows: none overlaps the next
    assert spans and [(t, dur) for t, dur, _ in spans] == wins
    assert all(t0 + dur <= t1 + 1e-9 for (t0, dur, _), (t1, _, _)
               in zip(spans, spans[1:]))
    if spec:
        # however the drafters split the work into rounds and blocks
        assert ahead and not any(ahead)
        assert gaps and all(g > 0 for g in gaps)
    else:
        assert ahead == [0, 1, 1, 0, 1] and d["block_steps_sum"] == 17
        # one gap a block that carries a request on, none a stall
        assert gaps == [0, 0, 0]
        # llm_tpot_s is each block's own window over its steps
        assert len(spans) == 5
        assert sorted(seen["llm_tpot_s"]) == pytest.approx(
            sorted(dur / n for _, dur, n in spans), abs=1e-6)
    events.clear()


# --- the stall between two blocks: a block, a step, a finished request ----


@pytest.mark.parametrize("case", ["admitted_while_decoding", "alone",
                                  "spec"])
def test_a_slot_stalls_where_an_admission_came_between_two_blocks(
        tiny_model, case, monkeypatch):
    """PR 56. A request admitted while another decodes has its prefill
    enqueued between the block in flight and the next: the admission
    waits for that block (``prefill.behind``, once an admission that
    found one), its exit is where the decoding slot's stall starts, and
    the stall is counted a block (``gap`` / ``gap_admit``), inside the
    blocks' windows (``block_window``) and on the finished request
    (``stall_s`` / ``stall_admit_s`` / ``tpot_stall_s`` of its generate
    span, ``request_tpot_stall``). A request that decodes alone stalls
    nowhere, and an engine whose slots hold drafters (``spec``) leaves
    nothing in flight: no admission waits behind a block, and its gaps
    are the read-back-to-launch ones they were."""
    from ray_tpu.llm import LLMEngine
    found = []      # an admission: was a decode block in flight?
    real = LLMEngine._first_token

    def first_token(self, *a, **kw):
        found.append(self._inflight is not None)
        return real(self, *a, **kw)
    monkeypatch.setattr(LLMEngine, "_first_token", first_token)
    tids = {"long": "6a" * 16, "late": "6b" * 16}

    async def traced(eng, who, prompt, new):
        tok = tracing.set_request_context(
            tracing.TraceContext(tids[who], tracing.new_span_id()))
        try:
            return await eng.generate(prompt, max_new_tokens=new)
        finally:
            tracing.reset_request_context(tok)

    async def go():
        eng = _engine(tiny_model, prefix_cache=False, spec=case == "spec")
        await eng.generate([9, 8, 7], max_new_tokens=6)     # compile
        found.clear()
        events.clear()
        before = _totals()
        base = eng.stats["tokens_generated"]
        long = asyncio.ensure_future(
            traced(eng, "long", [3, 5, 7, 11], 58))
        if case != "alone":
            # the first block has been read back: from here to the long
            # request's end a plain engine always has one in flight
            while eng.stats["tokens_generated"] < base + 5:
                await asyncio.sleep(0)
            await traced(eng, "late", [2, 9, 4], 6)
        await long
        await eng.stop()
        return _delta(before, _totals())

    d = asyncio.run(go())
    spans = {e["trace"]: e for e in events.dump()
             if e.get("cat") == "request" and e.get("seg") == "generate"}
    events.clear()
    long, late = spans[tids["long"]], spans.get(tids["late"])
    assert d["loop_prefill_behind_count"] == sum(found)
    assert d["block_window_count"] == d["batch_count"] > 0
    assert d["block_window_sum"] >= d["gap_sum"] >= d["gap_admit_sum"] >= 0
    assert d["gap_count"] == d["gap_admit_count"]
    for e in spans.values():
        n = e["tokens"] - 1
        assert 0 <= e["stall_admit_s"] <= e["stall_s"] <= d["gap_sum"] + 1e-9
        # the stalls lie between the request's first and last emit
        assert e["stall_s"] <= e["tpot_s"] * n + 1e-9
        assert e["tpot_stall_s"] == pytest.approx(e["stall_s"] / n)
        assert "stalled between blocks" in tracing.stream_attrs(e)
    assert d["request_tpot_stall_count"] == len(spans)
    assert d["request_tpot_stall_sum"] == pytest.approx(
        sum(e["tpot_stall_s"] for e in spans.values()), abs=1e-9)
    if case == "admitted_while_decoding":
        assert found == [False, True] and d["gap_count"] > 0
        assert 0 < d["gap_admit_sum"] < d["gap_sum"]
        # every gap fell after the long request's mark; the late one's
        # was taken after the one gap it was admitted in
        assert long["stall_s"] == pytest.approx(d["gap_sum"], abs=1e-9)
        assert long["stall_admit_s"] == pytest.approx(
            d["gap_admit_sum"], abs=1e-9)
        assert late["stall_s"] == 0 == late["tpot_stall_s"]
    elif case == "alone":
        assert found == [False] and late is None and d["gap_count"] > 0
        assert d["gap_sum"] == 0 == d["loop_prefill_behind_sum"]
        assert long["stall_s"] == 0 == long["tpot_stall_s"]
        assert d["request_tpot_stall_sum"] == 0
    else:
        assert found == [False, False]
        assert d["loop_prefill_behind_sum"] == 0


@pytest.mark.parametrize("name, accrues", [
    ("admit.alloc", True), ("prefill.dispatch", True),
    ("prefill.behind", False), ("prefill.wait", True),
    ("prefill.sample", True), ("decode.prepare", False)])
def test_admission_phases_accrue_to_the_gap_but_the_wait_behind_a_block(
        tiny_model, name, accrues):
    """``prefill.behind`` is the decode block's own time: the gap starts
    at its exit, so it is no part of the gap's admission share."""
    eng = _engine(tiny_model, prefix_cache=False)
    eng._gap_admit = 0.0
    with eng._phase(name) as ph:
        time.sleep(0.001)
    assert eng._gap_admit == (ph.dur if accrues else 0.0)


def test_the_block_s_end_is_looked_for_without_waiting_and_taken_once(
        tiny_model):
    """An admission looks whether the block in flight has ended (a
    chunk's launch can sit it out inside ``prefill.dispatch``): a look
    that finds it running changes nothing, one that finds it ended
    stamps the block, and from there on the phase's seconds, not the
    whole phase, count as the gap's admission share."""
    import types

    class Out:
        ready = False

        def is_ready(self):
            return self.ready
    eng = _engine(tiny_model, prefix_cache=False)
    blk = eng._inflight = types.SimpleNamespace(out=Out(), t_end=0.0)
    eng._gap_admit = 0.5
    eng._block_ended(time.monotonic())
    assert blk.t_end == 0.0 and eng._gap_admit == 0.5
    blk.out.ready = True
    with eng._phase("prefill.dispatch") as ph:
        time.sleep(0.002)
        eng._block_ended(ph.t0)
        time.sleep(0.002)
    assert ph.t0 < blk.t_end < ph.t1
    assert eng._gap_admit == pytest.approx(ph.t1 - blk.t_end, abs=1e-9)
    at = blk.t_end
    eng._block_ended(0.0, at=at + 1.0)      # once a block
    assert blk.t_end == at
    eng._inflight = None
    eng._block_ended(0.0)                   # nothing in flight: nothing


def test_spec_round_uses_the_verify_phases(tiny_model):
    tid = "5e" * 16

    async def go():
        eng = _engine(tiny_model, spec=True, prefix_cache=False)
        before = _totals()
        tok = tracing.set_request_context(
            tracing.TraceContext(tid, tracing.new_span_id()))
        try:
            # a repeating prompt, so the prompt-lookup drafter proposes
            await eng.generate([1, 2, 3, 1, 2, 3, 1, 2, 3, 1, 2],
                               max_new_tokens=12)
        finally:
            tracing.reset_request_context(tok)
        await eng.stop()
        return _delta(before, _totals())

    events.clear()
    d = asyncio.run(go())
    rounds = d["loop_verify_dispatch_count"]
    assert rounds > 0
    assert d["loop_verify_readback_count"] == rounds \
        == d["loop_verify_accept_count"]
    assert d["loop_verify_prepare_count"] >= rounds
    # each round left its linked span and its device window, from the
    # round's own dispatch-to-read-back interval
    spans = [e for e in events.dump()
             if e.get("name") == "batch" and "spec_k" in e]
    assert len(spans) == rounds and all(tid in e["links"] for e in spans)
    wins = [e for e in events.dump() if e.get("cat") == "device_window"
            and e.get("seg") == "decode"]
    assert {(e["ts"], e["dur"]) for e in spans} \
        <= {(e["ts"], e["dur"]) for e in wins}
    events.clear()


# --- the spans in the profiler's trace -----------------------------------


def test_engine_spans_land_in_the_profiler_trace_and_do_not_nest(
        tiny_model, tmp_path):
    import jax
    from jax.profiler import ProfileData

    async def go():
        eng = _engine(tiny_model, prefix_cache=False)
        await eng.generate([9, 8, 7], max_new_tokens=6)     # compile
        jax.profiler.start_trace(str(tmp_path))
        try:
            # of three requests on two slots the third is admitted when
            # the shortest ends, behind the block the other decodes in
            await asyncio.gather(*[
                eng.generate([3 + i, 5, 7, 9], max_new_tokens=10 + 8 * i)
                for i in range(3)])
        finally:
            jax.profiler.stop_trace()
        await eng.stop()

    asyncio.run(go())
    files = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert files
    pd = ProfileData.from_file(files[0])
    names, nested = set(), []
    for plane in pd.planes:
        for line in plane.lines:
            spans = sorted(
                (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                for ev in line.events if ev.name.startswith("engine."))
            names.update(n for _, _, n in spans)
            nested += [(a, b) for a, b in zip(spans, spans[1:])
                       if b[0] < a[1]]
    assert {"engine.decode.prepare", "engine.decode.dispatch",
            "engine.decode.readback", "engine.prefill.dispatch",
            "engine.prefill.behind", "engine.prefill.wait",
            "engine.prefill.sample",
            "engine.emit", "engine.yield"} <= names, names
    assert not nested, nested[:3]


# --- the record of a slow phase --------------------------------------------


def test_a_phase_over_a_second_leaves_one_slow_phase_event(
        tiny_model, monkeypatch):
    from ray_tpu.llm import LLMEngine

    async def go():
        eng = _engine(tiny_model, prefix_cache=False)
        await eng.generate([4, 5, 6], max_new_tokens=6)     # compile
        events.clear()
        slow = LLMEngine._sample_one

        def sample_slowly(self, logits, r):
            time.sleep(1.05)
            return slow(self, logits, r)
        monkeypatch.setattr(LLMEngine, "_sample_one", sample_slowly)
        await eng.generate([6, 5, 4], max_new_tokens=6)
        await eng.stop()

    asyncio.run(go())
    slow = [e for e in events.dump()
            if e.get("cat") == "engine" and e.get("name") == "slow_phase"]
    assert len(slow) == 1, slow
    assert slow[0]["phase"] == "prefill.sample"
    assert 1.05 <= slow[0]["dur"] < 5
    assert slow[0]["active"] == 1 and slow[0]["waiting"] == 0
    events.clear()
