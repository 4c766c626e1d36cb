"""One regime for a cell's whole window (PR 49): a configuration that
lists ``frozen_leaves`` trains with those leaves standing still, a
traffic file that has ``order_seed`` offers every seed the same arrivals
in the same order, and a file without either key is run as before, to
the digit. CPU, rehearsal widths."""
import hashlib
import json
import os
import time

import pytest

from harness import model as hmodel, spec, traffic

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEEDS = (1, 7, 3_000_000_001)
TRAIN_CELLS = [w["name"] for w in spec.benchmark()["workloads"]
               if spec.cell(w["name"])["model"]["deployment"]["kind"]
               == "train"]


def mix(name):
    with open(os.path.join(BENCH_DIR, "traffic", name + ".json")) as f:
        return json.load(f)


def digest(x) -> str:
    return hashlib.sha256(repr(x).encode()).hexdigest()[:16]


# --- order_seed ------------------------------------------------------------

def test_order_seed_gives_every_seed_the_same_arrivals_in_the_same_order():
    p = mix("reason-open")
    assert p["order_seed"] == 0
    orders = [traffic.order(p, s) for s in SEEDS]
    dues = [traffic.arrivals(p, s, 75.0) for s in SEEDS]
    assert orders[0] == orders[1] == orders[2]
    assert dues[0] == dues[1] == dues[2]
    # what the file's own number draws, which is what --seed 0 drew
    # before the key: the key moved nothing else
    bare = {k: v for k, v in p.items() if k != "order_seed"}
    assert orders[0] == traffic.order(bare, 0)
    assert dues[0] == traffic.arrivals(bare, 0, 75.0)
    assert traffic.order(bare, 1) != orders[0]
    # the issue's parameters stand: 61 gaps of a 50-s cycle at 1.22 / s,
    # 96 pairs in rounds of 12 that each hold every prompt length
    assert (p["rate_per_s"], p["arrival_gaps"], p["round"],
            p["steady_s"]) == (1.22, 61, 12, 20.0)
    assert len([t for t in dues[0] if t <= 50.0 + 1e-6]) == 61
    prompts = sorted({q for q, _ in p["pairs"]})
    for i in range(0, 96, 12):
        assert sorted(q for q, _ in orders[0][i:i + 12]) == prompts
    # token ids (and the weights) still come from --seed
    ids = [traffic.prompt_tokens(s, 0, orders[0][0][0], 19200)
           for s in SEEDS]
    assert ids[0] != ids[1] != ids[2] != ids[0]


def test_order_seed_holds_a_closed_loops_staggers_and_cuts_too():
    p = dict(mix("docs-batch"), order_seed=5)
    assert traffic.staggers(p, 1) == traffic.staggers(p, 2) \
        == traffic.staggers(mix("docs-batch"), 5)
    assert traffic.first_cuts(p, 1) == traffic.first_cuts(p, 2) \
        == traffic.first_cuts(mix("docs-batch"), 5)
    assert traffic.order(p, 1) == traffic.order(p, 2)


# what the parent's generator (PR 47's tree) gives for the files that have
# no ``order_seed``: [first three of the order, digest of the order,
# then for an open loop the first three due times, their count up to
# 75 s and their digest; for a closed loop the digests of the staggers
# and of the first cuts]
PARENT = {
    ("chat-open", 1): ([(763, 176), (189, 16), (91, 136)],
                       "82c026c0bcaeaa34",
                       [0.5710654973317241, 1.189904797767685,
                        1.2737085667068], 96, "587f9a4afd58c05b"),
    ("chat-open", 7): ([(58, 256), (91, 16), (763, 40)],
                       "56210fa8ec622c3c",
                       [1.2441934243653405, 1.471516690823103,
                        1.9555187264871243], 97, "640dda789ead9fa2"),
    ("chat-open", 3_000_000_001): ([(91, 40), (263, 176), (452, 16)],
                                   "7d5918618fc266a9",
                                   [1.6162222927208756, 1.6653390601802613,
                                    2.259899800320551], 107,
                                   "5efe982ee4c5c2a2"),
    ("docs-batch", 1): ([(3837, 136), (2429, 48), (2043, 120)],
                        "9c1379640ac8103a",
                        "c44e8a44b981b2c1", "abf4c1b7b1ab515c"),
    ("docs-batch", 7): ([(1801, 160), (2043, 48), (3837, 64)],
                        "5fe2924df47db745",
                        "24a53b0973ba3740", "00d55b9ebb64bbcc"),
    ("docs-batch", 3_000_000_001): ([(2043, 64), (2822, 136), (3331, 48)],
                                    "a49af22988ecfe97",
                                    "624f56c533567af6", "a9aea6b460e259b5"),
}


@pytest.mark.parametrize("name,seed", sorted(PARENT))
def test_a_file_without_the_key_is_offered_as_the_parent_offered_it(name,
                                                                    seed):
    p = mix(name)
    assert "order_seed" not in p
    want = PARENT[name, seed]
    o = traffic.order(p, seed)
    assert (o[:3], digest(o)) == want[:2]
    if p["kind"] == "open":
        a = traffic.arrivals(p, seed, 75.0)
        assert (a[:3], len(a), digest(a)) == want[2:]
    else:
        assert (digest(traffic.staggers(p, seed)),
                digest(traffic.first_cuts(p, seed))) == want[2:]


# --- frozen_leaves ---------------------------------------------------------

class _Reached(Exception):
    pass


@pytest.mark.parametrize("name", TRAIN_CELLS)
def test_what_reaches_make_train_step(name, monkeypatch):
    """A configuration without ``frozen_leaves`` reaches the program
    with ``optimizer=None``: the program builds its own default, so the
    step it compiles is the one it compiled before the key existed."""
    import jax
    from harness import train_cell
    from ray_tpu.parallel import mesh as pmesh
    cell = spec.cell(name)
    if len(jax.devices()) < cell["chips"]:
        pytest.skip("needs as many devices as the cell has chips")
    seen = {}

    def spy(cfg, mesh, **kw):
        seen.update(kw)
        raise _Reached

    monkeypatch.setattr(hmodel, "REHEARSAL", True)
    monkeypatch.setattr(hmodel, "compile_cache", lambda: "off")
    monkeypatch.setattr(pmesh, "make_train_step", spy)
    with pytest.raises(_Reached):
        train_cell.run(cell, 1, 1.0, False, time.monotonic())
    listed = cell["model"]["deployment"].get("frozen_leaves")
    assert set(seen) == {"model", "optimizer"}
    dep = cell["model"]["deployment"]
    if listed or "peak_learning_rate" in dep:
        assert name == "train-qwen3next-ep16" and listed == ["router"]
        assert seen["optimizer"] is not None
    else:
        assert seen["optimizer"] is None


def test_frozen_leaves_stand_still_to_the_bit_and_the_rest_learns(
        monkeypatch):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from harness import train_cell
    from ray_tpu.parallel import mesh as pmesh
    monkeypatch.setattr(hmodel, "REHEARSAL", True)
    cell = spec.cell("train-qwen3next-ep16")
    m = hmodel.resolved(cell["model"])
    assert m["deployment"]["frozen_leaves"] == ["router"]
    assert cell["model"]["deployment"]["peak_learning_rate"] == 1e-5
    # three steps at the default peak (the rehearsal's), so that what
    # learns moves by more than a bf16 weight's rounding; the cell's own
    # peak is held by the test of the schedule below
    dep = m["deployment"]
    assert dep["peak_learning_rate"] == 3e-4
    fam = spec.family(cell["family"])
    cfg = fam.config(m, **dep["model_overrides"])
    mesh = pmesh.make_mesh(pmesh.MeshSpec(data=1, context=1, **dep["mesh"]),
                           devices=jax.devices()[:1])
    opt = train_cell.optimizer(dep)
    init_fn, step_fn = pmesh.make_train_step(cfg, mesh, model=fam.module(),
                                             optimizer=opt)
    key = jax.random.PRNGKey(3)
    toks = jax.random.randint(jax.random.fold_in(key, 1), (2, 257), 0,
                              cfg.vocab_size, dtype=jnp.int32)
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
    with mesh:
        state, kept = init_fn(key), []
        # ``step_fn`` consumes the state it is handed (PR 53: donated),
        # so what the leaves were is copied before the first step
        params0 = jax.tree.map(jnp.copy, state.params)
        for _ in range(3):
            state, met = step_fn(state, batch)
            kept.append(met)
    # the optimizer's state is the default's tree, leaf for leaf
    default = jax.eval_shape(pmesh.default_optimizer().init, params0)
    assert jax.tree.structure(state.opt_state) == jax.tree.structure(default)
    assert [(x.shape, x.dtype) for x in jax.tree.leaves(state.opt_state)] \
        == [(x.shape, x.dtype) for x in jax.tree.leaves(default)]

    def by_path(tree):
        return {jax.tree_util.keystr(p): np.asarray(x) for p, x
                in jax.tree_util.tree_leaves_with_path(tree)}
    before, after = by_path(params0), by_path(state.params)
    routers = [p for p in before if "'router'" in p]
    assert routers
    for p in routers:
        assert np.array_equal(before[p], after[p]), p
    moved = [p for p in before
             if p not in routers and not np.array_equal(before[p], after[p])]
    assert len(moved) >= len(before) // 2, moved
    # adam's moments are whole: a router's gradient is still counted
    # (mu moved off zero), and so is another leaf's
    mu = by_path(state.opt_state[1][0].mu)
    assert all(np.abs(mu[p]).max() > 0 for p in routers)
    assert sum(np.abs(mu[p]).max() > 0 for p in moved) == len(moved)
    # the runner's readers get every scalar of every step
    by_step = train_cell.step_metrics(kept)
    assert {"grad_norm", "moe_local_share", "moe_compact_share"} \
        <= set(by_step)
    assert "loss" not in by_step and "step" not in by_step
    assert all(len(v) == 3 for v in by_step.values())


def test_no_key_no_optimizer():
    from harness import train_cell
    assert train_cell.optimizer({"batch": 4}) is None
    assert train_cell.optimizer({"frozen_leaves": []}) is None


def test_the_jobs_peak_learning_rate_is_the_defaults_schedule_scaled():
    """``peak_learning_rate`` is the program's own optimizer at another
    peak: the same clip, warm-up and decay, so at every count an update
    is the default's times peak / 3e-4."""
    import jax.numpy as jnp
    import numpy as np
    from harness import train_cell
    from ray_tpu.parallel import mesh as pmesh
    params = {"w": jnp.full((4,), 0.5), "router": jnp.full((4,), 0.25)}
    grads = {"w": jnp.array([0.1, -0.2, 0.3, -0.4]),
             "router": jnp.array([0.4, 0.3, -0.2, 0.1])}

    def third_update(opt):
        state = opt.init(params)
        for _ in range(3):      # the schedule starts at 0: count 2
            up, state = opt.update(grads, state, params)
        return up
    want = third_update(pmesh.default_optimizer())
    got = third_update(train_cell.optimizer({"peak_learning_rate": 1e-5}))
    for k in want:
        np.testing.assert_allclose(got[k], want[k] * (1e-5 / 3e-4),
                                   rtol=1e-5)
    both = third_update(train_cell.optimizer(
        {"peak_learning_rate": 1e-5, "frozen_leaves": ["router"]}))
    np.testing.assert_allclose(both["w"], got["w"], rtol=1e-6)
    assert not np.any(np.asarray(both["router"]))


# --- the reader --------------------------------------------------------------

def test_the_least_of_a_steps_metric():
    mf = spec.metric_file("moe_compact_share_min.train")
    read = spec.reader(mf["reader"])
    ctx = {"train": {"step_metrics": {
        "moe_compact_share": [1.0, 1.0, 0.75, 0.5, 1.0],
        "grad_norm": [3.0, 2.0]}}}
    assert read(ctx, **mf["args"]) == 0.5
    ctx["train"]["step_metrics"]["moe_compact_share"] = [1.0] * 80
    assert read(ctx, **mf["args"]) == 1.0
    # a family whose step carries no such scalar, a run of no steps, a
    # serving cell: nothing to read, and the metric is left out
    assert read({"train": {"step_metrics": {"grad_norm": [1.0]}}},
                **mf["args"]) is None
    assert read({"train": {"step_metrics": {"moe_compact_share": []}}},
                **mf["args"]) is None
    assert read({"requests": []}, **mf["args"]) is None
    # prepared, like ``serve_tok_s``: the entry is not in BENCHMARK.json
    # yet, because a tier-1 test outside ``benchmarks/`` holds the cell's
    # per-layer metrics to an exact set (README, "A prepared metric");
    # the entry a later PR adds is the file without reader and args
    assert mf["workloads"] == ["train-qwen3next-ep16"]
    assert mf["moves"] == "train_tok_s_chip"
    cell = spec.cell(mf["workloads"][0])
    assert mf["moves"] in {m["name"] for m in cell["end_to_end"]}
    assert mf["layer"] in {m["layer"] for m in cell["per_layer"]}
