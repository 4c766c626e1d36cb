"""Tune: variant generation, concurrent trials, ASHA early stopping.

Reference test shape: python/ray/tune/tests/test_tune_* on a local
cluster."""

import numpy as np
import pytest

import ray_tpu
from ray_tpu import tune


@pytest.fixture(scope="module")
def runtime():
    from ray_tpu.config import Config
    cfg = Config.from_env(num_workers_prestart=2, max_workers_per_node=8)
    ray_tpu.init(num_cpus=8, config=cfg)
    yield
    ray_tpu.shutdown()


def test_generate_variants_grid_and_sample():
    from ray_tpu.tune.search import generate_variants
    space = {"lr": tune.grid_search([0.1, 0.01]),
             "wd": tune.uniform(0, 1),
             "layers": tune.choice([2, 4]),
             "fixed": 7}
    variants = generate_variants(space, num_samples=3, seed=0)
    assert len(variants) == 6  # 2 grid x 3 samples
    assert {v["lr"] for v in variants} == {0.1, 0.01}
    assert all(0 <= v["wd"] <= 1 and v["fixed"] == 7 for v in variants)


def test_tuner_fit_returns_best(runtime):
    def trainable(config):
        # Quadratic bowl: best near x=3.
        loss = (config["x"] - 3.0) ** 2
        tune.report({"loss": loss})
        return {"final_loss": loss}

    tuner = tune.Tuner(
        trainable,
        param_space={"x": tune.grid_search([0.0, 1.0, 3.0, 5.0])},
        tune_config=tune.TuneConfig(metric="loss", mode="min",
                                    num_samples=1))
    results = tuner.fit()
    assert len(results) == 4
    best = results.get_best_result()
    assert best.config["x"] == 3.0
    assert best.metrics["loss"] == 0.0
    assert best.metrics["final_loss"] == 0.0


def test_tuner_reports_and_checkpoint(runtime):
    def trainable(config):
        for step in range(5):
            tune.report({"score": step * config["m"]},
                        checkpoint={"step": step, "m": config["m"]})

    tuner = tune.Tuner(
        trainable, param_space={"m": tune.grid_search([1, 2])},
        tune_config=tune.TuneConfig(metric="score", mode="max"))
    results = tuner.fit()
    best = results.get_best_result()
    assert best.config["m"] == 2
    assert best.metrics["score"] == 8
    assert best.checkpoint["step"] == 4
    assert len(best.all_reports) == 5


def test_tuner_trial_error_isolated(runtime):
    def trainable(config):
        if config["x"] == 1:
            raise RuntimeError("boom")
        tune.report({"loss": config["x"]})

    tuner = tune.Tuner(
        trainable, param_space={"x": tune.grid_search([0, 1, 2])},
        tune_config=tune.TuneConfig(metric="loss", mode="min"))
    results = tuner.fit()
    assert len(results.errors) == 1
    assert "boom" in results.errors[0].error
    assert results.get_best_result().config["x"] == 0


def test_asha_stops_losers(runtime):
    def trainable(config):
        import time as _t
        for it in range(1, 33):
            # Good trials improve; bad trials stagnate high. Paced so the
            # controller can observe reports and stop mid-run.
            loss = 100.0 if config["bad"] else 100.0 / it
            tune.report({"loss": loss})
            _t.sleep(0.05)

    # Good trials run in the first wave so rung cutoffs exist before the
    # stagnating trials reach them.
    tuner = tune.Tuner(
        trainable,
        param_space={"bad": tune.grid_search(
            [False, False, False, True, True, True])},
        tune_config=tune.TuneConfig(
            metric="loss", mode="min", num_samples=1,
            max_concurrent_trials=3,
            scheduler=tune.ASHAScheduler(
                metric="loss", mode="min", grace_period=2,
                reduction_factor=2, max_t=32)))
    results = tuner.fit()
    assert len(results) == 6
    best = results.get_best_result()
    assert best.config["bad"] is False
    stopped = [r for r in results if r.status == "STOPPED"]
    finished_iters = {r.config["bad"]: len(r.all_reports) for r in results}
    # At least one stagnating trial must have been culled early.
    assert stopped, f"ASHA culled nothing: {finished_iters}"
    assert all(r.config["bad"] for r in stopped)


def test_asha_rung_math():
    s = tune.ASHAScheduler(metric="m", mode="max", grace_period=1,
                           reduction_factor=2, max_t=8)
    # Trial A leads at every rung; trial B trails badly.
    assert s.on_result("A", {"training_iteration": 1, "m": 10}) == "CONTINUE"
    assert s.on_result("A", {"training_iteration": 2, "m": 20}) == "CONTINUE"
    assert s.on_result("B", {"training_iteration": 1, "m": 1}) == "STOP"


def test_tuner_over_trainer(runtime):
    """Tuner(trainer) parity: sweep a JaxTrainer's train_loop_config
    (reference: tuner.py accepting a Trainer trainable)."""
    from ray_tpu import train
    from ray_tpu.train import ScalingConfig

    def train_fn(config=None):
        lr = (config or {}).get("lr", 1.0)
        for step in range(3):
            train.report({"loss": lr * (3 - step), "lr": lr})

    trainer = train.JaxTrainer(
        train_fn, scaling_config=ScalingConfig(num_workers=1))
    grid = tune.Tuner(
        trainer,
        param_space={"lr": tune.grid_search([0.1, 1.0])},
        tune_config=tune.TuneConfig(metric="loss", mode="min",
                                    max_concurrent_trials=1),
    ).fit()
    assert len(grid) == 2
    best = grid.get_best_result()
    assert best.config["lr"] == 0.1
    assert best.metrics["loss"] == pytest.approx(0.1)


def test_asha_interrupts_trainer_trials_live(runtime, tmp_path):
    """Live report streaming: ASHA must stop a losing TRAINER trial
    mid-run (before its 20 steps finish), not post-hoc. Asynchronous
    halving culls a trial at a rung only if a better one recorded
    there first, and under load the two trials' workers come up in
    either order (the loser ran ahead, and to its end, in 1 of 10 runs
    beside five busy xdist workers): the loser holds its first report
    until the winner has passed two rungs (a flag file), so who is
    ahead is the test's choice and not a race of process start-ups."""
    from ray_tpu import train
    from ray_tpu.train import ScalingConfig
    flag = str(tmp_path / "winner_passed_two_rungs")

    def train_fn(config=None):
        import os
        import time as _t
        lr = (config or {}).get("lr", 1.0)
        while lr > 1.0 and not os.path.exists(flag):
            _t.sleep(0.05)
        for step in range(20):
            train.report({"loss": lr * 100.0 / (step + 1)})
            if lr <= 1.0 and step == 3:
                open(flag, "w").close()
            _t.sleep(0.25)

    trainer = train.JaxTrainer(
        train_fn, scaling_config=ScalingConfig(num_workers=1))
    grid = tune.Tuner(
        trainer,
        param_space={"lr": tune.grid_search([0.001, 50.0])},
        tune_config=tune.TuneConfig(
            metric="loss", mode="min", max_concurrent_trials=2,
            scheduler=tune.ASHAScheduler(
                metric="loss", mode="min", grace_period=2,
                reduction_factor=2, max_t=20)),
    ).fit()
    assert len(grid) == 2
    by_lr = {r.config["lr"]: r for r in grid}
    assert by_lr[0.001].status == "TERMINATED"
    loser = by_lr[50.0]
    assert loser.status == "STOPPED", (loser.status, loser.error)
    assert len(loser.all_reports) < 20, len(loser.all_reports)


def test_pbt_scheduler_unit():
    from ray_tpu.tune.schedulers import CONTINUE, Exploit
    pbt = tune.PopulationBasedTraining(
        metric="score", mode="max", perturbation_interval=2,
        hyperparam_mutations={"lr": [0.1, 1.0]},
        quantile_fraction=0.5, seed=0)
    for tid, cfg in (("a", {"lr": 1.0}), ("b", {"lr": 0.1})):
        pbt.on_trial_start(tid, cfg)
    # before the interval: no decision
    assert pbt.on_result("a", {"training_iteration": 1,
                               "score": 10}) == CONTINUE
    assert pbt.on_result("b", {"training_iteration": 1,
                               "score": 1}) == CONTINUE
    # at the interval, the top trial continues...
    assert pbt.on_result("a", {"training_iteration": 2,
                               "score": 20}) == CONTINUE
    # ...and the bottom trial exploits it
    d = pbt.on_result("b", {"training_iteration": 2, "score": 2})
    assert isinstance(d, Exploit) and d.donor_id == "a"
    assert "lr" in d.config and d.config["lr"] in (0.1, 1.0)
    assert pbt.num_exploits == 0   # counted only when actually applied
    pbt.on_exploit_applied("b", d.config)
    assert pbt.num_exploits == 1


def test_pbt_exploit_migrates_trials(runtime):
    """Bad-lr trials must clone the good trial's state mid-run and end
    near the best trajectory (reference behavior:
    tune/tests/test_trial_scheduler_pbt.py)."""
    # horizon long enough (~4s/trial) that the controller's poll loop
    # decides + stops mid-run even on a slow contended box; exploits
    # that lose the race to a finished trial are dropped by design
    def trainable(config):
        x = tune.get_checkpoint() or 0.0
        lr = config["lr"]
        for _ in range(25):
            x += lr
            tune.report({"score": x}, checkpoint=x)
            import time as _t
            _t.sleep(0.15)
        return {"score": x}

    pbt = tune.PopulationBasedTraining(
        metric="score", mode="max", perturbation_interval=3,
        hyperparam_mutations={"lr": [1.0]},
        quantile_fraction=0.34, resample_probability=1.0, seed=3)
    grid = tune.Tuner(
        trainable,
        param_space={"lr": tune.grid_search([1.0, 0.01, 0.01])},
        tune_config=tune.TuneConfig(metric="score", mode="max",
                                    max_concurrent_trials=3,
                                    scheduler=pbt),
    ).fit()
    assert pbt.num_exploits >= 1, "no exploit ever happened"
    best = grid.get_best_result().metrics["score"]
    assert best >= 24.9
    # a migrated trial must beat what lr=0.01 alone could reach (0.25)
    others = sorted(r.metrics.get("score", 0.0) for r in grid)
    assert others[-2] > 2.0, others


def test_tpe_searcher_beats_random_on_quadratic():
    """Unit (no cluster): after warmup, TPE's suggestions concentrate
    near the optimum of a quadratic — mean distance over the model
    phase must beat the random phase (reference capability:
    tune/search/hyperopt, reimplemented natively)."""
    from ray_tpu.tune.search import TPESearcher, loguniform, uniform

    s = TPESearcher(n_initial=10, n_candidates=32, seed=0)
    s.set_search_properties(
        "loss", "min", {"x": uniform(-10.0, 10.0),
                        "lr": loguniform(1e-5, 1e-1)})
    import math
    rand_d, model_d = [], []
    for i in range(60):
        tid = f"t{i}"
        cfg = s.suggest(tid)
        d = abs(cfg["x"] - 3.0) + abs(math.log10(cfg["lr"]) + 3.0)
        (rand_d if i < 10 else model_d).append(d)
        loss = (cfg["x"] - 3.0) ** 2 + (math.log10(cfg["lr"]) + 3.0) ** 2
        s.on_trial_complete(tid, {"loss": loss})
    late = model_d[len(model_d) // 2:]
    assert sum(late) / len(late) < sum(rand_d) / len(rand_d), \
        (sum(late) / len(late), sum(rand_d) / len(rand_d))


def test_tpe_categorical_and_mode_max():
    from ray_tpu.tune.search import TPESearcher, choice

    s = TPESearcher(n_initial=6, seed=1)
    s.set_search_properties("score", "max", {"arm": choice(["a", "b", "c"])})
    reward = {"a": 0.1, "b": 1.0, "c": 0.2}
    picks = []
    for i in range(40):
        tid = f"t{i}"
        cfg = s.suggest(tid)
        picks.append(cfg["arm"])
        s.on_trial_complete(tid, {"score": reward[cfg["arm"]]})
    late = picks[25:]
    assert late.count("b") > len(late) // 2, picks


def test_tpe_rejects_grid_and_missing_metric():
    from ray_tpu.tune.search import TPESearcher, grid_search, uniform

    s = TPESearcher()
    with pytest.raises(ValueError, match="metric"):
        s.set_search_properties(None, "min", {"x": uniform(0, 1)})
    with pytest.raises(ValueError, match="grid_search"):
        s.set_search_properties("m", "min", {"x": grid_search([1, 2])})


def test_tuner_with_tpe_search_alg(runtime):
    """Integration: Tuner drives the searcher sequentially — exactly
    num_samples trials run, later configs use observed results."""
    from ray_tpu import tune as rt_tune

    def objective(config):
        rt_tune.report({"loss": (config["x"] - 2.0) ** 2})

    res = rt_tune.Tuner(
        objective,
        param_space={"x": rt_tune.uniform(-5.0, 5.0)},
        tune_config=rt_tune.TuneConfig(
            metric="loss", mode="min", num_samples=12,
            search_alg=rt_tune.TPESearcher(n_initial=4, seed=3),
            max_concurrent_trials=2),
    ).fit()
    assert len(res._results) == 12
    best = res.get_best_result()
    assert abs(best.config["x"] - 2.0) < 2.5, best.config


def test_tpe_sweep_runs_wide(runtime, tmp_path):
    """A 16-trial TPE sweep with max_concurrent_trials=4 overlaps
    trials (the searcher refills every free slot, it does not
    serialize the sweep on one suggestion at a time)."""
    import time as _time

    from ray_tpu import tune as rt_tune
    log = str(tmp_path / "spans.log")

    def objective(config):
        t0 = _time.monotonic()
        _time.sleep(0.5)
        with open(log, "a") as f:
            f.write(f"{t0} {_time.monotonic()}\n")
        rt_tune.report({"loss": (config["x"] - 1.0) ** 2})

    res = rt_tune.Tuner(
        objective,
        param_space={"x": rt_tune.uniform(-4.0, 4.0)},
        tune_config=rt_tune.TuneConfig(
            metric="loss", mode="min", num_samples=16,
            search_alg=rt_tune.TPESearcher(n_initial=4, seed=0),
            max_concurrent_trials=4),
    ).fit()
    assert len(res._results) == 16
    spans = [tuple(map(float, ln.split()))
             for ln in open(log).read().splitlines()]
    assert len(spans) == 16
    peak = max(sum(1 for s, e in spans if s <= t < e)
               for t, _ in spans)
    assert peak >= 2, f"sweep ran sequentially (peak overlap {peak})"


def test_tuner_restore_reruns_unfinished(runtime, tmp_path):
    """Kill-and-restore accounting: trials that crashed in run 1 are
    re-run by Tuner.restore; finished trials keep their results and do
    NOT re-execute."""
    from ray_tpu import tune as rt_tune
    marker = str(tmp_path / "healed")
    runs = str(tmp_path / "runs.log")
    storage = str(tmp_path / "sweep")

    def objective(config):
        import os as _os
        with open(runs, "a") as f:
            f.write(f"{config['x']}\n")
        if config["x"] >= 4 and not _os.path.exists(marker):
            _os._exit(1)        # hard crash, like a kill -9 of the trial
        rt_tune.report({"loss": float(config["x"])})

    space = {"x": rt_tune.grid_search([1, 2, 3, 4, 5])}
    cfg = rt_tune.TuneConfig(metric="loss", mode="min", num_samples=1,
                             max_concurrent_trials=2)
    run1 = rt_tune.Tuner(objective, param_space=space, tune_config=cfg,
                         storage_path=storage, name="sweep1").fit()
    assert len(run1.errors) == 2          # x=4, x=5 crashed
    assert len(run1._results) == 5

    open(marker, "w").close()             # "fix the bug", then restore
    run2 = rt_tune.Tuner.restore(storage, objective,
                                 name="sweep1").fit()
    assert len(run2._results) == 5
    assert not run2.errors, [r.error for r in run2.errors]
    assert {r.config["x"] for r in run2._results} == {1, 2, 3, 4, 5}
    # finished trials did not re-execute: 5 first-run + 2 re-runs
    executed = [int(x) for x in open(runs).read().split()]
    assert len(executed) == 7, executed
    assert sorted(executed[5:]) == [4, 5]


def test_tuner_restore_with_tpe_refeeds_observations(runtime, tmp_path):
    """Restoring a TPE sweep replays finished observations into the
    searcher (suggestions after restore condition on them) and runs
    only the remaining budget."""
    from ray_tpu import tune as rt_tune
    marker = str(tmp_path / "healed")
    storage = str(tmp_path / "tpe_sweep")

    def objective(config):
        import os as _os
        if config.get("boom") and not _os.path.exists(marker):
            raise RuntimeError("injected")
        rt_tune.report({"loss": (config["x"] - 2.0) ** 2})

    class FlakyTPE(rt_tune.TPESearcher):
        n_suggested = 0

        def suggest(self, trial_id):
            cfg = super().suggest(trial_id)
            if cfg is not None:
                FlakyTPE.n_suggested += 1
                cfg["boom"] = FlakyTPE.n_suggested == 3  # 3rd trial fails
            return cfg

    cfg = rt_tune.TuneConfig(
        metric="loss", mode="min", num_samples=8,
        search_alg=FlakyTPE(n_initial=3, seed=1),
        max_concurrent_trials=2)
    run1 = rt_tune.Tuner(objective,
                         param_space={"x": rt_tune.uniform(-4.0, 4.0)},
                         tune_config=cfg, storage_path=storage,
                         name="tpe1").fit()
    assert len(run1._results) == 8
    assert len(run1.errors) >= 1

    open(marker, "w").close()
    restored = rt_tune.Tuner.restore(storage, objective, name="tpe1")
    searcher = restored._cfg.search_alg
    run2 = restored.fit()
    assert len(run2._results) == 8
    assert not run2.errors
    # the searcher saw the pre-restore observations again
    assert len(searcher._obs) >= 8 - len(run1.errors)
