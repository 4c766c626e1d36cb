"""One run of a training cell, in the one process that holds the
chip(s): ``make_train_step`` on the cell's mesh, weights and batches
made on the device from the seed, whole steps timed to
``block_until_ready``.
"""

from __future__ import annotations

import glob
import os
import shutil
import tempfile
import time

from harness import model as hmodel, result, spec


class _FreezeWatch:
    """A thread that asks to sleep 5 ms at a time and notes by how much
    it overslept most: when a slow step coincides with a long oversleep
    of a thread that shares nothing with the step but the host, the host
    stood still, not the program."""

    def __init__(self):
        import threading
        self.worst, self.worst_at = 0.0, 0.0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        last = time.perf_counter()
        while not self._stop.wait(0.005):
            now = time.perf_counter()
            if now - last - 0.005 > self.worst:
                self.worst, self.worst_at = now - last - 0.005, now
            last = now

    def stop(self):
        self._stop.set()
        self._thread.join()


def parity(fam, cfg, mesh, params, ref_params, sub) -> tuple:
    """(program's loss, reference's loss, relative norm of the logits'
    difference, relative difference of the losses) on the slice
    ``sub``: the program's forward and loss on ``params`` on this mesh
    against the family's plain reference on ``ref_params``."""
    import jax
    from harness import reference
    from ray_tpu.parallel import mesh as pmesh
    module = fam.module()
    got_logits = jax.jit(lambda p, t: module.forward(p, t, cfg, mesh))(
        params, sub["tokens"])
    got = float(pmesh.make_eval_step(cfg, mesh, model=module)(params, sub))
    want_logits, want = fam.logits_and_loss(ref_params, sub, cfg)
    want = float(want)
    logits_err = float(reference.rel_err_device(got_logits, want_logits))
    return got, want, logits_err, abs(got - want) / abs(want)


def run(cell: dict, seed: int, seconds: float, trace: bool,
        t_proc0: float) -> int:
    cache = hmodel.compile_cache()
    import jax
    import jax.numpy as jnp
    from harness.compiles import CompileCounter
    from ray_tpu.util import jaxenv
    from ray_tpu.parallel import mesh as pmesh

    compiles = CompileCounter()
    device = jaxenv.describe_device()
    result.require_tpu(device, cell["chips"])
    m = hmodel.resolved(cell["model"])
    dep = m["deployment"]
    params = hmodel.traffic(cell)
    seq, batch = int(params["seq_len"]), int(dep["batch"])
    fam = spec.family(cell["family"])
    cfg = fam.config(m, **dep["model_overrides"])
    devices = jax.devices()[:cell["chips"]]
    mesh = pmesh.make_mesh(pmesh.MeshSpec(
        data=1, context=1, **dep["mesh"]), devices=devices)
    init_fn, step_fn = pmesh.make_train_step(cfg, mesh, model=fam.module())
    key = jax.random.PRNGKey(seed % (2 ** 31))
    n_batches = int(params.get("distinct_batches", 2))

    @jax.jit
    def make_batches(key):
        """(n_batches, batch, seq + 1) ids: a uniform first id, then
        id -> (a * id + c) mod vocab, a fixed bijection (a is coprime
        with both vocabularies), so the NEXT token is a function of
        this one - something the step can learn, which makes "the loss
        falls" a check of backward and optimizer."""
        first = jax.random.randint(key, (n_batches, batch), 0,
                                   cfg.vocab_size, dtype=jnp.int32)

        def nxt(t, _):
            t = (t * 1103 + 12345) % cfg.vocab_size
            return t, t
        _, rest = jax.lax.scan(nxt, first, None, length=seq)
        return jnp.concatenate([first[..., None],
                                jnp.moveaxis(rest, 0, -1)], -1)

    with mesh:
        state = init_fn(key)
        toks = make_batches(jax.random.fold_in(key, 1))
        data = [{"tokens": toks[i, :, :-1], "targets": toks[i, :, 1:]}
                for i in range(n_batches)]
        # correctness, before anything is timed, on a slice of the first
        # batch (on this mesh, same weights, same tokens): the program's
        # logits against the plain reference's by relative norm, and the
        # program's loss against the reference's
        n_ref = int(dep["parity_tokens"])
        rows = mesh.shape["data"] * mesh.shape["fsdp"]   # one a shard
        sub = {k: v[:rows, :n_ref] for k, v in data[0].items()}
        got, want, logits_err, loss_err = parity(
            fam, cfg, mesh, state.params, state.params, sub)
        parity_ok = (logits_err <= dep["parity_logits_tolerance"]
                     and loss_err <= dep["parity_loss_tolerance"])
        result.note(note="parity", device=device, loss=got, reference=want,
                    loss_rel_err=loss_err,
                    loss_tolerance=dep["parity_loss_tolerance"],
                    logits_rel_err=logits_err,
                    logits_tolerance=dep["parity_logits_tolerance"],
                    ok=parity_ok)
        losses = []
        for i in range(2):      # compile, then one warm step
            state, met = step_fn(state, data[i % n_batches])
            losses.append(float(met["loss"]))
        c0 = compiles.n
        freeze = _FreezeWatch()
        t0 = time.perf_counter()
        setup_s = time.monotonic() - t_proc0
        tdir, traced = None, None
        steps, ends = 0, []
        while True:
            if trace and steps == 2:
                tdir = tempfile.mkdtemp(prefix="ray_tpu_bench_trace_")
                jax.profiler.start_trace(tdir)
            with jax.profiler.TraceAnnotation("bench.train_step"):
                state, met = step_fn(state, data[steps % n_batches])
                losses.append(float(met["loss"]))   # waits for the step
            steps += 1
            ends.append(time.perf_counter() - t0)
            if trace and tdir and traced is None \
                    and steps == 2 + int(params.get("trace_steps", 3)):
                jax.profiler.stop_trace()
                traced = glob.glob(os.path.join(
                    tdir, "**", "*.xplane.pb"), recursive=True)
            if ends[-1] >= seconds and not (trace and traced is None):
                break
        elapsed = ends[-1]
        freeze.stop()
    finite = all(x == x and abs(x) != float("inf") for x in losses)
    # the lr schedule starts at 0, so compare like with like: the last
    # loss on a batch with the first loss on that same batch
    last_batch = (steps - 1) % n_batches
    fell = losses[-1] < losses[last_batch]
    mem = [d.memory_stats() or {} for d in devices]
    ctx = {"setup_s": setup_s, "model": m, "traffic": params, "cell": cell,
           "train": {"steps": steps, "elapsed_s": elapsed,
                     "tokens_per_step": batch * seq, "chips": len(devices),
                     "seq": seq, "batch": batch,
                     "trace_steps": int(params.get("trace_steps", 3))},
           "info": {"device": device,
                    "memory_peak_bytes": max(
                        (x.get("peak_bytes_in_use", 0) for x in mem),
                        default=0),
                    "memory_limit_bytes": max(
                        (x.get("bytes_limit", 0) for x in mem), default=0)},
           "counters": {"window": {"compiles": compiles.n - c0}},
           "trace": None}
    if traced:
        from harness import xplane
        ctx["trace"] = xplane.reduce_file(traced[0])
    if tdir:
        shutil.rmtree(tdir, ignore_errors=True)
    durs = [b - a for a, b in zip([0.0] + ends, ends)]
    result.note(note="window", steps=steps, elapsed_s=elapsed,
                step_s_median=sorted(durs)[len(durs) // 2],
                step_s_slowest=max(durs), slowest_step=durs.index(max(durs)),
                losses=[losses[0], losses[1], losses[-1]],
                loss_fell=fell, host_freeze_max_s=freeze.worst,
                host_freeze_at_s=freeze.worst_at - t0,
                compiles_in_window=compiles.n - c0,
                compile_cache=cache, cache_hits=compiles.hits,
                programs=compiles.n, setup_s=setup_s)
    result.finish(cell, trace, ctx, correct=parity_ok and finite and fell,
                  attempted=steps, failed=0)
    return 0
