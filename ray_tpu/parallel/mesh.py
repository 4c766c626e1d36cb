"""Device mesh + SPMD train step.

The TPU-native answer to the reference's whole parallelism-strategy table
(SURVEY.md section 2.3): DP/FSDP/TP/CP are axes of ONE ``jax.sharding.Mesh``;
XLA GSPMD inserts the collectives (psum for grads over data/fsdp,
reduce-scatter/all-gather for fsdp params, all-reduce for tensor partials,
ppermute rings for the context axis via ray_tpu.ops.ring_attention).

Where the reference wires NCCL process groups per strategy
(reference: python/ray/util/collective/collective.py:303), here the only
"backend setup" is building the mesh; sharding annotations do the rest.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.models import llama
from ray_tpu.models.llama import LlamaConfig, MeshAxes
from ray_tpu.util import devmon


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Axis sizes; -1 means "absorb all remaining devices" (at most one)."""
    data: int = 1
    fsdp: int = -1
    tensor: int = 1
    context: int = 1
    expert: int = 1
    axes: MeshAxes = MeshAxes()

    def resolve(self, n_devices: int) -> dict[str, int]:
        sizes = {self.axes.data: self.data, self.axes.fsdp: self.fsdp,
                 self.axes.tensor: self.tensor,
                 self.axes.context: self.context,
                 self.axes.expert: self.expert}
        unknown = [a for a, s in sizes.items() if s == -1]
        known = 1
        for s in sizes.values():
            if s != -1:
                known *= s
        if len(unknown) > 1:
            raise ValueError("at most one mesh axis may be -1")
        if unknown:
            if n_devices % known:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes {known}")
            sizes[unknown[0]] = n_devices // known
        total = 1
        for s in sizes.values():
            total *= s
        if total != n_devices:
            raise ValueError(f"mesh {sizes} != {n_devices} devices")
        return sizes


def make_mesh(spec: MeshSpec = MeshSpec(),
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    # this process now holds a backend: the device monitor may query it
    devmon.mark_backend_live()
    sizes = spec.resolve(len(devices))
    names = tuple(sizes.keys())
    shape = tuple(sizes.values())
    import numpy as np
    return Mesh(np.asarray(devices).reshape(shape), names)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jax.Array


def default_optimizer(learning_rate: float = 3e-4,
                      weight_decay: float = 0.1,
                      warmup_steps: int = 100,
                      total_steps: int = 10_000,
                      grad_clip: float = 1.0) -> optax.GradientTransformation:
    sched = optax.warmup_cosine_decay_schedule(
        0.0, learning_rate, warmup_steps, max(total_steps, warmup_steps + 1))
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(sched, b1=0.9, b2=0.95, weight_decay=weight_decay),
    )


def make_train_step(cfg, mesh: Mesh,
                    axes: MeshAxes = MeshAxes(),
                    optimizer: Optional[optax.GradientTransformation] = None,
                    loss_fn: Optional[Callable] = None,
                    model=llama):
    """Returns (init_fn(rng) -> TrainState, step_fn(state, batch) ->
    (state, metrics)). Both jitted with GSPMD sharding: params per
    model.param_shardings, batch over (data+fsdp, context), the
    optimizer's per-parameter state sharded like its parameter.

    ``model`` is any module exposing the model-family protocol
    (init_params / param_shardings / loss_fn) — ray_tpu.models.llama
    (default) or ray_tpu.models.moe. A family that also has
    ``loss_and_metrics`` (-> loss, {name: device scalar}) gets those
    scalars added to a step's metrics (moe: ``moe_aux_loss``,
    ``moe_load_max_over_mean``).

    ``step_fn`` CONSUMES the state it is handed (its first argument is
    donated): every leaf of the old ``TrainState`` is deleted and the
    returned one lives in its buffers, so rebind (``state, met =
    step_fn(state, batch)``) and copy first (``jax.tree.map(jnp.copy,
    state)``) what is to be read afterwards. Why: the TPU compiler
    plans a step's memory as arguments + outputs + the temporaries'
    peak, so undonated the params and both moments are held twice for
    the whole program; at the train cells' sizes that left 2-4 GB of
    16 for every temporary, and XLA's own rematerialisation answered
    by computing whole FFN products again (``.remat`` instructions)."""
    opt = optimizer if optimizer is not None else default_optimizer()
    _loss = loss_fn if loss_fn is not None else (
        lambda p, b: model.loss_fn(p, b, cfg, mesh, axes))
    family_metrics = loss_fn is None and hasattr(model, "loss_and_metrics")
    pspecs = model.param_shardings(cfg, axes)
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                          is_leaf=lambda x: isinstance(x, P))
    batch_spec = NamedSharding(mesh, P(axes.batch, axes.context))

    @jax.jit
    def init_fn(rng) -> TrainState:
        params = jax.lax.with_sharding_constraint(
            model.init_params(rng, cfg), pshard)
        # moments start as zeros, which nothing ties to the params'
        # sharding: left to propagation they come out replicated (and
        # the first step then compiles twice, once per input layout)
        opt_state = optax.tree_map_params(
            opt, jax.lax.with_sharding_constraint, opt.init(params),
            pshard)
        return TrainState(params, opt_state, jnp.zeros((), jnp.int32))

    @jax.jit(donate_argnums=(0,))
    def step_fn(state: TrainState, batch: dict):
        batch = {k: jax.lax.with_sharding_constraint(v, batch_spec)
                 for k, v in batch.items()}
        if family_metrics:
            (loss, extra), grads = jax.value_and_grad(
                lambda p, b: model.loss_and_metrics(p, b, cfg, mesh, axes),
                has_aux=True)(state.params, batch)
        else:
            loss, grads = jax.value_and_grad(_loss)(state.params, batch)
            extra = {}
        with jax.named_scope("optimizer"):   # a name in the device trace
            updates, opt_state = opt.update(grads, state.opt_state,
                                            state.params)
            params = optax.apply_updates(state.params, updates)
            gnorm = optax.global_norm(grads)
        return (TrainState(params, opt_state, state.step + 1),
                {"loss": loss, "grad_norm": gnorm, "step": state.step + 1,
                 **extra})

    return init_fn, step_fn


def make_eval_step(cfg, mesh: Mesh,
                   axes: MeshAxes = MeshAxes(), model=llama):
    @jax.jit
    def eval_fn(params, batch):
        return model.loss_fn(params, batch, cfg, mesh, axes)
    return eval_fn
