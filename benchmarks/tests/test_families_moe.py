"""The ``moe`` family file beside ``llama``: the configuration that names
it resolves to the program's OLMoE settings, it cannot be served yet, and
its plain reference computes the program's forward. (The comparison of
loss and gradients, and the kernel's tests, are tier-1:
tests/test_zz_moe_olmoe.py.)"""
import dataclasses

from harness import spec


def test_the_olmoe_configuration_resolves():
    from ray_tpu.models import moe
    cell = spec.cell("train-olmoe")
    assert cell["family"] == "moe"
    dep = cell["model"]["deployment"]
    cfg = spec.family("moe").config(cell["model"], **dep["model_overrides"])
    want = moe.olmoe_1b_7b(n_layers=2, **dep["model_overrides"])
    assert dataclasses.asdict(cfg) == dataclasses.asdict(want)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.ffn_dim, cfg.dim) \
        == (64, 8, 1024, 2048)
    assert cfg.qk_norm and not cfg.norm_topk_prob


def test_the_family_cannot_be_served_yet():
    assert not hasattr(spec.family("moe"), "serve_parity")


def test_moe_reference_is_the_programs_forward():
    """float32 weights, XLA attention, ragged_dot: the plain reference
    (every expert for every token) and ``ray_tpu.models.moe.forward``
    (sorted rows, grouped matmul) agree to rounding."""
    import jax
    import numpy as np
    fam = spec.family("moe")
    moe = fam.module()
    model = dict(spec.cell("train-olmoe")["model"]["rehearsal"],
                 model_type="olmoe", norm_topk_prob=False,
                 router_aux_loss_coef=0.01, rope_theta=1e4,
                 rms_norm_eps=1e-5, torch_dtype="float32")
    cfg = fam.config(model, attn_impl="reference")
    params = moe.init_params(jax.random.PRNGKey(7), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, 64), 0,
                                cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(moe.forward(params, tokens, cfg))
        loss = float(moe.loss_fn(params, {"tokens": tokens,
                                          "targets": tokens}, cfg))
    want = np.asarray(fam.forward(params, tokens, cfg))
    assert np.abs(got - want).max() <= 1e-5 * max(1.0, np.abs(want).max())
    _, want_loss = fam.logits_and_loss(
        params, {"tokens": tokens, "targets": tokens}, cfg)
    assert abs(loss - float(want_loss)) <= 1e-5 * float(want_loss)
