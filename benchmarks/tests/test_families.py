"""A configuration's family is a file the harness finds by name:
``families/<deployment.family>.py``, ``llama`` where the key is absent.
The move out of ``harness/`` lost nothing: the three accepted
configurations resolve to the program config they had, and the plain
reference still computes the program's forward."""
import dataclasses
import json
import os

import pytest

from harness import spec

SHARED_MISTRAL = dict(vocab_size=32768, dim=4096, n_heads=32, n_kv_heads=8,
                      ffn_dim=14336, max_seq_len=32768, rope_theta=1e6,
                      norm_eps=1e-5, dtype="bfloat16")
TRAIN_OVERRIDES = dict(attn_impl="flash", attn_block_q=1024,
                       attn_block_k=1024, logits_dtype="bfloat16",
                       remat_policy="full")
# what harness/model.py llama_config built from each file at PR 24
EXPECTED = {
    "mistral-7b-v0.3-serve": dict(SHARED_MISTRAL, n_layers=8),
    "mistral-7b-v0.3-train": dict(SHARED_MISTRAL, n_layers=4,
                                  **TRAIN_OVERRIDES),
    "yi-1.5-34b-train-4chip": dict(
        vocab_size=64000, dim=7168, n_layers=5, n_heads=56, n_kv_heads=8,
        ffn_dim=20480, max_seq_len=4096, rope_theta=5e6, norm_eps=1e-6,
        dtype="bfloat16", **TRAIN_OVERRIDES),
}


def test_the_expected_table_covers_every_configuration():
    assert set(EXPECTED) == {c["name"] for c in spec.benchmark()["configs"]}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_accepted_configurations_resolve_as_before(name):
    from ray_tpu.models.llama import LlamaConfig
    bench = spec.benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == name)
    with open(os.path.join(spec.ROOT, entry["file"])) as f:
        model = json.load(f)
    dep = model["deployment"]
    assert "family" not in dep      # the files are as they were accepted
    wl = next(w for w in bench["workloads"] if w["config"] == name)
    assert spec.cell(wl["name"])["family"] == "llama"
    cfg = spec.family("llama").config(
        model, **dep.get("model_overrides", {}))
    assert isinstance(cfg, LlamaConfig)
    # field for field; what the file does not set is the program's default
    want = dataclasses.asdict(LlamaConfig(**EXPECTED[name]))
    assert dataclasses.asdict(cfg) == want


def test_a_family_with_no_file_names_the_missing_path():
    with pytest.raises(SystemExit) as e:
        spec.family("nosuch")
    assert os.path.join("benchmarks", "families", "nosuch.py") in str(e.value)


def test_the_family_is_loaded_once():
    assert spec.family("llama") is spec.family("llama")


def test_llama_reference_is_the_programs_forward():
    """float32 weights, the program's XLA attention: the plain reference
    and ``ray_tpu.models.llama.forward`` agree to rounding."""
    import jax
    import numpy as np
    fam = spec.family("llama")
    llama = fam.module()
    model = dict(vocab_size=512, hidden_size=128, intermediate_size=256,
                 num_attention_heads=4, num_key_value_heads=2,
                 num_hidden_layers=2, max_position_embeddings=256,
                 rope_theta=1e4, rms_norm_eps=1e-5, torch_dtype="float32")
    cfg = fam.config(model, attn_impl="reference")
    params = llama.init_params(jax.random.PRNGKey(7), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, 64), 0,
                                cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(llama.forward(params, tokens, cfg))
    want = np.asarray(fam.forward(params, tokens, cfg))
    assert np.abs(got - want).max() <= 1e-5 * max(1.0, np.abs(want).max())
    batch = {"tokens": tokens, "targets": tokens}
    logits, loss = fam.logits_and_loss(params, batch, cfg)
    assert np.allclose(np.asarray(logits), want, atol=1e-6)
    assert np.isfinite(float(loss))


def test_train_required_flops_per_token():
    m = dict(hidden_size=4096, intermediate_size=14336,
             num_attention_heads=32, num_key_value_heads=8,
             vocab_size=32768)
    per_layer = 4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    matmul = 4 * per_layer + 4096 * 32768
    attn = 14 * 128 * 32 * (4096 + 1) / 2 * 4
    got = spec.family("llama").train_required_flops_per_token(m, 4, 4096)
    assert got == pytest.approx(6 * matmul + attn)
