"""From a configuration file (the model's published ``config.json`` keys
plus this benchmark's ``deployment``) to the program's config object."""

from __future__ import annotations

import os

REHEARSAL = os.environ.get("BENCH_REHEARSAL") == "1"


def resolved(model: dict) -> dict:
    """The configuration as it is run: in a rehearsal (CPU, tiny
    widths, kernels interpreted) the file's ``rehearsal`` group
    overrides the published sizes."""
    if not REHEARSAL:
        return model
    over = model.get("rehearsal", {})
    out = {**model, **{k: v for k, v in over.items() if k != "deployment"}}
    out["deployment"] = {**model["deployment"],
                         **over.get("deployment", {})}
    return out


def llama_config(model: dict, **overrides):
    from ray_tpu.models.llama import LlamaConfig
    heads = model["num_attention_heads"]
    if model.get("head_dim", model["hidden_size"] // heads) * heads \
            != model["hidden_size"]:
        raise ValueError("the program derives head_dim as hidden_size / "
                         "heads; this configuration needs another")
    return LlamaConfig(
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_layers=model["num_hidden_layers"], n_heads=heads,
        n_kv_heads=model["num_key_value_heads"],
        ffn_dim=model["intermediate_size"],
        max_seq_len=model["max_position_embeddings"],
        rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]),
        dtype={"bfloat16": "bfloat16",
               "float32": "float32"}[model["torch_dtype"]],
        **overrides)


def traffic(cell: dict) -> dict:
    """The cell's traffic parameters as they are run."""
    params = cell["traffic_params"]
    if REHEARSAL:
        params = {**params, **params.get("rehearsal", {})}
    return params


def compile_cache() -> str:
    """Point this process and its children at the persistent compile
    cache the program places (``JAX_COMPILATION_CACHE_DIR``, else inside
    the checkout) and keep sub-second programs in it too: a replica
    builds some two dozen of them at every start."""
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    from ray_tpu.util import jaxenv
    return jaxenv.setup_compile_cache()
