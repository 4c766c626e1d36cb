"""A configuration file (the model's published ``config.json`` keys
plus this benchmark's ``deployment``) and a traffic file as they are
run, and the compile cache. What turns the keys into the program's
config object is the configuration's family (``families/*.py``)."""

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
