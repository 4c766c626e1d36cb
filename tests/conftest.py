"""Test harness: force an 8-device virtual CPU platform BEFORE jax imports.

Mirrors the reference's multi-node-without-a-cluster strategy
(reference: python/ray/cluster_utils.py:137) — sharding and multi-chip code
paths are exercised on virtual devices; real-TPU benchmarking happens in
bench.py outside pytest.
"""

import os

# The environment is the one pin: jax reads JAX_PLATFORMS at import, and
# the worker processes a test spawns inherit it (runtime/agent.py).
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
# Tests count compiles (devmon spans, jit cache keys); a persistent
# compilation cache left warm by an earlier run would turn them into
# cache hits. Off here and in every spawned worker.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _serve_port_per_xdist_worker():
    """Every xdist worker runs clusters of its own on the one host, and
    serve's HTTP proxy binds a fixed default port: two workers inside
    serve tests at the same moment fought over it ("address already in
    use": test_llm.py's serve tests, red in two of PR 29's tier-1
    runs). Each worker gets a default of its own; a run without xdist
    keeps 8000."""
    worker = os.environ.get("PYTEST_XDIST_WORKER", "")    # "gw3"
    if worker[2:].isdigit():
        from ray_tpu.serve import api as serve_api
        serve_api.DEFAULT_HTTP_PORT = 8001 + int(worker[2:])
    yield


@pytest.fixture(scope="session")
def mesh8():
    import jax
    from ray_tpu.parallel import MeshSpec, make_mesh
    assert len(jax.devices()) == 8
    return make_mesh(MeshSpec(data=1, fsdp=2, tensor=2, context=2))
