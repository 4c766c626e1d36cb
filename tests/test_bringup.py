"""What the chip bring-up repaired, pinned on CPU: no guessed peak, an
HBM cap that reads the keys devmon writes, monitors that never claim a
chip, one place for the compile cache, and two entry points that fail
without a TPU (chip_smoke.py, bench.py)."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_args, **env):
    args = code_or_args if isinstance(code_or_args, list) \
        else ["-c", code_or_args]
    return subprocess.run(
        [sys.executable, *args], cwd=ROOT, capture_output=True,
        text=True, timeout=300, env={**os.environ, **env})


def test_peak_tflops_knows_v5_lite_and_raises_on_unknown():
    from ray_tpu.util.accelerators import peak_tflops
    assert peak_tflops("TPU v5 lite") == 197    # a v5e's device_kind
    with pytest.raises(ValueError, match="cpu"):
        peak_tflops("cpu")


def test_auto_pool_blocks_shrinks_under_small_hbm_headroom(monkeypatch):
    """The cap reads the rows devmon.hbm_snapshot really returns
    (`limit`/`used`): a quarter of the fullest device's free HBM,
    never below one full-horizon request."""
    from ray_tpu.llm import kvcache as kc
    from ray_tpu.util import devmon
    slots, width, block_bytes = 8, 256, 1 << 20
    rows = [{"device": "tpu:0", "limit": 16 << 30, "used": 15 << 30},
            {"device": "tpu:1", "limit": 16 << 30, "used": 1 << 30}]
    monkeypatch.setattr(devmon, "hbm_snapshot", lambda record=True: rows)
    worst = slots * width + width + 1
    # 1 GiB free on the fullest device -> 256 one-MiB blocks
    assert kc.auto_pool_blocks(slots, width, block_bytes) == 256 + 1
    assert kc.auto_pool_blocks(slots, width, 1 << 10) == worst
    assert kc.auto_pool_blocks(slots, width, 1 << 30) == width + 1
    # the explicit knob wins; a backend without a capacity caps nothing
    assert kc.auto_pool_blocks(slots, width, block_bytes, 77) == 77
    monkeypatch.setattr(devmon, "hbm_snapshot", lambda record=True: [])
    assert kc.auto_pool_blocks(slots, width, block_bytes) == worst


def test_monitors_do_not_initialise_a_backend():
    """A process that only imported jax owns no chip, and neither the
    device monitor nor the goodput ledger may take one to fill a
    gauge."""
    r = _run(
        "import jax\n"
        "from jax._src import xla_bridge\n"
        "from ray_tpu.util import devmon, goodput\n"
        "assert devmon.install()\n"
        "assert devmon.hbm_snapshot() == []\n"
        "assert goodput._peak() is None\n"
        "devmon.record_device_window('decode', 1.0, 2.0)\n"
        "assert not xla_bridge.backends_are_initialized()\n"
        "import jax.numpy as jnp\n"
        "jax.jit(lambda x: x + 1)(jnp.ones(3)).block_until_ready()\n"
        "assert devmon.backend_live()    # the compile listener saw it\n"
        "assert devmon.hbm_snapshot()\n")
    assert r.returncode == 0, r.stderr


def test_compile_cache_is_placed_from_outside(monkeypatch):
    import jax
    from ray_tpu.util import jaxenv
    default = os.path.join(ROOT, ".jax_cache")
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert jaxenv.setup_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == was  # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert jaxenv.setup_compile_cache() == default
        assert jax.config.jax_compilation_cache_dir == default
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    # before jax is imported, the variable carries it to the import
    # (and to every worker this process spawns)
    r = _run("import os, sys\n"
             "os.environ.pop('JAX_COMPILATION_CACHE_DIR', None)\n"
             "from ray_tpu.util import jaxenv\n"
             "d = jaxenv.setup_compile_cache()\n"
             "assert 'jax' not in sys.modules\n"
             "assert os.environ['JAX_COMPILATION_CACHE_DIR'] == d\n"
             "print(d)\n")
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == default


def test_chip_smoke_fails_without_a_tpu_and_parent_stays_off_jax():
    r = _run("import sys, chip_smoke\n"
             "rc = chip_smoke.main([])\n"
             "assert 'jax' not in sys.modules, 'parent imported jax'\n"
             "sys.exit(rc)\n", JAX_PLATFORMS="cpu")
    assert r.returncode == 1, r.stderr[-2000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert "parent imported jax" not in r.stderr


def test_bench_fails_without_a_tpu():
    r = _run(["bench.py"], JAX_PLATFORMS="cpu")
    assert r.returncode != 0
    assert "llama_train_mfu" not in r.stdout


def test_head_does_not_count_its_own_stall_as_node_silence():
    """A node dies after `threshold` seconds without a heartbeat — but
    not when the head's own loop stood still that long (on the chip a
    starting replica froze the host past the threshold, and the head
    declared its own in-process node and every actor on it dead)."""
    import asyncio
    import time

    from ray_tpu.config import Config
    from ray_tpu.runtime.control import ControlService
    from ray_tpu.runtime.ids import NodeID

    async def go():
        head = ControlService(Config.from_env(
            health_check_period_s=0.05,
            health_check_failure_threshold=8))      # 0.4 s of silence
        await head.start("127.0.0.1", 0)
        try:
            nid = NodeID.generate()
            await head.register_node(nid, ("127.0.0.1", 1), {"CPU": 1})
            await head.heartbeat(nid)
            await asyncio.sleep(0.06)
            time.sleep(1.0)             # the whole loop stands still
            await asyncio.sleep(0.12)   # two ticks after the stall
            alive_after_stall = head.nodes[nid].alive
            await asyncio.sleep(0.8)    # real silence, loop running
            return alive_after_stall, head.nodes[nid].alive
        finally:
            await head.stop()

    assert asyncio.run(go()) == (True, False)
