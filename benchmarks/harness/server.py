"""The deployment the serve cells run: ray_tpu.serve.llm's server, built
by the program's own constructor (``_load_model``, ``_serving_mesh``,
``LLMEngine``), plus four methods for the harness: device and engine
facts, a snapshot of the engine's PUBLIC counters (``engine.stats`` and
the ``engine_metrics()`` histograms as they are exposed), a profiler
trace of some seconds of serving, and the comparison of the served
forwards with the plain reference. Nothing of the engine is replaced or
wrapped, and no private name of it feeds a metric: what the public
counters do not carry (live context tokens, prefilled tokens) the
harness takes from the client's own records of the same seconds.
It runs in the replica's process, which holds the chip.
"""

from __future__ import annotations

import asyncio
import glob
import os
import shutil
import time

from harness.compiles import CompileCounter
from ray_tpu.serve.llm import LLMConfig, _LLMServer


def _histogram_totals(h) -> tuple:
    """(sum, count) of a histogram over all its label sets, read from
    its Prometheus exposition (``render()``), the public surface."""
    total, count = 0.0, 0.0
    for line in h.render().splitlines():
        if line.startswith(h.name + "_sum"):
            total += float(line.rsplit(" ", 1)[1])
        elif line.startswith(h.name + "_count"):
            count += float(line.rsplit(" ", 1)[1])
    return total, count


class BenchLLMServer(_LLMServer):
    def __init__(self, cfg: LLMConfig):
        t0 = time.monotonic()
        self._compiles = CompileCounter()
        super().__init__(cfg)       # the program's own construction
        self._init_s = time.monotonic() - t0
        self._blocks_peak = 0
        self._sampler = None

    async def _sample_pool(self, every_s: float = 0.05):
        """``stats`` gives the pool's use as a level; its peak is the
        highest level seen at this period."""
        while True:
            used = self.engine.stats.get("blocks_used", 0)
            self._blocks_peak = max(self._blocks_peak, used)
            await asyncio.sleep(every_s)

    def bench_counters(self) -> dict:
        """Cumulative counters; the harness takes differences."""
        from ray_tpu.llm.engine import engine_metrics
        out = {}
        for key, h in engine_metrics().items():
            if hasattr(h, "boundaries"):        # a histogram
                out[f"{key}_sum"], out[f"{key}_count"] = \
                    _histogram_totals(h)
        st = self.engine.stats
        for key in ("tokens_generated", "requests", "ttft_count"):
            out[key] = st[key]
        out["pool_blocks"] = st.get("pool_blocks", 0)
        out["blocks_used_peak"] = max(self._blocks_peak,
                                      st.get("blocks_used", 0))
        out["compiles"] = self._compiles.n
        out["cache_hits"] = self._compiles.hits
        out["compiled_names"] = list(self._compiles.names)
        out["t"] = time.monotonic()
        return out

    async def bench_info(self) -> dict:
        import jax
        st = dict(self.engine.stats)
        mem = [d.memory_stats() or {} for d in jax.local_devices()]
        st["memory_peak_bytes"] = max(
            (m.get("peak_bytes_in_use", 0) for m in mem), default=0)
        st["memory_limit_bytes"] = max(
            (m.get("bytes_limit", 0) for m in mem), default=0)
        st["init_s"] = self._init_s
        st["n_layers"] = self.engine.cfg.n_layers
        return st

    async def bench_snapshot(self, reset_peak: bool = False) -> dict:
        if self._sampler is None:
            self._sampler = asyncio.get_running_loop().create_task(
                self._sample_pool())
        out = self.bench_counters()
        if reset_peak:      # the peak is "since the last reset"
            self._blocks_peak = 0
        return out

    async def bench_trace(self, trace_dir: str, seconds: float) -> dict:
        """Trace ``seconds`` of serving. Returns the xplane file, the
        counters at both edges and the edges on this host's monotonic
        clock (CLOCK_MONOTONIC is one clock for every process of a
        host, so the client's token stamps can be cut at them)."""
        import jax
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
        c0 = self.bench_counters()
        try:
            await asyncio.sleep(seconds)
        finally:
            c1 = self.bench_counters()
            jax.profiler.stop_trace()
        files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        return {"xplane": files[0] if files else None, "c0": c0,
                "c1": c1, "edges": (c0["t"], c1["t"])}

    async def bench_reduce(self, xplane: str) -> dict:
        """Reduce the trace here, where the file is (it can be large)."""
        from harness import xplane as xp
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(None, xp.reduce_file, xplane)

    async def bench_parity(self, seed: int, prompt_len: int,
                           family: str) -> dict:
        from harness import spec
        loop = asyncio.get_running_loop()
        st = self.engine.stats
        return await loop.run_in_executor(
            None, lambda: spec.family(family).serve_parity(
                self.engine.params, self.engine.cfg, seed, prompt_len,
                buckets=self.engine.buckets, block=st["block_size"],
                kv_impl=st["kv_impl"], interpret=st["kv_interpret"]))
