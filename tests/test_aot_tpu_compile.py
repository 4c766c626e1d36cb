"""The Pallas kernels of the main path, compiled ahead of time for a
described TPU v5e chip, at the widths chip_smoke.py runs them.

No chip is attached under pytest: the TPU compiler installed here
compiles for a device that is only described, and raises what the
chip's compiler would raise. Interpret mode (every other kernel test)
checks the arithmetic but not the tiling: the paged-decode kernel passed
every interpret-mode test while the compiler refused it at every shape.

(The name sorts first on purpose: tier-1 is cut by a clock, and a file
the clock never reaches guards nothing.)
"""

import os
import re
import sys

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # or libtpu logs to /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from ray_tpu.ops.pallas import paged_attention as pa  # noqa: E402

A = sys.modules["ray_tpu.ops.attention"]    # the package re-exports a fn

HD = 128        # head_dim of every Llama-2/3 width


@pytest.fixture(scope="module")
def chip():
    """One described v5e device; the persistent compile cache is off
    around these compiles (an entry written for a described device
    cannot be read back without one, and warns on the next run)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _flash(q, k, v, **kw):
    return A.flash_attention(q, k, v, causal=True, **kw)


def _flash_bwd(q, k, v):
    def loss(q, k, v):
        return _flash(q, k, v, block_q=1024, block_k=1024).astype(
            jnp.float32).sum()
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


def _paged_case(kv_heads, group, slots=8, blocks=2305):
    block, width = 16, 256          # 8 x 4096 tokens unless told otherwise
    return pa.paged_attention, (
        ((slots, kv_heads, group, HD), jnp.bfloat16),
        ((blocks, kv_heads, block, HD), jnp.bfloat16),
        ((blocks, kv_heads, block, HD), jnp.bfloat16),
        ((slots, width), jnp.int32), ((slots,), jnp.int32))


_TRAIN = ((4, 4096, 32, HD), jnp.bfloat16)      # batch 4 x seq 4096, 7B
# name: (function, argument shapes, the kernels its program holds, by
# the names the program gives them and the benchmark looks for)
CASES = {
    "flash_fwd": (lambda q, k, v: _flash(q, k, v, block_q=1024,
                                         block_k=1024),
                  (_TRAIN, _TRAIN, _TRAIN), {"flash_fwd"}),
    "flash_bwd": (_flash_bwd, (_TRAIN, _TRAIN, _TRAIN),
                  {"flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"}),
    # one 512-token chunk at position 1024 of a 4608-row accumulator
    "flash_prefill_q_offset": (
        lambda q, k, v: _flash(q, k, v, q_offset=1024),
        (((1, 512, 32, HD), jnp.bfloat16),
         ((1, 4608, 32, HD), jnp.bfloat16),
         ((1, 4608, 32, HD), jnp.bfloat16)), {"flash_fwd"}),
    "paged_decode_g1": (*_paged_case(kv_heads=32, group=1),  # Llama-2-7B
                        {"paged_decode"}),
    "paged_decode_g4": (*_paged_case(kv_heads=8, group=4),   # Llama-3-8B
                        {"paged_decode"}),
    # the cell serve-chat-open as the chip runs it: Mistral-7B-v0.3,
    # 32 slots over the auto-sized pool of 5,882 blocks
    "paged_decode_chat_cell": (
        *_paged_case(kv_heads=8, group=4, slots=32, blocks=5882),
        {"paged_decode"}),
}


def _bench_kernels():
    """benchmarks/harness/kernels.py, by path: how the benchmark's
    trace reduction recognises the program's kernels."""
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        os.pardir, "benchmarks", "harness", "kernels.py")
    spec = importlib.util.spec_from_file_location("bench_kernels", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _custom_calls(compiled) -> list:
    """The program's custom-call instructions as a device trace
    spells them: operand shapes printed."""
    from jax._src.lib import _jax
    opts = _jax.HloPrintOptions()
    opts.print_operand_shape = True
    text = compiled.runtime_executable().hlo_modules()[0].to_string(opts)
    return [ln.strip().removeprefix("ROOT ") for ln in text.splitlines()
            if "tpu_custom_call" in ln]


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(chip, name):
    fn, shapes, kernels = CASES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    # the kernel itself, not an XLA rewrite of it
    assert "tpu_custom_call" in compiled.as_text()
    # each kernel carries its stable name (pallas_call(name=...)), and
    # the benchmark's reduction, which goes by the call's operand
    # signature, still tells the four apart with the names in place
    bench = _bench_kernels()
    ops = [bench.parse_op(ln) for ln in _custom_calls(compiled)]
    assert {bench.classify(op) for op in ops} == kernels
    for op in ops:
        assert bench.classify(op) in op["name"], op["name"]


# --- the grouped matmul of the MoE train path -------------------------------

def _gmm(lhs, rhs, group_sizes):
    from ray_tpu.ops.pallas import grouped_matmul
    return grouped_matmul.gmm(lhs, rhs, group_sizes)


def _gmm_bwd(lhs, rhs, group_sizes):
    return jax.grad(lambda l, r: _gmm(l, r, group_sizes).astype(
        jnp.float32).sum(), argnums=(0, 1))(lhs, rhs)


# the cell train-olmoe: 4 x 4096 tokens x 8 experts a token = 131,072
# rows, 64 groups, hidden 2048, expert width 1024
_ROWS, _EXPERTS, _HIDDEN, _WIDTH = 131072, 64, 2048, 1024
GMM_CASES = {
    "gmm_up": (_gmm, (_HIDDEN, _WIDTH), {"moe_gmm"}),
    "gmm_down": (_gmm, (_WIDTH, _HIDDEN), {"moe_gmm"}),
    "gmm_up_bwd": (_gmm_bwd, (_HIDDEN, _WIDTH),
                   {"moe_gmm_t", "moe_gmm_drhs"}),
    "gmm_down_bwd": (_gmm_bwd, (_WIDTH, _HIDDEN),
                     {"moe_gmm_t", "moe_gmm_drhs"}),
}


@pytest.mark.parametrize("name", sorted(GMM_CASES))
def test_grouped_matmul_compiles_for_v5e(chip, name):
    fn, (k, n), names = GMM_CASES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in (
        ((_ROWS, k), jnp.bfloat16), ((_EXPERTS, k, n), jnp.bfloat16),
        ((_EXPERTS,), jnp.int32))]
    compiled = jax.jit(fn).lower(*args).compile()
    bench = _bench_kernels()
    ops = [bench.parse_op(ln) for ln in _custom_calls(compiled)]
    # the kernels themselves, under their names; the benchmark's
    # reduction takes none of them for a flash or a paged kernel: they
    # land in unknown_kernel, where readers/moe_gmm.py looks
    for op in ops:
        assert bench.classify(op) == "unknown_kernel", op
    # moe_gmm__.1, transpose_jvp_moe_gmm_t__.1, ...
    found = [re.search(r"moe_gmm(_drhs|_t)?(?=_|\.|$)", op["name"])
             for op in ops]
    assert all(found), [op["name"] for op in ops]
    assert sorted(m.group(0) for m in found) == sorted(names)
