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

import math
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
def topo():
    """A described v5e 2x2; the persistent compile cache is off
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
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    """One described v5e device."""
    return SingleDeviceSharding(topo.devices[0])


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


# --- the flash kernels at the three train cells' shapes ---------------------

# (batch on this chip, heads on this chip): train-dense-1chip 4 x 32,
# train-yi34b-4chip 4 x 28 (batch 8 over fsdp 2, 56 heads over tensor 2),
# train-olmoe 4 x 16; 4096 tokens, head 128, 1024 x 1024 tiles
_CELL_HEADS = {"dense_bh128": 32, "yi_bh112": 28, "olmoe_bh64": 16}
# operand ranks and result count by which the benchmark tells them apart
_FLASH_SIGNATURE = {"flash_fwd": ([3] * 3, 2), "flash_bwd_dkv": ([3] * 6, 2),
                    "flash_bwd_dq": ([3] * 6, 1)}


@pytest.mark.parametrize("cell", sorted(_CELL_HEADS))
def test_flash_kernels_compile_at_the_train_cells_shapes(chip, cell):
    """Forward and both backward kernels, with the sub-tiled body, fit
    the compiler's VMEM limit at each cell's b*h and keep the names,
    operand signatures and result counts the benchmark finds them by."""
    shape = ((4, 4096, _CELL_HEADS[cell], HD), jnp.bfloat16)
    args = [jax.ShapeDtypeStruct(*shape, sharding=chip)] * 3
    compiled = jax.jit(_flash_bwd).lower(*args).compile()
    bench = _bench_kernels()
    ops = [bench.parse_op(ln) for ln in _custom_calls(compiled)]
    assert sorted(bench.classify(op) for op in ops) == sorted(
        _FLASH_SIGNATURE)
    for op in ops:
        name = bench.classify(op)
        assert name in op["name"], op["name"]
        ranks, results = _FLASH_SIGNATURE[name]
        assert [len(dims) for _, dims in op["operands"]] == ranks
        assert len(op["result"]) == results
        # q first: the readers take b*h, seq and head_dim from it
        assert op["operands"][0] == (
            "bf16", (4 * _CELL_HEADS[cell], 4096, HD)), op["operands"][0]
        assert all(dt in ("bf16", "f32") for dt, _ in op["operands"])


# --- the grouped matmul of the MoE train path -------------------------------

def _gmm(lhs, rhs, group_sizes):
    from ray_tpu.ops.pallas import grouped_matmul
    return grouped_matmul.gmm(lhs, rhs, group_sizes)


def _gmm_bwd(lhs, rhs, group_sizes):
    return jax.grad(lambda l, r: _gmm(l, r, group_sizes).astype(
        jnp.float32).sum(), argnums=(0, 1))(lhs, rhs)


def _gate_up_by_id(y, w, group_sizes, order):
    """Gate and up of the rows of y in sorted order, read by id, and the
    three gradients (PR 43): one fused call forward, one for both
    weights' gradients, one for the rows'."""
    from ray_tpu.models import moe
    inverse = jnp.argsort(order).astype(jnp.int32)

    def loss(y, w_gate, w_up):
        gate, up = moe._gate_up(y, w_gate, w_up, order, inverse,
                                group_sizes, False)
        return (gate.astype(jnp.float32) * up.astype(jnp.float32)).sum()
    return jax.value_and_grad(loss, argnums=(0, 1, 2))(y, w, w)


# the cell train-olmoe: 4 x 4096 tokens x 8 experts a token = 131,072
# rows, 64 groups, hidden 2048, expert width 1024
_ROWS, _EXPERTS, _HIDDEN, _WIDTH = 131072, 64, 2048, 1024
_TOKENS = _ROWS // 8
GMM_CASES = {
    "gmm_up": (_gmm, (_HIDDEN, _WIDTH), {"moe_gmm"}),
    "gmm_down": (_gmm, (_WIDTH, _HIDDEN), {"moe_gmm"}),
    "gmm_up_bwd": (_gmm_bwd, (_HIDDEN, _WIDTH),
                   {"moe_gmm_t", "moe_gmm_drhs"}),
    "gmm_down_bwd": (_gmm_bwd, (_WIDTH, _HIDDEN),
                     {"moe_gmm_t", "moe_gmm_drhs"}),
    # y (16,384 rows) and the 131,072 ids in place of the gathered lhs
    "gate_up_by_id": (_gate_up_by_id, (_HIDDEN, _WIDTH),
                      {"moe_gmm_rows", "moe_gmm_t", "moe_gmm_drhs_rows"}),
}


@pytest.mark.parametrize("name", sorted(GMM_CASES))
def test_grouped_matmul_compiles_for_v5e(chip, name):
    fn, (k, n), names = GMM_CASES[name]
    by_id = name.endswith("by_id")
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in (
        ((_TOKENS if by_id else _ROWS, k), jnp.bfloat16),
        ((_EXPERTS, k, n), jnp.bfloat16), ((_EXPERTS,), jnp.int32),
        *([((_ROWS,), jnp.int32)] if by_id else []))]
    compiled = jax.jit(fn).lower(*args).compile()
    bench = _bench_kernels()
    ops = [bench.parse_op(ln) for ln in _custom_calls(compiled)]
    # the kernels themselves, under their names; the benchmark's
    # reduction takes none of them for a flash or a paged kernel: they
    # land in unknown_kernel, where readers/moe_gmm.py looks
    for op in ops:
        assert bench.classify(op) == "unknown_kernel", op
    # moe_gmm__.1, transpose_jvp_moe_gmm_t__.1, ...
    found = [re.search(r"moe_gmm(_drhs_rows|_drhs|_rows|_t)?(?=_|\.|$)",
                       op["name"]) for op in ops]
    assert all(found), [op["name"] for op in ops]
    assert sorted(m.group(0) for m in found) == sorted(names)
    if by_id:
        # 8 or 9 operands, the walk's and the ids of rank 1, rows of rank
        # 2 (weights of rank 3): never the three or six rank-3 operands of
        # a flash kernel nor the five of the paged one
        for op in ops:
            ranks = [len(dims) for _, dims in op["operands"]]
            assert len(ranks) >= 8 and {1, 2} <= set(ranks), op
        # nothing of the gathered copy's shape but the rows' gradient
        big = [op["name"] for op, _ in _with_result_of(
            compiled, {(_ROWS, _HIDDEN)})]
        assert len(big) <= 2, big       # d_lhs's gather home, its reshape


def _olmoe_layer_grad(chip):
    """The train-olmoe cell's loss gradient, compiled: the program whose
    scanned layer body the step runs."""
    from ray_tpu.models import llama, moe
    cfg = _cell_config("olmoe-1b-7b-train.json", "moe", gmm_impl="pallas",
                       attn_impl="flash", attn_block_q=1024,
                       attn_block_k=1024, logits_dtype="bfloat16",
                       remat_policy="full")
    params = _shapes_of(chip, jax.eval_shape(
        lambda: moe.init_params(jax.random.PRNGKey(0), cfg)))
    batch = {k: jax.ShapeDtypeStruct((4, 4096), jnp.int32, sharding=chip)
             for k in ("tokens", "targets")}
    was = llama._on_tpu
    llama._on_tpu = lambda: True      # flash: the kernel, not the fallback
    try:
        return jax.jit(jax.grad(lambda p, b: moe.loss_fn(p, b, cfg))).lower(
            params, batch).compile()
    finally:
        llama._on_tpu = was


def test_the_olmoe_layer_writes_three_gathered_copies_not_six(chip):
    """The engagement of PR 43's mechanism is static, so a count holds
    it: of the six ``bf16[T*k, d]`` gathers a layer pass could write
    (``models/moe.py``'s docstring), the compiled gradient holds the
    combine's forward one, ONE combine gather in the backward (the
    cotangent's rows; the gates' gradient is taken from them) and the rows'
    gradient going home; y's rows are read by id, forward and
    recomputed."""
    compiled = _olmoe_layer_grad(chip)
    text = compiled.as_text()
    big = f"bf16[{_ROWS},{_HIDDEN}]"
    gathers = [ln for ln in text.splitlines()
               if re.match(r"\s*(ROOT )?%gather[\w.]* = " + re.escape(big),
                           ln)]
    where = [re.search(r'op_name="([^"]*)"', ln).group(1) for ln in gathers]
    experts = [w for w in where if "moe.experts/gather" in w]
    combine = [w for w in where if "moe.combine/gather" in w]
    assert len(experts) + len(combine) == len(where), where
    # only ``_token_sums``: in the backward, and not the recomputation
    assert all("transpose(jvp" in w and "rematted" not in w
               for w in experts), experts
    assert len({w for w in experts}) == 1, experts
    backward = {w for w in combine if "transpose(jvp" in w}
    assert len(backward) <= 1 and len(set(combine) - backward) == 1, combine
    names = {re.search(r"moe_gmm[a-z_]*?(?=_*\.|__)", op["name"]).group(0)
             for op in _kernel_ops(compiled)
             if op["class"] == "unknown_kernel"}
    assert {"moe_gmm_rows", "moe_gmm_drhs_rows"} <= names, names


# --- the decode programs hold the KV pool in place --------------------------

# the cell serve-chat-open's pool: 8 layers x 5,882 blocks x 8 KV heads
# x 16 rows x 128, bf16, 32 slots over tables of 256 blocks. The model
# around it is narrow (the compile is about the pool, and fast)
_POOL = (8, 5882, 8, 16, HD)
_SLOTS, _TABLE = 32, 256
_LAYER_BYTES = 2 * 5882 * 8 * 16 * HD     # one layer's K (or V), bf16: 193 MB
# what may have a result of the pool's shape: the kernels (which alias
# it), and what passes a buffer on without touching it
_PASS_ON = {"parameter", "tuple", "get-tuple-element", "bitcast", "while"}


def _decode_args(chip, wq=None, tp=None, cfg=None):
    """Shapes of a paged_decode_steps / paged_verify_steps call at the
    cell's pool geometry, on one described chip or (``tp``: a mesh of
    the topology's four) tensor-parallel as the engine lays them out.
    The model is narrow unless a ``cfg`` is given."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ray_tpu.llm import model as lm
    from ray_tpu.models import llama
    cfg = cfg or llama.LlamaConfig(
        vocab_size=2048, dim=8 * HD, n_layers=_POOL[0], n_heads=8,
        n_kv_heads=_POOL[2], ffn_dim=1024, dtype="bfloat16")

    def shape(s, d, spec=P()):
        return jax.ShapeDtypeStruct(
            s, d, sharding=NamedSharding(tp, spec) if tp else chip)

    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    params = jax.tree.map(
        lambda a, spec: shape(a.shape, a.dtype, spec), params,
        lm.serve_param_specs(cfg), is_leaf=lambda x: isinstance(x, P))
    pool = {k: shape(_POOL, jnp.bfloat16,
                     P(None, None, "tensor", None, None)) for k in "kv"}
    ids = shape((_SLOTS,), jnp.int32)
    args = [params, pool, shape((_SLOTS, _TABLE), jnp.int32), ids]
    if wq:
        return pool, args + [shape((_SLOTS, wq), jnp.int32), cfg]
    return pool, args + [ids, shape((_SLOTS,), jnp.float32),
                         shape((2,), jnp.uint32), cfg, 8, None, None]


def _top_level_ops(compiled):
    """(parsed instruction, its line) for every instruction of the
    compiled program outside the fused computations (what a fusion makes
    is its own result's shape)."""
    bench = _bench_kernels()
    text = compiled.as_text()
    fused = set(re.findall(r"calls=%([\w.\-]+)", text))
    computation = None
    for ln in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", ln)
        if head:
            computation = head.group(1)
        if head or computation in fused:
            continue
        yield bench.parse_op(ln.strip().removeprefix("ROOT ")), ln


def _with_result_of(compiled, shapes):
    """``_top_level_ops`` with a result of one of ``shapes``, the Pallas
    kernels apart."""
    for op, ln in _top_level_ops(compiled):
        if shapes & {dims for _, dims in op["result"]} \
                and not op.get("custom_kernel"):
            yield op, ln


def _pool_shaped(compiled, tp=1) -> list:
    """(opcode, name) of every instruction of the compiled program
    with a result of the pool's shape — one layer of it, all layers
    stacked or flat; under ``tp`` a shard's heads — the Pallas
    kernels apart."""
    layers, blocks, *block = _POOL
    block[0] //= tp
    shapes = {(n, *block) for n in (blocks, layers * blocks)} \
        | {(layers, blocks, *block)}
    return [(op["opcode"], op["name"])
            for op, _ in _with_result_of(compiled, shapes)]


def _kernel_ops(compiled) -> list:
    bench = _bench_kernels()
    ops = [bench.parse_op(ln) for ln in _custom_calls(compiled)]
    for op in ops:
        op["class"] = bench.classify(op)
    return ops


def _compile_chat_decode(topo, chip, tp, cfg=None):
    """(parameter shapes, the compiled paged_decode_steps, n = 8) at the
    chat cell's pool geometry, on one chip or over the topology's four."""
    import numpy as np
    from jax.sharding import Mesh
    from ray_tpu.llm import kvcache
    mesh = Mesh(np.asarray(topo.devices), ("tensor",)) if tp > 1 else None
    pool, args = _decode_args(chip, tp=mesh, cfg=cfg)
    return args[0], kvcache.decode_steps_program(
        pool, impl="paged_flash", mesh=mesh).lower(*args).compile()


@pytest.mark.parametrize("tp", [1, 4], ids=["one_chip", "tp4"])
def test_decode_steps_update_the_pool_in_place(topo, chip, tp):
    """paged_decode_steps (n = 8) as the chat cell runs it, compiled
    for the described v5e: the donated pool is the ONE pool-sized
    buffer of the program. Before PR 31 XLA converted the whole pool
    between its own layout for the one-row write and the kernel's, on
    every layer: eight pool-sized copies a step, 3.9 GB of
    temporaries, three quarters of the cell's device time — and
    nothing on a CPU showed it. This is the guard."""
    _, compiled = _compile_chat_decode(topo, chip, tp)
    # (a) nothing but the kernels makes a pool-sized array
    held = _pool_shaped(compiled, tp)
    assert held and {code for code, _ in held} <= _PASS_ON, held
    # (b) no pool-sized temporary: under ONE layer's K, and the pool
    # that comes in is the pool that goes out
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < _LAYER_BYTES // tp
    assert mem.alias_size_in_bytes >= 2 * _POOL[0] * _LAYER_BYTES // tp
    # (c) the benchmark's reduction tells the walk from the writer,
    # and each carries its name
    ops = _kernel_ops(compiled)
    assert {op["class"] for op in ops} == {"paged_decode",
                                           "unknown_kernel"}
    names = {"paged_decode": "paged_decode", "unknown_kernel": "kv_write"}
    for op in ops:
        assert names[op["class"]] in op["name"], op["name"]
    # the writer's signature, as the issue fixed it
    writer = next(op for op in ops if op["class"] == "unknown_kernel")
    assert [(d, len(s)) for d, s in writer["operands"]] == [
        ("s32", 1), ("s32", 1), ("bf16", 3), ("bf16", 3), ("bf16", 4),
        ("bf16", 4)]


def test_verify_steps_write_the_pool_in_place(chip):
    """paged_verify_steps (w = 5): the same writer (160 entries), then
    an XLA gather over the flat pool, the view of every slot's table
    (0.57 GB at this geometry: what a fused verify kernel would
    save). No cell runs it; what holds is held: no pool-sized copy,
    temporaries under one stacked pool."""
    from ray_tpu.llm import kvcache
    pool, args = _decode_args(chip, wq=5)
    compiled = kvcache.verify_steps_program(
        pool, 5, impl="paged_flash").lower(*args).compile()
    held = _pool_shaped(compiled)
    assert held and {code for code, _ in held} <= _PASS_ON, held
    assert compiled.memory_analysis().temp_size_in_bytes \
        < _POOL[0] * _LAYER_BYTES
    ops = _kernel_ops(compiled)
    assert {op["class"] for op in ops} == {"unknown_kernel"}
    assert all("kv_write" in op["name"] for op in ops)


# --- window layers and a served expert layer (PR 35) -------------------------
# the cell serve-exaone-reason-open: 8 KV heads, group 8, head 128, 32
# slots over tables of 256 blocks; the window layers' pool is 32 rings of
# 10 blocks; a step's expert layer sees 256 rows, 16 held experts of
# width 2048 beside hidden 6144

def _paged_window(q, k, v, tables, lengths):
    return pa.paged_attention(q, k, v, tables, lengths, window=128)


def _flash_band(q, k, v):
    return A.flash_attention(q, k, v, causal=True, window=128)


def _gmm_stacked(lhs, stack, sizes, layer):
    from ray_tpu.ops.pallas import grouped_matmul
    return grouped_matmul.gmm_stacked(lhs, stack, sizes, layer[0])


_BF, _I32 = jnp.bfloat16, jnp.int32
HYBRID_CASES = {
    # name: (function, shapes, {kernel name: the benchmark's class})
    "paged_decode_window": (
        _paged_window,
        (((32, 8, 8, HD), _BF), ((6 * 321, 8, 16, HD), _BF),
         ((6 * 321, 8, 16, HD), _BF), ((32, 256), _I32), ((32,), _I32)),
        {"paged_decode_window": "paged_decode"}),
    "flash_prefill_band_2048": (
        _flash_band, (((1, 2048, 64, HD), _BF),) * 3,
        {"flash_fwd_window": "flash_fwd"}),
    "gmm_decode_up": (
        _gmm_stacked,
        (((256, 6144), _BF), ((7, 16, 6144, 2048), _BF), ((16,), _I32),
         ((1,), _I32)), {"moe_gmm_decode": "unknown_kernel"}),
    "gmm_decode_down": (
        _gmm_stacked,
        (((256, 2048), _BF), ((7, 16, 2048, 6144), _BF), ((16,), _I32),
         ((1,), _I32)), {"moe_gmm_decode": "unknown_kernel"}),
    "gmm_prefill_up": (
        _gmm_stacked,
        (((2048 * 8, 6144), _BF), ((7, 16, 6144, 2048), _BF), ((16,), _I32),
         ((1,), _I32)), {"moe_gmm": "unknown_kernel"}),
}


@pytest.mark.parametrize("name", sorted(HYBRID_CASES))
def test_window_and_expert_kernels_compile_for_v5e(chip, name):
    """The window walk and the band keep the operand signatures the
    benchmark's reduction knows the paged and flash kernels by (so a
    program that runs them still counts as decode / prefill), and
    carry names of their own; the serving grouped matmuls read the
    stacked weights in place (no copy of a layer beside the call)."""
    fn, shapes, names = HYBRID_CASES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    bench = _bench_kernels()
    ops = [bench.parse_op(ln) for ln in _custom_calls(compiled)]
    assert ops
    (kernel, cls), = names.items()
    for op in ops:
        assert bench.classify(op) == cls, op
        assert re.search(kernel + r"(?=_|\.|$)", op["name"]), op["name"]
    if "gmm" in name:
        stack = shapes[1][0]
        # nothing of the stack's size, or a layer's, but the argument
        assert compiled.memory_analysis().temp_size_in_bytes \
            < 2 * stack[1] * stack[2] * stack[3] // 4


def _cell_config(file, family, model=None, **overrides):
    """The program's config of ``benchmarks/configs/<file>``, as the
    cell builds it (``model``: keys of the file changed first)."""
    import json
    import sys
    bench_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             os.pardir, "benchmarks")
    with open(os.path.join(bench_dir, "configs", file)) as f:
        published = {**json.load(f), **(model or {})}
    sys.path.insert(0, bench_dir)
    try:
        from harness import spec
        return spec.family(family).config(published, **overrides)
    finally:
        sys.path.remove(bench_dir)


def _hybrid_config(**kw):
    return _cell_config("k-exaone-236b-a23b-serve-ep8.json", "exaone_moe",
                        gmm_impl="pallas", **kw)


def _shapes_of(chip, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        tree)


def _hybrid_params(chip, cfg):
    from ray_tpu.models import moe
    return _shapes_of(chip, jax.eval_shape(
        lambda: moe.init_params(jax.random.PRNGKey(0), cfg)))


def _hybrid_decode(chip, cfg):
    """(params, pool, the compiled paged_decode_steps, n = 8) at the
    cell's pool geometry: 32 slots, the global layers' 8,449 blocks,
    the window layers' 32 rings."""
    from ray_tpu.llm import kvcache
    from ray_tpu.llm import model as lm

    def shape(s, d):
        return jax.ShapeDtypeStruct(s, d, sharding=chip)
    params = _hybrid_params(chip, cfg)
    kinds = lm.kind_layers(cfg)
    ring = kvcache.window_ring_blocks(128, 16, 8)
    glob = (len(kinds["global"]), 8449, 8, 16, HD)
    win = (len(kinds["window"]), 32 * ring + 1, 8, 16, HD)
    pool = {"k": shape(glob, _BF), "v": shape(glob, _BF),
            "wk": shape(win, _BF), "wv": shape(win, _BF)}
    ids = shape((32,), _I32)
    tables = {k: shape((32, 256), _I32) for k in ("global", "window")}
    compiled = kvcache.decode_steps_program(pool, impl="paged_flash").lower(
        params, pool, tables, ids, ids, shape((32,), jnp.float32),
        shape((2,), jnp.uint32), cfg, 8, None, None).compile()
    return params, pool, compiled


def _assert_decode_signatures(ops):
    """What the benchmark's readers know the decode kernels by: a walk's
    five operands (two of scalars, three of rank 4) and a writer's six.
    A slot that holds no request travels inside them (PR 57: a length of
    0, a negative block), not beside them."""
    for op in ops:
        ranks = [(d, len(s)) for d, s in op["operands"]]
        if op["class"] == "paged_decode":
            assert ranks == [("s32", 2), ("s32", 1)] + [("bf16", 4)] * 3, op
        elif "write" in op["name"]:
            assert ranks[:2] == [("s32", 1), ("s32", 1)] \
                and len(ranks) == 6 and ranks[2] == ranks[3] \
                and ranks[4] == ranks[5], op


@pytest.fixture(scope="module")
def hybrid_decode(chip):
    """The hybrid cell's decode program, compiled once for its tests."""
    return _hybrid_decode(chip, _hybrid_config())


def test_the_hybrid_cells_decode_program_compiles_for_v5e(hybrid_decode):
    """paged_decode_steps (n = 8) at the cell's published widths and
    pool geometry, on a described v5e: every layer calls the paged
    kernel once a step (6 window walks, 2 global) and the writer once,
    the seven sparse layers three decode-shape grouped matmuls each,
    both pools are updated in place, and the weights and the pools fit
    one chip beside the program's temporaries."""
    _, pool, compiled = hybrid_decode
    ops = _kernel_ops(compiled)
    names = {}
    for op in ops:
        name = re.sub(r"[._]*\d*$", "", op["name"])
        names[name] = names.get(name, 0) + 1
    assert names == {"paged_decode_window": 6, "paged_decode": 2,
                     "kv_write": 8, "moe_gmm_decode": 21}, names
    # the reduction's steps = paged calls / layers holds: 8 a step
    assert sum(op["class"] == "paged_decode" for op in ops) == 8
    _assert_decode_signatures(ops)
    mem = compiled.memory_analysis()
    import numpy as np
    pool_bytes = sum(2 * int(np.prod(a.shape)) for a in pool.values())
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.5e9


# --- the serving forwards read wq, wk and wv in place (PR 36) ----------------
# Without the fence in ``llm/model.py _qkv`` XLA turns the three leaves
# (layer, out, in): the stack copied in a decode program's entry, a layer's
# slice of it written out again on every step where ``_run_layers`` unrolls
# a segment (13% of the hybrid cell's device time), a transposing copy a
# layer in prefill. Nothing on a CPU shows it.

def _assert_projections_in_place(compiled, params, tp=1,
                                 leaves=("wq", "wk", "wv"),
                                 prefetch=("copy-start", "copy-done")):
    """No instruction of the compiled program has a result of the shape
    of wq, wk or wv (``leaves``; under ``tp`` a shard's columns) - one
    layer or the stack, as held or with its last two axes turned - but
    what passes a buffer on, and the compiler's prefetch: a copy
    (``prefetch``: asynchronous) that keeps the held layout and lands in
    the fast memory space S(1), the weight's ONE read."""
    shapes = set()
    for stack in ("layers", "dense_layers"):
        for w in leaves:
            if w in params.get(stack, {}):
                n, *lead, rows, cols = params[stack][w].shape
                for dims in ((*lead, rows, cols // tp),
                             (*lead, cols // tp, rows)):
                    shapes |= {dims, (1, *dims), (n, *dims)}
    moved = []
    for op, ln in _with_result_of(compiled, shapes):
        code = op["opcode"]
        if code in prefetch:
            result = ln.split(" " + code + "(")[0]
            orders = set(re.findall(r"\]\{([\d,]*)", result)) - {""}
            if "S(1)" in result and len(orders) == 1:
                continue
        if code not in _PASS_ON:
            moved.append((code, op["name"]))
    assert not moved, moved


def _in_place_case(topo, chip, hybrid_decode, case):
    """(parameter shapes, compiled program, tensor-parallel size)."""
    from ray_tpu.models import llama
    chat = _cell_config("mistral-7b-v0.3-serve.json", "llama")
    if case.startswith("chat_decode"):
        tp = 4 if case.endswith("tp4") else 1
        return *_compile_chat_decode(topo, chip, tp, cfg=chat), tp
    if case == "hybrid_decode":
        params, _, compiled = hybrid_decode
        return params, compiled, 1
    if case == "chat_prefill":
        cfg, params = chat, _shapes_of(chip, jax.eval_shape(
            lambda: llama.init_params(jax.random.PRNGKey(0), chat)))
    else:
        cfg = _hybrid_config()
        params = _hybrid_params(chip, cfg)
    # the flash kernel named: under pytest 'auto' sees the CPU and takes
    # the reference
    import dataclasses
    from ray_tpu.llm import model as lm
    bucket = 2048
    return params, lm.prefill.lower(
        params, jax.ShapeDtypeStruct((bucket,), _I32, sharding=chip),
        jax.ShapeDtypeStruct((), _I32, sharding=chip),
        dataclasses.replace(cfg, attn_impl="flash"), bucket).compile(), 1


@pytest.mark.parametrize("case", ["chat_decode", "chat_decode_tp4",
                                  "chat_prefill", "hybrid_decode",
                                  "hybrid_prefill"])
def test_serving_forwards_read_the_projections_in_place(
        topo, chip, hybrid_decode, case):
    """Both serve cells' decode programs (n = 8; the chat cell's at
    Mistral's PUBLISHED widths, on one chip and tensor-parallel over
    four) and their prefill programs at the largest bucket (2,048):
    nothing but the products reads wq, wk or wv - no copy of the stack
    in the entry, no slice of it written out, no transpose - and the
    decode programs' temporaries stay under 0.2 GB (1.76 GB and 0.34 GB
    before PR 36)."""
    params, compiled, tp = _in_place_case(topo, chip, hybrid_decode, case)
    _assert_projections_in_place(compiled, params, tp)
    assert "tpu_custom_call" in compiled.as_text()
    if "decode" in case:
        assert compiled.memory_analysis().temp_size_in_bytes < 0.2e9


def test_the_period_scan_reads_the_projections_stack_in_place(chip):
    """``_run_layers``' scan over a repeated PERIOD of layer kinds (no
    cell runs it; the published depth would: eleven periods of four):
    the hybrid configuration at 9 layers and 4 held experts is the dense
    layer and one segment of two periods. Since PR 36 its decode program
    no longer re-lays-out the stacked wq / wk / wv in its entry (2.56 GB
    of temporaries before). What is left is the scan's own: each turn
    copies its period's rows of every leaf the body indexes (four
    layers' wq and wo 403 MB each, wk / wv 50, the shared expert's three
    101: 1.21 GB): PERF.md section 7."""
    cfg = _hybrid_config(model={"num_hidden_layers": 9, "num_experts": 4})
    params, _, compiled = _hybrid_decode(chip, cfg)
    _assert_projections_in_place(compiled, params)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.3e9


# --- the linear-attention train cell's step ---------------------------------

def _cell_on_mesh(topo, file, **overrides):
    """(config, mesh over the described chips, deployment) of
    ``benchmarks/configs/<file>``'s train cell."""
    import json
    from ray_tpu.parallel import mesh as pmesh
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           os.pardir, "benchmarks", "configs", file)) as f:
        dep = json.load(f)["deployment"]
    cfg = _cell_config(file, dep.get("family", "llama"), **overrides,
                       **dep["model_overrides"])
    sizes = {"data": 1, "context": 1, **dep["mesh"]}
    mesh = pmesh.make_mesh(pmesh.MeshSpec(**sizes),
                           devices=topo.devices[:math.prod(sizes.values())])
    return cfg, mesh, dep


def _compile_cell_step(topo, file, model, **overrides):
    """(compiled step, the state's bytes on one device, the deployment)
    of ``benchmarks/configs/<file>``'s train step as its cell runs it:
    ``make_train_step`` on the cell's mesh over the described chips, the
    state's shardings those of ``init_fn``'s compiled outputs, batch and
    rows the cell's, the flash kernels in."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ray_tpu.models import llama
    from ray_tpu.parallel import mesh as pmesh
    cfg, mesh, dep = _cell_on_mesh(topo, file, **overrides)
    init_fn, step_fn = pmesh.make_train_step(cfg, mesh, model=model)
    with mesh:
        init = init_fn.lower(jax.ShapeDtypeStruct((2,), jnp.uint32)).compile()
    state = jax.tree.map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        jax.eval_shape(init_fn, jax.random.PRNGKey(0)),
        init.output_shardings)
    rows = NamedSharding(mesh, P(("data", "fsdp"), "context"))
    batch = {k: jax.ShapeDtypeStruct((dep["batch"], 4096), jnp.int32,
                                     sharding=rows)
             for k in ("tokens", "targets")}
    was = llama._on_tpu
    llama._on_tpu = lambda: True      # flash: the kernel, not the fallback
    try:
        with mesh:
            compiled = step_fn.lower(state, batch).compile()
    finally:
        llama._on_tpu = was
    held = sum(math.prod(s.sharding.shard_shape(s.shape)) * s.dtype.itemsize
               for s in jax.tree.leaves(state))
    return compiled, held, dep


def _assert_state_aliased(compiled, held, dep):
    """The compiled step's memory analysis, after asserting that every
    byte handed in but the batch's is aliased into an output: the
    donated ``TrainState`` (``held`` logical bytes a device; the
    compiler counts each leaf in whole tiles, so no fewer)."""
    mem = compiled.memory_analysis()
    batch = 2 * dep["batch"] // dep["mesh"]["fsdp"] * 4096 * 4
    assert mem.alias_size_in_bytes >= held, (mem, held)
    assert mem.argument_size_in_bytes - mem.alias_size_in_bytes == batch, mem
    return mem


def _remat_matmuls(compiled) -> list:
    """Names of the matmuls XLA's own rematerialisation pass made
    (``fusion.386.remat3`` and its like): the products it computes again
    where a program does not fit its memory plan. A forward recomputed by
    ``jax.checkpoint`` is an ordinary instruction and carries no such
    suffix; a rematerialised bitcast, copy or broadcast costs next to
    nothing and is let through."""
    return [name for name, op, rest in re.findall(
        r"%([\w.-]*remat[\w.-]*) = \S+ ([\w-]+)\(([^\n]*)",
        compiled.as_text())
        if op in ("fusion", "convolution", "dot") and re.search(
            r"dot_general|convolution|kind=kOutput", rest)]


def _layer_loops(text: str) -> list:
    """The scheduled instructions of each computation of a compiled
    train step's ``text`` that calls a flash kernel: the layer loops'
    bodies, forward and backward."""
    bodies = re.split(r"\n(?=(?:ENTRY )?%[\w.-]+ \([^\n]*\) -> [^\n]*\{\n)",
                      text)
    return [b.splitlines() for b in bodies if "flash_fwd" in b
            and "tpu_custom_call" in b]


def _chunk_exchanges(body: list, shape: str) -> list:
    """For each ``collective-permute-start`` of ``shape`` in a scheduled
    computation, the matmul fusions between it and its ``-done``."""
    out = []
    for i, ln in enumerate(body):
        start = re.match(
            rf"\s*%(collective-permute-start[\w.]*) = \({re.escape(shape)}",
            ln)
        if start:
            done = next(j for j in range(i, len(body)) if re.search(
                rf"collective-permute-done\(%{re.escape(start.group(1))}\)",
                body[j]))
            out.append([m.group(1) for m in (
                re.match(r"\s*%([\w.-]+) = .* fusion\(.*(?:dot_general|"
                         r"convolution)", x) for x in body[i:done]) if m])
    return out


@pytest.mark.parametrize("file", [
    "mistral-7b-v0.3-train.json", "yi-1.5-34b-train-4chip.json"],
    ids=["dense_1chip", "yi34b_4chip"])
def test_the_dense_cells_steps_alias_their_state_and_recompute_nothing(
        topo, file):
    """``step_fn`` donates its ``TrainState``: the compiled step of
    ``train-dense-1chip`` and of ``train-yi34b-4chip`` (a chip of the
    four) aliases every byte of params, moments and counters into its
    outputs, so its plan is the state ONCE plus the temporaries, fits the
    chip, and the compiler rematerialises no matmul to make it fit
    (undonated: 28 and 29 ``.remat`` instructions, three and ten of them
    whole FFN / projection products a layer).

    The same compile, PR 55: over the four chips' ``tensor`` pair no
    layer sums the residual stream with an ``all-reduce`` (this compiler
    runs one blocking: 5.7 ms each, 25 a step); each of a loop body's
    sums is ``_tp_chunks`` exchanges of a chunk, and all but a sum's
    last chunk have a matmul between their start and their wait. One
    chip's step has no exchange and no chunk."""
    from ray_tpu.models import llama
    compiled, held, dep = _compile_cell_step(topo, file, llama)
    mem = _assert_state_aliased(compiled, held, dep)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9, mem
    assert not _remat_matmuls(compiled)
    n = llama._tp_chunks(4096)
    rows = dep["batch"] // dep["mesh"].get("fsdp", 1)
    text = compiled.as_text()
    loops = _layer_loops(text)
    assert len(loops) == 2
    if dep["mesh"].get("tensor", 1) == 1:
        assert "collective-permute" not in text
        assert f"bf16[{rows},{4096 // n}," not in text
        return
    stream = f"bf16[{rows},4096,7168]"
    assert not [ln for body in loops for ln in body
                if re.search(rf"= {re.escape(stream)}\S* all-reduce\(", ln)
                or " all-to-all(" in ln]     # nor reshards a chunk
    exchanges = [_chunk_exchanges(body, f"bf16[{rows},{4096 // n},7168]")
                 for body in loops]
    # forward: o @ wo and the MLP's down product; backward: the first
    # again (remat) and both norms' cotangents
    assert sorted(len(e) for e in exchanges) == [2 * n, 3 * n], exchanges
    for e in exchanges:
        assert sum(not hidden for hidden in e) <= len(e) // n, e


def test_the_parity_slices_forward_exchanges_its_sums_too(topo):
    """What decides ``correct`` in ``train-yi34b-4chip`` runs the changed
    code: the harness's parity forward (``benchmarks/harness/train_cell.py
    parity``: ``llama.forward`` on the cell's mesh over ``parity_tokens``
    positions of one row a shard) makes each layer's two sums as four
    exchanges of 256 rows, with no ``all-reduce`` of the stream in its
    layer loop and no ``all-to-all`` (left to itself GSPMD reshards
    chunks this small: ``_tensor_pair_products keep``)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ray_tpu.models import llama
    from ray_tpu.parallel import mesh as pmesh
    cfg, mesh, dep = _cell_on_mesh(topo, "yi-1.5-34b-train-4chip.json")
    rows, s = dep["mesh"]["fsdp"], dep["parity_tokens"]
    init_fn, _ = pmesh.make_train_step(cfg, mesh, model=llama)
    with mesh:
        init = init_fn.lower(jax.ShapeDtypeStruct((2,), jnp.uint32)).compile()
    params = jax.tree.map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        jax.eval_shape(init_fn, jax.random.PRNGKey(0)),
        init.output_shardings).params
    tokens = jax.ShapeDtypeStruct(
        (rows, s), jnp.int32,
        sharding=NamedSharding(mesh, P(("data", "fsdp"), "context")))
    was = llama._on_tpu
    llama._on_tpu = lambda: True
    try:
        with mesh:
            text = jax.jit(lambda p, t: llama.forward(p, t, cfg, mesh)).lower(
                params, tokens).compile().as_text()
    finally:
        llama._on_tpu = was
    n = llama._tp_chunks(s)
    (loop,) = _layer_loops(text)
    assert not [ln for ln in loop if re.search(
        rf"= bf16\[1,{s},7168\]\S* all-reduce\(", ln) or " all-to-all(" in ln]
    assert len(_chunk_exchanges(loop, f"bf16[1,{s // n},7168]")) == 2 * n


def test_the_linear_attention_cells_step_compiles_for_v5e(topo):
    """``train-qwen3next-ep16``'s whole train step (``make_train_step`` on a
    mesh of one described chip, batch and rows as the cell runs them):
    the flash kernels compile at head 256 with 16 / 2 heads, forward and
    backward; the step aliases its whole state (``step_fn`` donates it),
    fits the chip by the compiler's memory analysis, the state counted
    once, and is sized like a deployment's (11 GB or more); and it holds
    no custom call the benchmark would not know: every one is a flash
    kernel by its operand signature or a grouped matmul by its name, so
    that ``moe_gmm_dev_ms.train`` (every unknown kernel's time) counts
    the grouped matmuls alone. The chunked delta rule is plain XLA."""
    from ray_tpu.models import moe
    compiled, held, dep = _compile_cell_step(
        topo, "qwen3-next-80b-a3b-train-ep16.json", moe, gmm_impl="pallas")
    mem = _assert_state_aliased(compiled, held, dep)
    total = mem.argument_size_in_bytes + mem.output_size_in_bytes \
        + mem.temp_size_in_bytes - mem.alias_size_in_bytes
    assert 11e9 <= total < 15.75e9, total
    ops = _kernel_ops(compiled)
    flash = {"flash_fwd": 0, "flash_bwd_dq": 0, "flash_bwd_dkv": 0}
    for op in ops:
        if op["class"] in flash:
            flash[op["class"]] += 1
            assert op["class"] in op["name"], op["name"]
            # q first: b * heads (the KV heads repeated), rows, head 256
            assert op["operands"][0] == (
                "bf16", (dep["batch"] * 16, 4096, 256)), op["operands"][0]
        else:
            assert op["class"] == "unknown_kernel" \
                and "moe_gmm" in op["name"], op["name"]
    # one full layer in four: forward, the remat's forward, one backward
    assert flash == {"flash_fwd": 2, "flash_bwd_dq": 1, "flash_bwd_dkv": 1}
    # the grouped matmuls: eight a layer on the work list (``moe._held_sum``:
    # 20,480 rows of the 163,840), and the same eight on the full path,
    # the other side of the layer's branch
    assert len(ops) - 4 == 2 * 4 * 8, [op["name"] for op in ops]
    # the row ids, ``moe_gmm_rows``' sixth prefetched scalar array
    assert {op["operands"][5] for op in ops
            if "moe_gmm_rows" in op["name"]} \
        == {("s32", (20480,)), ("s32", (163840,))}
    # the chunk inverse starts from directly solved diagonal blocks: of its
    # twelve float32 products a pass (78 a step: a layer's forward, the
    # remat's forward and two in its backward, three layers) the levels
    # under ``gated_delta.SOLVE_BLOCK`` are gone
    solves = [ln for ln in compiled.as_text().splitlines()
              if "operand_precision={high,high}" in ln
              and re.search(r" = f32\[64,4,32,64,64\]", ln)]
    assert 0 < len(solves) <= 42, len(solves)


def test_the_linear_mixer_moves_no_float32_activation(chip):
    """``models/moe.py _gated_delta_net``, forward and backward at the
    cell's shapes (batch 4 x 4096, hidden 2048; no remat around it): between
    its two projections the mixer is head-major and its elementwise stages
    keep bf16 residuals only, so the compiled program holds NO relayout and
    no stored broadcast of an activation in float32 (before PR 47: nine
    ``copy f32[2048,8,32,128]`` and its like, the L2 norm's ``rsqrt``
    broadcast to full size, the conv's float32 result kept for the
    backward), and its bytes stay under a cap set from the finished change
    (34.9 GB by the compiler's count, 47.4 at the parent; the chunked rule
    itself is 30 of them) with 15% of room."""
    from ray_tpu.models import moe
    file = "qwen3-next-80b-a3b-train-ep16.json"
    cfg = _cell_config(file, "qwen3_next")
    mp = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype, sharding=chip),
        jax.eval_shape(lambda: moe.init_params(jax.random.PRNGKey(0), cfg))[
            "linear_layers"])
    y = jax.ShapeDtypeStruct((4, 4096, cfg.dim), jnp.bfloat16, sharding=chip)

    def both(y, mp, ct):
        out, vjp = jax.vjp(lambda y, mp: moe._gated_delta_net(y, mp, cfg),
                           y, mp)
        return out, vjp(ct)
    compiled = jax.jit(both).lower(y, mp, y).compile()
    big = 4 * 4096 * cfg.dim
    moved = [(op["opcode"], op["name"], dims)
             for op, _ in _top_level_ops(compiled)
             if op["opcode"] in ("copy", "reshape", "broadcast")
             for dtype, dims in op["result"]
             if dtype == "f32" and math.prod(dims) >= big]
    assert not moved, moved
    assert "tpu_custom_call" not in compiled.as_text()
    assert compiled.cost_analysis()["bytes accessed"] < 1.15 * 34.9e9


# --- latent attention: the pool's third kind (PR 51) --------------------------
# the cell serve-mistral4-longdoc-open: 32 heads over ONE row a position,
# c 256 | kr 64 in 128 lanes, blocks of 128 rows, 32 slots over tables of
# 208 blocks, 9 layers of 2,373 blocks

def _latent_walk(q, c, r, tables, lengths):
    return pa.latent_decode(q, c, r, tables, lengths, sm_scale=0.195)


LATENT_CASES = {
    # name: (function, shapes, the kernel's name, the benchmark's class)
    "latent_decode": (
        _latent_walk,
        (((32, 32, 384), _BF), ((9 * 2373, 128, 256), _BF),
         ((9 * 2373, 128, 128), _BF), ((32, 208), _I32), ((32,), _I32)),
        "latent_decode", "paged_decode"),
    "latent_write": (
        pa.latent_write,
        (((9 * 2373, 128, 256), _BF), ((9 * 2373, 128, 128), _BF),
         ((32,), _I32), ((32,), _I32), ((32, 256), _BF), ((32, 128), _BF)),
        "latent_write", "unknown_kernel"),
}


@pytest.mark.parametrize("name", sorted(LATENT_CASES))
def test_latent_kernels_compile_for_v5e(chip, name):
    """The latent walk has the paged-decode kernel's operand signature
    (tables, lengths, the queries, two pools seen as one shared KV head:
    a program that runs it counts as decode in the benchmark's
    reduction), the row writer falls into no flash class, and each
    carries its own name."""
    fn, shapes, kernel, cls = LATENT_CASES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    bench = _bench_kernels()
    ops = [bench.parse_op(ln) for ln in _custom_calls(compiled)]
    assert len(ops) == 1
    assert bench.classify(ops[0]) == cls, ops[0]
    assert re.search(kernel + r"(?=_|\.|$)", ops[0]["name"]), ops[0]["name"]


def _latent_config(**kw):
    return _cell_config("mistral-small-4-119b-serve-ep8.json", "mistral4",
                        gmm_impl="pallas", **kw)


LATENT_LEAVES = ("wq_a", "wq_b", "wkv_a", "wk_b", "wv_b", "wo")


def test_the_latent_cells_decode_program_reads_rows_and_weights_in_place(
        chip):
    """paged_decode_steps (n = 8) at the cell's published widths and pool
    geometry, on a described v5e: every layer calls the latent walk and
    the row writer once a step and three decode-shape grouped matmuls;
    the pool (c and kr) is the program's ONE pool-sized buffer, updated
    in place; nothing but the products reads the latent projections (no
    copy of a stack, no slice of one written out, no transpose: wk_b and
    wv_b lie by head as the absorbed products take them); weights and
    pool fit one chip beside the temporaries."""
    import numpy as np
    from ray_tpu.llm import kvcache
    cfg = _latent_config()
    params = _hybrid_params(chip, cfg)

    def shape(s, d):
        return jax.ShapeDtypeStruct(s, d, sharding=chip)
    pool = {"c": shape((9, 2373, 128, 256), _BF),
            "kr": shape((9, 2373, 128, 128), _BF)}
    ids = shape((32,), _I32)
    compiled = kvcache.decode_steps_program(pool, impl="paged_flash").lower(
        params, pool, {"latent": shape((32, 208), _I32)}, ids, ids,
        shape((32,), jnp.float32), shape((2,), jnp.uint32), cfg, 8, None,
        None).compile()
    ops = _kernel_ops(compiled)
    names = {}
    for op in ops:
        name = re.sub(r"[._]*\d*$", "", op["name"])
        names[name] = names.get(name, 0) + 1
    assert names == {"latent_decode": 1, "latent_write": 1,
                     "moe_gmm_decode": 3}, names     # one scanned layer
    assert {op["class"] for op in ops} == {"paged_decode", "unknown_kernel"}
    _assert_decode_signatures(ops)
    # (a) nothing but the kernels makes a pool-sized array
    pool_shapes = {tuple(a.shape[i:]) for a in pool.values() for i in (0, 1)} \
        | {(a.shape[0] * a.shape[1], *a.shape[2:]) for a in pool.values()}
    held = [(op["opcode"], op["name"])
            for op, _ in _with_result_of(compiled, pool_shapes)]
    assert held and {code for code, _ in held} <= _PASS_ON, held
    mem = compiled.memory_analysis()
    pool_bytes = sum(2 * int(np.prod(a.shape)) for a in pool.values())
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 0.2e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.5e9
    # (b) the latent projections are read where they lie (the narrow
    # wkv_a stack, 24 MB, is brought to fast memory once a program, as
    # it lies: its one read in eight steps)
    _assert_projections_in_place(
        compiled, params, leaves=LATENT_LEAVES,
        prefetch=("copy", "copy-start", "copy-done"))


# --- the state-space cell: a decode step's states move in place -----------

# nemotron-3-nano-30b-a3b-serve-ep8: 12 state layers x 64 slots, a state of
# 64 heads x 64 x 128 float32 in 8 groups
_STATES = (12, 64, 64, 64, 128)
_STATE_SHAPES = {_STATES, _STATES[1:], (_STATES[0] * _STATES[1], *_STATES[2:])}


def _ssm_config(**kw):
    return _cell_config("nemotron-3-nano-30b-a3b-serve-ep8.json",
                        "nemotron_h", gmm_impl="pallas", **kw)


def _assert_state_step_signature(op):
    """The state kernel as the benchmark's reduction meets it: by name, and
    of NO class the readers count by (five operands of which the first two
    are s32 and the last three of rank 4 would be a ``paged_decode``, and
    ``scope_dev_ms_counted.steps_in_profile`` would count the state layers'
    calls as decode steps)."""
    assert re.search(r"ssm_step(?=_|\.|$)", op["name"]), op["name"]
    assert op["class"] == "unknown_kernel", op
    assert [(d, len(s)) for d, s in op["operands"]] == [
        ("s32", 1), ("s32", 1), ("f32", 2), ("f32", 2), ("f32", 2),
        ("f32", 3), ("f32", 3), ("f32", 3), ("f32", 4)], op


def test_the_state_step_kernel_compiles_for_v5e(chip):
    """``ssm_step`` at the cell's shapes with the layer's index traced: one
    custom call, the stack of states aliased in to out (1.61 GB) and no
    temporary of a layer's size beside it."""
    import numpy as np
    from ray_tpu.ops.pallas import ssm_step

    def shape(s, d=jnp.float32):
        return jax.ShapeDtypeStruct(s, d, sharding=chip)
    _, slots, h, p, n = _STATES
    compiled = jax.jit(ssm_step.ssm_step, donate_argnums=(0,)).lower(
        shape(_STATES), shape((), _I32), shape((slots,), _I32),
        shape((), _I32), shape((slots, h, p)), shape((slots, h)),
        shape((h,)), shape((slots, 8, n)), shape((slots, 8, n)),
        shape((h,))).compile()
    ops = _kernel_ops(compiled)
    assert len(ops) == 1, [op["name"] for op in ops]
    _assert_state_step_signature(ops[0])
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 4 * int(np.prod(_STATES))
    assert mem.temp_size_in_bytes < 4 * int(np.prod(_STATES[2:]))
    assert ssm_step.chunk_heads(h, p, n) == 8


def test_the_state_cells_decode_program_moves_the_states_in_place(chip):
    """paged_decode_steps (n = 8) at the cell's published widths and pool
    geometry, on a described v5e (the twin of
    test_decode_steps_update_the_pool_in_place for the fourth cache kind):
    a turn of the layer scan (a period MEMEM*E) holds the state kernel
    three times, 12 a step, under the name ``ssm_step`` and in no class the
    readers count steps by; the stack of states goes from the program's
    donated pool to its result through the kernels alone: no other
    instruction makes an array of the stack's, a layer's or the flat
    stack's shape (plain XLA made three a layer: the update's
    ``select_dynamic-update-slice`` fusion over the stack and the
    read-out's reduction, 4.8 GB a step)."""
    import numpy as np
    from ray_tpu.llm import kvcache
    cfg = _ssm_config()
    params = _hybrid_params(chip, cfg)
    slots, width = 64, 2048 // 16
    pool = _shapes_of(chip, jax.eval_shape(lambda: kvcache.init_pool(
        cfg, 65 * width + 1, 16, _BF, state_slots=slots)))
    assert pool["ssm"].shape == _STATES and pool["ssm"].dtype == jnp.float32

    def shape(s, d):
        return jax.ShapeDtypeStruct(s, d, sharding=chip)
    ids = shape((slots,), _I32)
    compiled = kvcache.decode_steps_program(pool, impl="paged_flash").lower(
        params, pool, {"global": shape((slots, width), _I32)}, ids, ids,
        shape((slots,), jnp.float32), shape((2,), jnp.uint32), cfg, 8, None,
        None).compile()
    ops = _kernel_ops(compiled)
    names = {}
    for op in ops:
        name = re.sub(r"[._]*\d*$", "", op["name"])
        names[name] = names.get(name, 0) + 1
    # one scanned period: three state layers, three expert layers (two
    # products each), one attention layer
    assert names == {"ssm_step": 3, "moe_gmm_decode": 6, "kv_write": 1,
                     "paged_decode": 1}, names
    for op in ops:
        if "ssm_step" in op["name"]:
            _assert_state_step_signature(op)
    assert sum(op["class"] == "paged_decode" for op in ops) == 1
    _assert_decode_signatures(ops)
    held = [(op["opcode"], op["name"])
            for op, _ in _with_result_of(compiled, _STATE_SHAPES)]
    assert held and {code for code, _ in held} <= _PASS_ON, held
    mem = compiled.memory_analysis()
    pool_bytes = sum(a.dtype.itemsize * int(np.prod(a.shape))
                     for a in pool.values())
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 4 * int(np.prod(_STATES[1:]))


def _shortconv_config(**kw):
    return _cell_config("lfm2-24b-a2b-serve-pp5.json", "lfm2_moe",
                        gmm_impl="pallas", **kw)


# the mixed cell's pool: 2 attention layers' K/V, two heads of 64 a row of
# 128 lanes, blocks of 32 positions; 7 conv layers' tails, flat, a slot
_LFM2_SLOTS, _LFM2_WIDTH, _LFM2_BLOCKS = 128, 17408 // 32, 10001


def _shortconv_pool(chip, cfg):
    from ray_tpu.llm import kvcache
    return _shapes_of(chip, jax.eval_shape(lambda: kvcache.init_pool(
        cfg, _LFM2_BLOCKS, 32, _BF, state_slots=_LFM2_SLOTS)))


def test_the_shortconv_cells_decode_program_compiles_for_v5e(chip):
    """paged_decode_steps (n = 8) at the mixed cell's published widths and
    pool geometry on a described v5e: 128 slots, K/V heads of 64 packed two
    a pool row (the walk at 4 rows of 128 lanes and 8 queries a row), a
    table of 544 blocks of 32, the conv tails (7, 128, 4096) beside the K/V
    pool. The scanned period (attention, conv, conv, conv, each with 64
    experts) holds the walk and the writer once and the grouped matmuls
    three times a layer; the dense lead runs unrolled before it with no
    kernel. The whole pool, tails included, goes from the donated argument
    to the result in place."""
    import numpy as np
    from ray_tpu.llm import kvcache
    cfg = _shortconv_config()
    params = _hybrid_params(chip, cfg)
    assert "lm_head" not in params          # tied: the embedding is the head
    pool = _shortconv_pool(chip, cfg)
    assert pool["k"].shape == (2, _LFM2_BLOCKS, 4, 32, HD)
    assert pool["conv"].shape == (7, _LFM2_SLOTS, 4096) and "ssm" not in pool

    def shape(s, d):
        return jax.ShapeDtypeStruct(s, d, sharding=chip)
    ids = shape((_LFM2_SLOTS,), _I32)
    compiled = kvcache.decode_steps_program(pool, impl="paged_flash").lower(
        params, pool, {"global": shape((_LFM2_SLOTS, _LFM2_WIDTH), _I32)},
        ids, ids, shape((_LFM2_SLOTS,), jnp.float32),
        shape((2,), jnp.uint32), cfg, 8, None, None).compile()
    ops = _kernel_ops(compiled)
    names = {}
    for op in ops:
        name = re.sub(r"[._]*\d*$", "", op["name"])
        names[name] = names.get(name, 0) + 1
    assert names == {"moe_gmm_decode": 12, "kv_write": 1,
                     "paged_decode": 1}, names
    _assert_decode_signatures(ops)
    mem = compiled.memory_analysis()
    pool_bytes = sum(a.dtype.itemsize * int(np.prod(a.shape))
                     for a in pool.values())
    assert mem.alias_size_in_bytes >= pool_bytes
    # no copy of the experts (9.7 GB), of a layer's (1.2 GB) or of the pool
    assert mem.temp_size_in_bytes < 400 * 2 ** 20, mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 13e9


def test_the_shortconv_cells_last_chunk_compiles_for_v5e(chip):
    """The chunked prefill's program at the longest prompt's last chunk
    (4,096 tokens at offset 12,288 against an accumulator of 21,504 rows):
    flash at heads of 64 over the rows unpacked from the accumulator, the
    conv from a tail to a tail, the grouped matmuls over 256 rows an
    expert, within the memory that is left beside the weights."""
    from ray_tpu.llm import model as lm
    cfg = _shortconv_config()
    params = _hybrid_params(chip, cfg)

    def shape(s, d):
        return jax.ShapeDtypeStruct(s, d, sharding=chip)
    rows = (17408 // 4096 + 2) * 4096       # engine._acc_len()
    acc = {"k": shape((2, rows, 4, HD), _BF), "v": shape((2, rows, 4, HD), _BF),
           "conv": shape((7, 4096), _BF)}
    compiled = lm._prefill_chunk_flash.lower(
        params, shape((4096,), _I32), shape((), _I32), 12288, acc, cfg,
        "flash").compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2.5e9, mem.temp_size_in_bytes
