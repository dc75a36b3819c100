"""Compile the custom kernels for a described TPU v5e, at the bench model's widths.

The TPU's compiler is installed without the chip: ``get_topology_desc`` describes
a ``v5e:2x2`` and a jitted function lowered for one of its devices compiles as it
would on the machine. Nothing runs, so this says what the compiler accepts and
nothing about results or speed. It shows what interpret mode cannot: tiling,
alignment and layout rules of the real lowering.

This is the only file that describes the chip. The topology is described inside
a fixture (only one process at a time may load the TPU's library, and xdist
workers import every test file), the persistent compilation cache is off around
the compiles (such an entry cannot be read back without a chip), and no child
process compiles.

Six cases must compile with a ``tpu_custom_call`` in the program. Three are
refused by the compiler today and are strict ``xfail``s carrying its words: the
PR that repairs one of those kernels has to flip its case. One more case
compiles a whole program, the paged engine's decode window at the geometry of
the benchmark's Qwen3 cells, and reads what the compiler made of its view
assembly.
"""

import json
import math
import os
import re
from pathlib import Path
from dataclasses import dataclass
from typing import Callable

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

# The bench model (bench.py "large"): 18 heads of 128, hidden 2304, MLP 9216;
# the paged engine as chip_smoke.py sizes it: block 16, 8 slots, 128 blocks a
# slot.
HEADS, HEAD_DIM, HIDDEN, MLP = 18, 128, 2304, 9216
SLOTS, BLOCK, BLOCKS_PER_SLOT = 8, 16, 128
POOL_BLOCKS = SLOTS * BLOCKS_PER_SLOT + 1


class KernelRefused(Exception):
    """The chip's compiler refused the kernel with the words the case expects."""


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as patch:
        # Or the compiler writes its logs under /tmp, at every compile.
        patch.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            described = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield described


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _bf16(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.bfloat16)


def _flash():
    from accelerate_tpu.ops.attention import flash_attention

    def fwd_bwd(q, k, v):
        loss = lambda q, k, v: flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    qkv = _bf16(12, 1024, HEADS, HEAD_DIM)
    return fwd_bwd, (qkv, qkv, qkv)


def _splash_window():
    from accelerate_tpu.ops.attention import splash_attention

    qkv = _bf16(2, 1024, HEADS, HEAD_DIM)
    return (lambda q, k, v: splash_attention(q, k, v, causal=True, window=256)), (qkv, qkv, qkv)


def _int8_matmul(rows):
    def build():
        from accelerate_tpu.ops.pallas.int8_mm import int8_matmul_kernel

        return int8_matmul_kernel, (_bf16(rows, HIDDEN), _bf16(HIDDEN, MLP))

    return build


def _fused_update_adamw():
    import optax

    from accelerate_tpu.ops.pallas.fused_update import fused_update_apply, plan_fused_update

    tx = optax.adamw(3e-4, weight_decay=0.01)
    plan = plan_fused_update(tx)
    params = {"w": jax.ShapeDtypeStruct((HIDDEN, MLP), jnp.float32)}

    def update(params, opt_state, grads):
        return fused_update_apply(params, opt_state, grads, plan=plan,
                                  clip_factor=jnp.float32(1.0))

    return update, (params, jax.eval_shape(tx.init, params), params)


def _paged_gather(kv_heads, quant=False):
    def build():
        from accelerate_tpu.ops.pallas.paged_decode import gather_block_view_kernel

        pool = jax.ShapeDtypeStruct(
            (POOL_BLOCKS, BLOCK, kv_heads, HEAD_DIM), jnp.int8 if quant else jnp.bfloat16
        )
        tables = jax.ShapeDtypeStruct((SLOTS, BLOCKS_PER_SLOT), jnp.int32)
        if not quant:
            return gather_block_view_kernel, (pool, tables)
        scales = jax.ShapeDtypeStruct((POOL_BLOCKS, BLOCK), jnp.float32)
        return (lambda p, t, s: gather_block_view_kernel(p, t, scales=s)), (pool, tables, scales)

    return build


def _paged_decode():
    from accelerate_tpu.ops.pallas.paged_decode import paged_attention_kernel

    pool = _bf16(POOL_BLOCKS, BLOCK, HEADS, HEAD_DIM)
    args = (
        _bf16(SLOTS, 1, HEADS, HEAD_DIM), pool, pool,
        jax.ShapeDtypeStruct((SLOTS, BLOCKS_PER_SLOT), jnp.int32),
        jax.ShapeDtypeStruct((SLOTS, 1), jnp.int32),
    )
    return (lambda q, k, v, t, pos: paged_attention_kernel(q, k, v, t, q_positions=pos)), args


@dataclass
class Case:
    name: str
    build: Callable  # () -> (function, abstract arguments); runs inside the test
    refusal: str | None = None  # the compiler's words, where it refuses today


CASES = [
    Case("flash_fwd_bwd_b12_s1024_h18_d128", _flash),
    Case("splash_window256_b2_s1024_h18_d128", _splash_window),
    Case("int8_matmul_3072x2304x9216", _int8_matmul(3072)),
    Case("int8_matmul_8x2304x9216", _int8_matmul(8)),
    Case("fused_update_adamw_2304x9216_f32", _fused_update_adamw),
    Case("paged_gather_bf16_kv8", _paged_gather(8)),
    Case("paged_decode_h18", _paged_decode,
         refusal="last two dimensions of your block shape are divisible by 8 and 128"),
    Case("paged_gather_bf16_kv18", _paged_gather(18),
         refusal="Slice shape along dimension 3 must be aligned to tiling (8), but is 18"),
    Case("paged_gather_int8_scales_kv8", _paged_gather(8, quant=True),
         refusal="unsupported shape cast"),
]


def _param(case: Case):
    marks = ()
    if case.refusal:
        marks = pytest.mark.xfail(strict=True, raises=KernelRefused,
                                  reason=f"refused by the v5e compiler: {case.refusal}")
    return pytest.param(case, id=case.name, marks=marks)


@pytest.mark.parametrize("case", [_param(c) for c in CASES])
def test_kernel_compiles_for_v5e(case, one_chip, no_persistent_cache):
    fn, args = case.build()
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), args
    )
    try:
        compiled = jax.jit(fn).lower(*args).compile()
    except Exception as exc:
        if case.refusal and case.refusal in str(exc):
            raise KernelRefused(str(exc)[:400]) from exc
        raise
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel in the compiled program"


def test_decode_window_assembles_its_view_without_a_select_for_v5e(one_chip, no_persistent_cache):
    """The decode window of an engine at the Qwen3 cells' geometry (12 slots,
    98 table entries of 16 tokens, 8 KV heads of 128, a pool of 961 blocks;
    the published widths and depth, a small vocabulary), with abstract
    arguments. ``gather_block_view`` clamps its ids to the pool and gathers a
    layer's block a slice (an entry over 28 layers is 896 KiB, past what the
    compiler gathers natively), so the optimised program holds no ``select``
    the size of a view (a gather that fills out-of-range reads pays one a
    view, and a mask beside it), no serial copy loop into a buffer the size
    of a view, and what it needs beyond its arguments and its results stays
    under 1.75 times the K and V views: it reads 1.50 (two views and the
    gather's result for one of them, which a copy brings into the readers'
    layout) where the ``mode="fill"`` gather of whole entries read 2.00. (The
    results count only where the engine drops donation, as it does on a CPU
    backend with a persistent compilation cache set, which another test of
    the worker's process may have done.)"""
    from accelerate_tpu.models import Llama, LlamaConfig
    from accelerate_tpu.ops import paged_attention
    from accelerate_tpu.serving import ContinuousBatcher

    cell = json.loads((Path(__file__).parents[1] / "chipbench/configs/qwen3-1.7b.json").read_text())
    fields = {k: cell[k] for k in ("hidden_size", "intermediate_size", "num_hidden_layers",
                                   "num_attention_heads", "num_key_value_heads", "head_dim",
                                   "rope_theta", "rms_norm_eps", "tie_word_embeddings")}
    model = Llama(LlamaConfig(vocab_size=1024, **fields, **cell["model_overrides"]))
    params = jax.eval_shape(lambda: jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16), model.init(jax.random.key(0))))
    engine = ContinuousBatcher(model, params=params, **cell["engine"])
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), engine._decode_args())
    compiled = engine._decode().lower(*args).compile()

    layers, _, block, kv_heads, head_dim = engine._pool["k"].shape
    assert (engine.B, engine.max_blocks_per_slot, block, kv_heads, head_dim) == (12, 98, 16, 8, 128)
    assert layers * block * kv_heads * head_dim * 2 > paged_attention._NATIVE_SLICE_BYTES
    view_elements = layers * engine.B * engine.max_blocks_per_slot * block * kv_heads * head_dim

    program = compiled.as_text().splitlines()

    def view_sized(opcode):
        return [line.strip()[:160] for line in program
                for found in [re.search(rf"= \w+\[([\d,]+)\]\S* {opcode}\(", line)] if found
                and math.prod(int(n) for n in found.group(1).split(",")) == view_elements]

    assert not view_sized("select"), view_sized("select")
    assert not view_sized("dynamic-update-slice"), view_sized("dynamic-update-slice")
    memory = compiled.memory_analysis()
    views = 2 * view_elements * 2  # K and V, bf16
    temporaries = (memory.peak_memory_in_bytes - memory.argument_size_in_bytes
                   - (memory.output_size_in_bytes - memory.alias_size_in_bytes))
    assert temporaries < 1.75 * views, (temporaries / views, memory)
