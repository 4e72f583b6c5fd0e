"""Compile every Pallas kernel of the main path for a described TPU v5e,
at the paper's LLaMA-1B widths, from this CPU process; and the SL kernels
at the chip benchmark's Qwen2.5-32B widths, where one row block holds the
training step's 2048 tokens.

Interpret-mode tests cannot see what the chip's compiler refuses: block
shapes the TPU tiling does not accept, sublane packing, fast-memory use.
Here each kernel is lowered with ``interpret=False`` against a described
(not attached) ``v5e:2x2`` topology and compiled by the TPU compiler;
nothing runs. The topology is described inside a module fixture, never at
import, because only one process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.obs
from repro.configs.llama_paper import LLAMA_1B
from repro.core import support
from repro.kernels import ops

D, FF = LLAMA_1B.d_model, LLAMA_1B.d_ff
RANK, DELTA = LLAMA_1B.param.rank, LLAMA_1B.param.delta
HEADS, HD = LLAMA_1B.n_heads, LLAMA_1B.d_model // LLAMA_1B.n_heads
BATCH, SEQ = 8, 256                 # the training step of chip_smoke.py
# the chip benchmark's cell: Qwen2.5-32B widths, seq 2048 x batch 1
Q_D, Q_FF, Q_RANK, Q_DELTA, Q_SEQ = 5120, 27648, 1280, 0.03, 2048
SLOTS, BLOCK_LEN, MAX_LEN, CHUNK = 4, 16, 128, 32
BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile can be written to the persistent cache but
    # never read back without the chip: keep it out of any cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _tiles(d_in, d_out, dtype=I32, delta=DELTA):
    nkt, nnt = -(-d_in // 128), -(-d_out // 128)
    return (nkt, nnt, support.tile_cap(d_in, d_out, delta)), dtype


def _qwen_cases():
    """The SL kernels of the benchmark cell's MLP up projection (d → d_ff):
    forward, dx on the transposed factors and swapped tile arrays (K d_ff
    → N d), and dv."""
    up = _tiles(Q_D, Q_FF, delta=Q_DELTA)
    nkt, nnt, cap = up[0]
    up_t = ((nnt, nkt, cap), I32)
    return {
        "sl_matmul.qwen2.5-32b.fwd": (
            lambda x, B, A, v, r, c: ops.sl_matmul(
                x, B, A, v, r, c, 0.5, interpret=False),
            [((1, Q_SEQ, Q_D), BF16), ((Q_D, Q_RANK), BF16),
             ((Q_RANK, Q_FF), BF16), (up[0], F32), up, up]),
        "sl_matmul.qwen2.5-32b.dx": (
            lambda dy, At, Bt, v, r, c: ops.sl_matmul(
                dy, At, Bt, v, r, c, 0.5, interpret=False),
            [((1, Q_SEQ, Q_FF), BF16), ((Q_FF, Q_RANK), BF16),
             ((Q_RANK, Q_D), BF16), (up_t[0], F32), up_t, up_t]),
        "sddmm.qwen2.5-32b.dv": (
            lambda x, dy, r, c: ops.sddmm(x, dy, r, c, interpret=False),
            [((1, Q_SEQ, Q_D), BF16), ((1, Q_SEQ, Q_FF), BF16), up, up]),
    }


def _cases():
    """name → (function with interpret=False, [(shape, dtype), ...])."""
    up = _tiles(D, FF)                 # w1/w3: d_model → d_ff
    down = _tiles(FF, D)               # w2: d_ff → d_model
    n_blocks = 1 + SLOTS * MAX_LEN // BLOCK_LEN
    pool = ((n_blocks, BLOCK_LEN, HEADS, HD), BF16)
    table = ((SLOTS, MAX_LEN // BLOCK_LEN), I32)
    slots = ((SLOTS,), I32)
    n_q = RANK * FF // 256
    return {
        "sl_matmul": (
            lambda x, B, A, v, r, c: ops.sl_matmul(
                x, B, A, v, r, c, 0.5, interpret=False),
            [((BATCH, SEQ, D), BF16), ((D, RANK), BF16),
             ((RANK, FF), BF16), (up[0], F32), up, up]),
        "sddmm": (
            lambda x, dy, r, c: ops.sddmm(x, dy, r, c, interpret=False),
            [((BATCH, SEQ, FF), BF16), ((BATCH, SEQ, D), BF16), down,
             down]),
        "sparse_matmul": (
            lambda x, B, A, v, r, c: ops.sl_decode(
                x, B, A, v, r, c, 0.5, interpret=False),
            [((SLOTS, 1, D), BF16), ((D, RANK), BF16), ((RANK, FF), BF16),
             (up[0], F32), up, up]),
        "quant_sparse_matmul": (
            lambda x, B, A, qv, r, c, s: ops.sl_quant_decode(
                x, B, A, qv, r, c, s, 0.5, interpret=False),
            [((SLOTS, 1, D), BF16), ((D, RANK), BF16), ((RANK, FF), BF16),
             (up[0], jnp.int8), (up[0], jnp.int16), (up[0], jnp.int16),
             ((up[0][1], 128), F32)]),
        "adam8bit_update": (
            lambda p, g, mc, ms, vc, vs: ops.adam8bit_update(
                p, g, mc, ms, vc, vs, lr=jnp.float32(1e-3), b1=0.9,
                b2=0.999, bc1=jnp.float32(0.1), bc2=jnp.float32(1e-3),
                eps=1e-8, wd=0.0, interpret=False),
            [((RANK, FF), BF16), ((RANK, FF), F32), ((n_q, 256), jnp.int8),
             ((n_q,), F32), ((n_q, 256), jnp.int8), ((n_q,), F32)]),
        "paged_attention": (
            lambda q, k, v, t, p: ops.paged_attention(
                q, k, v, t, p, scale=HD ** -0.5, interpret=False),
            [((SLOTS, HEADS, HD), BF16), pool, pool, table, slots]),
        "paged_prefill": (
            lambda q, k, v, t, o: ops.paged_prefill_attention(
                q, k, v, t, o, scale=HD ** -0.5, interpret=False),
            [((SLOTS, CHUNK, HEADS, HD), BF16), pool, pool, table, slots]),
        **_qwen_cases(),
    }


@pytest.mark.parametrize("name", sorted(_cases()))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, specs = _cases()[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    # a Mosaic kernel, not the interpreter's XLA loop
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 ** 30, mem
    if name in _qwen_cases():
        # the whole step's 2048 tokens in one row block: each weight tile
        # is built (or gathered) once per call
        last = [e for e in repro.obs.get_trace().events
                if e["name"] == "sl.row_blocks"][-1]["args"]
        assert (last["rows"], last["row_blocks"]) == (Q_SEQ, 1), last
