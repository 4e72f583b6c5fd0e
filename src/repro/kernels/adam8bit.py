"""Fused blockwise 8-bit Adam update kernel (paper §5.1 "8-bit SLTrain").

One pass over the parameter: dequantize both moments, Adam update, write
the new parameter AND requantize the moments — the f32 moments exist only
as VMEM transients, never in HBM. The XLA reference path
(``repro.optim.quant`` + ``optim.optimizers.adam8bit``) round-trips f32
moments through HBM; the fused kernel removes 8 bytes/param of HBM traffic
per step, which is the dominant memory term of the optimizer phase.

Layout: the flattened parameter is reshaped to (n_q, Q) quantization
blocks (Q = oc.q_block, default 256). Grid tiles BB quantization blocks per
kernel instance; the per-block scales ride as (n_q, 1) columns so a
block's scale broadcasts along its lanes. Scalars arrive as one (10,) f32
operand in SMEM, shared by every instance: [lr, b1, b2, omb1, omb2, bc1,
bc2, eps, wd, 0]. ``omb1``/``omb2`` are the PRECOMPUTED (1 - beta) terms
— deriving them in-kernel from the f32 betas loses ~half the bits of
(1 - b2) ≈ 1e-3 and made the kernel drift ~1e-5 relative from the
``optim/quant.py`` reference (the ISSUE-4 audit).

``n_valid`` (a separate (1,) int32 SMEM operand — parameter counts exceed
the f32 24-bit integer range at 7B scale) masks the zero-padded tail lanes of
the last quantization block: padded m/v are pinned to exactly 0 so the
requantized state is BITWISE identical to the reference (which re-pads with
zeros every step), and a padded lane can never contaminate the last real
block's scale. The audit showed the old unmasked pads were *bounded* (the
v floor keeps them ≤ half a quantization step below the block max) but not
bit-identical — v pad codes round-tripped through the half-step floor.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(s_ref, n_ref, p_ref, g_ref, mc_ref, ms_ref, vc_ref, vs_ref,
            po_ref, mco_ref, mso_ref, vco_ref, vso_ref, *, bb: int, q: int):
    lr, b1, b2, omb1, omb2, bc1, bc2, eps, wd, _ = [s_ref[i] for i in range(10)]
    # validity mask over this instance's (bb, q) flat lanes
    base = pl.program_id(0) * bb * q
    flat = base \
        + jax.lax.broadcasted_iota(jnp.int32, (bb, q), 0) * q \
        + jax.lax.broadcasted_iota(jnp.int32, (bb, q), 1)
    valid = flat < n_ref[0]
    g = jnp.where(valid, g_ref[...].astype(jnp.float32), 0.0)
    p = p_ref[...].astype(jnp.float32)
    # dequantize (symmetric signed m; shifted unsigned v). The v code is
    # floored at half a quantization step: a linear code zero-quantizes
    # small v within a block, and m/(sqrt(0)+eps) explodes the update
    # (bitsandbytes avoids this with a dynamic exponent code; the floor is
    # the linear-code equivalent — see test_adam8bit_converges_like_fp32).
    # Padded lanes are forced to exactly 0 (the floor must not resurrect
    # them — they carry no state and must quantize back to the same codes
    # the reference's zero re-pad produces).
    m = mc_ref[...].astype(jnp.float32) * ms_ref[...]
    v = jnp.maximum(vc_ref[...].astype(jnp.float32) + 128.0, 0.5) \
        * vs_ref[...]
    m = jnp.where(valid, m, 0.0)
    v = jnp.where(valid, v, 0.0)
    # Adam
    m = b1 * m + omb1 * g
    v = b2 * v + omb2 * g * g
    u = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
    u = u + wd * p
    po_ref[...] = (p - lr * u).astype(po_ref.dtype)
    # requantize
    ms = jnp.max(jnp.abs(m), axis=1, keepdims=True) / 127.0
    mco_ref[...] = jnp.round(m / jnp.maximum(ms, 1e-12)).astype(jnp.int8)
    mso_ref[...] = ms
    vs = jnp.max(v, axis=1, keepdims=True) / 255.0
    vco_ref[...] = (jnp.round(v / jnp.maximum(vs, 1e-12)) - 128.0
                    ).astype(jnp.int8)
    vso_ref[...] = vs


@functools.partial(jax.jit, static_argnames=("bb", "interpret"))
def adam8bit_update(p, g, m_codes, m_scales, v_codes, v_scales, scalars,
                    n_valid, *, bb: int = 64, interpret: bool):
    """p/g: (n_q, Q); codes: int8 (n_q, Q); scales: f32 (n_q, 1);
    scalars: f32 (10,) = [lr, b1, b2, 1-b1, 1-b2, bc1, bc2, eps, wd, 0];
    n_valid: int32 (1,) — count of real (unpadded) elements.
    Returns (new_p, new_m_codes, new_m_scales, new_v_codes, new_v_scales)."""
    n_q, q = p.shape
    assert n_q % bb == 0, (n_q, bb)
    grid = (n_q // bb,)
    blk2 = pl.BlockSpec((bb, q), lambda i: (i, 0))
    col = pl.BlockSpec((bb, 1), lambda i: (i, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_kernel, bb=bb, q=q),
        grid=grid,
        in_specs=[smem, smem, blk2, blk2, blk2, col, blk2, col],
        out_specs=[blk2, blk2, col, blk2, col],
        out_shape=[
            jax.ShapeDtypeStruct((n_q, q), p.dtype),
            jax.ShapeDtypeStruct((n_q, q), jnp.int8),
            jax.ShapeDtypeStruct((n_q, 1), jnp.float32),
            jax.ShapeDtypeStruct((n_q, q), jnp.int8),
            jax.ShapeDtypeStruct((n_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(scalars, n_valid, p, g, m_codes, m_scales, v_codes, v_scales)
