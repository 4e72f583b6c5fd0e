"""SDDMM sparse-gradient kernel: dV = (xᵀ·dy)_I  (DESIGN §3.3).

The paper's backward (eq. 2) forms the full-rank transient G = xᵀ∇z in HBM
and gathers the support entries. On TPU we fuse: each (k, n) tile of G is
computed in VMEM (accumulating over the token dimension m) and only the
*gathered* values leave the kernel — the d_in·d_out transient never touches
HBM.

Gather-as-matmul: dv[e] = G[row_e, col_e] = (P_r · G ⊙ P_c)·1, i.e. one
(E, bk)@(bk, bn) MXU matmul + a masked row-sum, where P_r/P_c are the
one-hot support matrices of the tile.

Grid: (K/bk, N/bn, M/bm), m innermost. The tile's G accumulates in an f32
VMEM scratch across the row blocks; the f32 one-hot gather, the mask and
the row sum run once per tile, at the last row block, into the tile's
(1, E) output block. The row block ``bm`` follows ``sl_matmul``'s row rule
(``sl_matmul.row_blocks`` with this kernel's :func:`vmem_bytes`): at the
training step's M there is one row block, so x's block is constant across
the n axis and read once per k tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sl_matmul import as_tile_rows, tile_block


def vmem_bytes(bk: int, bn: int, e: int, itemsize: int) -> tuple[int, int]:
    """(fixed, per row) VMEM bytes of one call: the f32 G scratch and its
    block product, the two (·, E) f32 one-hots, the (bn, E) gathered rows
    and their masked copy, the (1, E) output and support rows (padded to 8
    sublanes, double-buffered); per row, x's and dy's double-buffered
    blocks."""
    fixed = (2 * bn * bk * 4 + (bk + bn) * e * 4 + 2 * bn * e * 4
             + 3 * 2 * 8 * e * 4)
    return fixed, 2 * (bk + bn) * itemsize


def _kernel(x_ref, dy_ref, r_ref, c_ref, o_ref, g_ref):
    m = pl.program_id(2)

    @pl.when(m == 0)
    def _init():
        g_ref[...] = jnp.zeros_like(g_ref)

    # transposed tile of G = xᵀ·dy: (bn, bk), f32 on the MXU
    g_ref[...] += jax.lax.dot_general(dy_ref[...], x_ref[...],
                                      (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)

    @pl.when(m == pl.num_programs(2) - 1)
    def _gather():
        bn, bk = g_ref.shape
        e = r_ref.shape[-1]
        # one-hots built transposed, (bk, E) and (bn, E), from the
        # lane-major (1, E) support vectors: column e of Gᵀ·P_rᵀ is row
        # rows[e] of G, and the P_cᵀ mask keeps its cols[e] entry
        prt = (jax.lax.broadcasted_iota(jnp.int32, (bk, e), 0) == r_ref[...]
               ).astype(jnp.float32)
        pct = (jax.lax.broadcasted_iota(jnp.int32, (bn, e), 0) == c_ref[...]
               ).astype(jnp.float32)
        rows_of_g = jax.lax.dot(g_ref[...], prt,
                                preferred_element_type=jnp.float32)  # (bn, E)
        o_ref[...] = jnp.sum(rows_of_g * pct, axis=0, keepdims=True)  # (1, E)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret"))
def sddmm(x, dy, rows_t, cols_t, *, bm: int = 128, bk: int = 128,
          bn: int = 128, interpret: bool):
    """dv tiles (K/bk, N/bn, E) f32 for the support laid out by
    ``ops.prepare_tiles``; x (M, K), dy (M, N) pre-padded to tile multiples
    and M to the row block ``bm`` (ops.py chooses it and pads)."""
    m, k = x.shape
    n = dy.shape[1]
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (m, k, n)
    nkt, nnt, e = rows_t.shape
    assert (nkt, nnt) == (k // bk, n // bn), rows_t.shape
    tile = lambda kk, j, i: (kk, j, 0, 0)
    grid = (k // bk, n // bn, m // bm)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda kk, j, i: (i, kk)),
            pl.BlockSpec((bm, bn), lambda kk, j, i: (i, j)),
            tile_block(e, tile), tile_block(e, tile),
        ],
        out_specs=tile_block(e, tile),
        out_shape=jax.ShapeDtypeStruct((nkt, nnt, 1, e), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bn, bk), jnp.float32)],
        interpret=interpret,
    )(x, dy, as_tile_rows(rows_t), as_tile_rows(cols_t))
    return out.reshape(nkt, nnt, e)
