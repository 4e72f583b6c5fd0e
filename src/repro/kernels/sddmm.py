"""SDDMM sparse-gradient kernel: dV = (xᵀ·dy)_I  (DESIGN §3.3).

The paper's backward (eq. 2) forms the full-rank transient G = xᵀ∇z in HBM
and gathers the support entries. On TPU we fuse: each (k, n) tile of G is
computed in VMEM (accumulating over the token dimension m) and only the
*gathered* values leave the kernel — the d_in·d_out transient never touches
HBM.

Gather-as-matmul: dv[e] = G[row_e, col_e] = (P_r · G ⊙ P_c)·1, i.e. one
(E, bk)@(bk, bn) MXU matmul + a masked row-sum, where P_r/P_c are the
one-hot support matrices of the tile. Grid: (K/bk, N/bn, M/bm), m
innermost, accumulating into the tile's (1, E) output block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.sl_matmul import as_tile_rows, tile_block


def _kernel(x_ref, dy_ref, r_ref, c_ref, o_ref):
    m = pl.program_id(2)

    @pl.when(m == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    bk = x_ref.shape[1]
    bn = dy_ref.shape[1]
    e = r_ref.shape[-1]
    # transposed tile of G = xᵀ·dy: (bn, bk), f32 on the MXU
    g_t = jax.lax.dot_general(dy_ref[...], x_ref[...],
                              (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    # one-hots built transposed, (bk, E) and (bn, E), from the lane-major
    # (1, E) support vectors: column e of Gᵀ·P_rᵀ is row rows[e] of G, and
    # the P_cᵀ mask keeps its cols[e] entry
    prt = (jax.lax.broadcasted_iota(jnp.int32, (bk, e), 0) == r_ref[...]
           ).astype(jnp.float32)
    pct = (jax.lax.broadcasted_iota(jnp.int32, (bn, e), 0) == c_ref[...]
           ).astype(jnp.float32)
    rows_of_g = jax.lax.dot(g_t, prt,
                            preferred_element_type=jnp.float32)  # (bn, E)
    o_ref[...] += jnp.sum(rows_of_g * pct, axis=0, keepdims=True)  # (1, E)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret"))
def sddmm(x, dy, rows_t, cols_t, *, bm: int = 128, bk: int = 128,
          bn: int = 128, interpret: bool):
    """dv tiles (K/bk, N/bn, E) f32 for the support laid out by
    ``ops.prepare_tiles``; x (M, K), dy (M, N) pre-padded to tile multiples."""
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
        interpret=interpret,
    )(x, dy, as_tile_rows(rows_t), as_tile_rows(cols_t))
    return out.reshape(nkt, nnt, e)
