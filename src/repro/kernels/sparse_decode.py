"""Sparse-only decode matmul kernel: y = x @ S  (DESIGN §3 beyond-paper).

Decode is weight-bound: the densify path reads 2 B/cell of W per step
(d_in·d_out·2 bytes). This kernel reads only the tile-CSR support —
v (4 B) + rows/cols (8 B) per NONZERO — i.e. 12·δ bytes/cell ≈ 0.36 B/cell
at δ=0.03, a 5.5× cut of the decode HBM term for the sparse component.
Combined with the factored low-rank part ((x·B)·A, plain XLA dots reading
(d_in+d_out)·r·2 bytes), the full SLTrain decode read shrinks by the
parameter-compression ratio — the serve_step "sparse" exec mode.

Body = the scatter-as-matmul of sl_matmul without the BA term: per (k, n)
tile build S_tile = P_rᵀ·diag(v)·P_c in VMEM (two one-hot MXU matmuls) and
immediately contract with x. S never exists in HBM.

The quantized sibling (:func:`quant_sparse_matmul`, the
``exec_mode="quant"`` serve path from repro.quant) consumes the int8
tile-CSR layout instead: qv (1 B) + int16 rows/cols (4 B) per nonzero
≈ 5·δ B/cell — a further 2.4× cut of the sparse decode term. Dequant
happens in VMEM: the tile is built from raw int8 codes and its columns
are rescaled against the per-output-channel f32 scale slice for that
column tile, so a code's scale is exactly scales[global_col] without any
per-entry gather (entries in column c of a tile land ONLY in s_tile
column c — a single row-vector multiply dequantizes the whole tile).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.sl_matmul import as_tile_rows, sparse_tile, tile_block


def _kernel(x_ref, v_ref, r_ref, c_ref, o_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    bk = x_ref.shape[1]
    bn = o_ref.shape[1]
    s_tile = sparse_tile(v_ref[...], r_ref[...], c_ref[...], bk, bn)
    o_ref[...] += jax.lax.dot(x_ref[...].astype(jnp.float32), s_tile,
                              preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret"))
def sparse_matmul(x, v_t, rows_t, cols_t, *, bm: int = 16, bk: int = 128,
                  bn: int = 128, interpret: bool):
    """y = x @ S for tile-CSR S; x (M, K) pre-padded to tile multiples.
    bm defaults small — decode batches are 1–128 rows; 16 is the bf16
    sublane tile."""
    m, k = x.shape
    nkt, nnt, e = rows_t.shape
    n = nnt * bn
    assert m % bm == 0 and k % bk == 0, (m, k)
    tile = lambda i, j, kk: (kk, j, 0, 0)
    grid = (m // bm, nnt, nkt)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            tile_block(e, tile), tile_block(e, tile), tile_block(e, tile),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(x, as_tile_rows(v_t), as_tile_rows(rows_t), as_tile_rows(cols_t))
    return out.astype(x.dtype)


def _qkernel(x_ref, qv_ref, r_ref, c_ref, s_ref, o_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    bk = x_ref.shape[1]
    bn = o_ref.shape[1]
    # tile of raw int8 codes (padding slots carry qv == 0), then one
    # row-vector multiply dequantizes every column against its channel
    # scale — column c of s_tile holds exactly the entries with col == c
    s_tile = sparse_tile(qv_ref[...].astype(jnp.float32),
                         r_ref[...].astype(jnp.int32),
                         c_ref[...].astype(jnp.int32), bk, bn)
    s_tile = s_tile * s_ref[...]
    o_ref[...] += jax.lax.dot(x_ref[...].astype(jnp.float32), s_tile,
                              preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret"))
def quant_sparse_matmul(x, qv_t, rows_q, cols_q, qscale, *, bm: int = 16,
                        bk: int = 128, bn: int = 128, interpret: bool):
    """y = x @ dequant(S) for the int8 tile-CSR layout (repro.quant).

    qv_t int8 (nkt, nnt, E) codes baked in tile order; rows_q/cols_q
    int16 tile-local indices (< 128, the byte win over the bf16 path's
    int32 consts); qscale f32 (nnt, TILE) per-output-channel scales
    blocked by column tile. x (M, K) pre-padded to tile multiples;
    accumulation is f32 (one final rounding, like the bf16 kernel)."""
    m, k = x.shape
    nkt, nnt, e = rows_q.shape
    n = nnt * bn
    assert m % bm == 0 and k % bk == 0, (m, k)
    assert qscale.shape == (nnt, bn), (qscale.shape, nnt, bn)
    tile = lambda i, j, kk: (kk, j, 0, 0)
    grid = (m // bm, nnt, nkt)
    out = pl.pallas_call(
        _qkernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            tile_block(e, tile), tile_block(e, tile), tile_block(e, tile),
            pl.BlockSpec((None, 1, bn), lambda i, j, kk: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(x, as_tile_rows(qv_t), as_tile_rows(rows_q), as_tile_rows(cols_q),
      qscale.reshape(nnt, 1, bn))
    return out.astype(x.dtype)
