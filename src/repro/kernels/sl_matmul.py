"""Fused SLTrain matmul kernel: y = x @ (scale·B·A ⊕_I V)  (DESIGN §3.1).

TPU adaptation of the paper's scatter-add forward. The GPU reference
materializes W = BA ⊕ V in HBM and then runs a dense GEMM — two extra HBM
round-trips of d_in·d_out·2 bytes. Here each (k, n) grid cell *densifies
its own 128×128 tile in VMEM* and immediately feeds it to the MXU; the
dense W never exists in HBM.

Scatter-as-matmul (DESIGN §3.2): TPUs have no fast unstructured VMEM
scatter, so the per-tile scatter is expressed as

    W_tile += P_r^T · diag(v) · P_c,   P_r = onehot(rows, bk),
                                       P_c = onehot(cols, bn)

two small MXU matmuls — the sparse work also runs on the systolic array.

Support layout: ``support.tile_layout`` buckets the fixed support by
128×128 tile at init, padded to the per-tile max (uniform random support ⇒
tight concentration). Padding slots carry v = 0 so they contribute nothing.

Grid: (M/bm, N/bn, K/bk), k innermost; the f32 output block is revisited
across k and used as the accumulator (standard Pallas matmul pattern), and
A's (r, bn) block is constant across k, so it is fetched once per column
tile. Building a tile costs far more MXU work (B_k·A_j over r, the f32
one-hot product) than spending it on 128 rows, so the row block ``bm``
covers every row of the call, up to a cap (:func:`row_blocks`): each tile
is built once per row block, ⌈M/cap⌉ times a call, and at the training
step's M every tile is built once.

Row rule (:func:`row_blocks`, shared with ``sddmm``): the cap is the most
rows, a multiple of 128, whose blocks fit :data:`VMEM_BUDGET` by the
kernel's own estimate (:func:`vmem_bytes`). M, rounded up to 128, is split
into the fewest row blocks under the cap, of equal size, padded up to a
multiple of 128; M ≤ 128 keeps one 128-row block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


#: VMEM the row rule fits a call's blocks and temporaries into: half of the
#: v5e's 16 MiB default scoped VMEM, the rest left to the compiler
VMEM_BUDGET = 8 * 2**20
#: rows are counted and padded in multiples of this
ROW_ALIGN = 128


def _round_up(a: int, b: int) -> int:
    return -(-a // b) * b


def row_blocks(m: int, fixed: int, per_row: int,
               cap: int | None = None) -> tuple[int, int]:
    """(rows per block, row blocks) for a call of ``m`` token rows whose
    blocks take ``fixed + rows·per_row`` bytes of VMEM. The cap is the
    most rows that fit :data:`VMEM_BUDGET`, or ``cap`` where given, rounded
    down to a multiple of 128 and at least 128."""
    if cap is None:
        cap = (VMEM_BUDGET - fixed) // per_row
    cap = max(ROW_ALIGN, cap // ROW_ALIGN * ROW_ALIGN)
    m_al = _round_up(max(m, 1), ROW_ALIGN)
    n_blocks = -(-m_al // cap)
    return _round_up(-(-m_al // n_blocks), ROW_ALIGN), n_blocks


def vmem_bytes(bk: int, bn: int, r: int, e: int,
               itemsize: int) -> tuple[int, int]:
    """(fixed, per row) VMEM bytes of one call's blocks, double-buffered as
    Pallas pipelines them, and of its temporaries: B's (bk, r) and A's
    (r, bn) blocks, the three (1, E) support rows (padded to 8 sublanes),
    the two (·, E) f32 one-hots and the f32 tile with its cast; per row,
    x's block, the f32 output block and the x·w product."""
    r_al = _round_up(r, 128)
    fixed = (2 * (bk + bn) * r_al * itemsize + 3 * 2 * 8 * e * 4
             + (bk + bn) * e * 4 + bk * bn * (4 + itemsize))
    return fixed, 2 * bk * itemsize + 3 * bn * 4


def sparse_tile(v, rows, cols, bk: int, bn: int):
    """(bk, bn) f32 tile P_rᵀ·diag(v)·P_c from one tile's (1, E) support
    row vectors (local rows, local cols, values). The one-hots are built
    transposed, (bk, E) and (bn, E), so the lane-major support vectors
    broadcast along sublanes and the product is one A·Bᵀ MXU matmul."""
    e = rows.shape[-1]
    prt = (jax.lax.broadcasted_iota(jnp.int32, (bk, e), 0) == rows
           ).astype(jnp.float32) * v.astype(jnp.float32)
    pct = (jax.lax.broadcasted_iota(jnp.int32, (bn, e), 0) == cols
           ).astype(jnp.float32)
    return jax.lax.dot_general(prt, pct, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def tile_block(e: int, index_map):
    """BlockSpec of one tile's support vector in the (nkt, nnt, 1, E)
    layout (see :func:`as_tile_rows`): the last two block dims equal the
    array's, which the TPU tiling requires; the kernel sees a (1, E) ref."""
    return pl.BlockSpec((None, None, 1, e), index_map)


def as_tile_rows(a):
    """(nkt, nnt, E) tile-CSR array → (nkt, nnt, 1, E), a free reshape."""
    return a.reshape(*a.shape[:2], 1, a.shape[2])


def _kernel(x_ref, b_ref, a_ref, v_ref, r_ref, c_ref, o_ref, *,
            scale: float):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    bk = b_ref.shape[0]
    bn = a_ref.shape[1]
    # low-rank tile: (bk, r) @ (r, bn) on the MXU, f32 accumulation
    w = jax.lax.dot(b_ref[...], a_ref[...],
                    preferred_element_type=jnp.float32) * scale
    # sparse tile via one-hot matmuls (scatter-as-matmul)
    w = w + sparse_tile(v_ref[...], r_ref[...], c_ref[...], bk, bn)
    # consume the tile immediately: (bm, bk) @ (bk, bn)
    o_ref[...] += jax.lax.dot(x_ref[...], w.astype(x_ref.dtype),
                              preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("scale", "bm", "bk", "bn",
                                             "interpret"))
def sl_matmul(x, B, A, v_t, rows_t, cols_t, *, scale: float,
              bm: int = 128, bk: int = 128, bn: int = 128,
              interpret: bool):
    """x (M,K) @ (scale·B(K,r)·A(r,N) ⊕ V) → (M,N) in x.dtype.

    v_t/rows_t/cols_t: (K/bk, N/bn, E) tile-CSR arrays from
    ``ops.prepare_tiles`` (E = padded per-tile capacity, pad v = 0).
    Shapes must be pre-padded to tile multiples and M to the row block
    ``bm`` (ops.py chooses ``bm`` by :func:`row_blocks` and pads).
    """
    m, k = x.shape
    n = A.shape[1]
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (m, k, n)
    assert rows_t.shape[:2] == (k // bk, n // bn), rows_t.shape
    e = v_t.shape[-1]
    tile = lambda i, j, kk: (kk, j, 0, 0)
    grid = (m // bm, n // bn, k // bk)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, B.shape[1]), lambda i, j, kk: (kk, 0)),
            pl.BlockSpec((A.shape[0], bn), lambda i, j, kk: (0, j)),
            tile_block(e, tile), tile_block(e, tile), tile_block(e, tile),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(x, B, A, as_tile_rows(v_t), as_tile_rows(rows_t),
      as_tile_rows(cols_t))
    return out.astype(x.dtype)
