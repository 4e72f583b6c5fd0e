"""Paged-attention kernels: attend directly over KV block pools, for
single-token decode AND chunked prefill.

The PR-2 paged serve path is correct but pays a per-layer gather: every
decode step materializes a dense ``(n_slots, view_len, Hkv, hd)`` per-slot
K/V view from the block pools before running dense attention over it, so
decode HBM traffic and scratch scale with the worst-case ``view_len``, not
with live tokens. This kernel is the vLLM-style fix: it reads K/V **blocks
in place** and computes flash-style online-softmax attention while
streaming them through VMEM — the gathered view never exists.

Layout and grid
---------------
Pools are the serve/kv.py layout ``(n_blocks, block_len, Hkv, hd)`` with
physical block 0 reserved as the null block; the per-slot block table
``(n_slots, blocks_per_slot)`` and position vector ``(n_slots,)`` ride the
**scalar-prefetch** channel (PrefetchScalarGridSpec), so each grid step's
BlockSpec ``index_map`` resolves the slot's next physical block id before
the body runs and Pallas double-buffers the block DMA like any other
pipelined input. Grid is ``(n_slots, blocks_per_slot)`` with the block
dim innermost: one kernel instance owns one slot and revisits its output
block across the block sweep, carrying the online softmax state (m, l,
acc) in VMEM scratch — the standard flash-decoding accumulator pattern.
A block is fetched whole, every kv head at once: the TPU tiling needs
the block's last two dims, ``(Hkv, hd)``, to be the pool's own. The body
moves the head axis in front and runs every head as one batched matmul.

GQA is handled in-kernel: q arrives as ``(slot, kv_head, rows, head_dim)``
so the whole query-head group of a kv head shares that head's single K/V
block fetch (the gather path re-reads the view once per q head group via
broadcasting instead).

One kernel serves decode and chunked prefill. A decode step is a chunk of
one query per slot: its ``rows`` are the ``group`` query heads of a kv
head. A prefill chunk of ``sq`` queries has ``sq·group`` rows, query-major,
so row ``r`` sits at absolute position ``offset + r // group``.

Masking
-------
Both masks live inside the kernel, applied to scores AND to the value
rows (a masked probability is exactly 0, but ``0 · NaN = NaN`` — zeroing v
is what makes poisoned/garbage null-block rows unable to leak):

* position: key position ``j·block_len + t`` must be ≤ the query row's
  position (decode writes the current token's K/V before attending, so
  "≤" includes it); a sliding window adds ``qpos - kpos < window``;
* null block: a table entry of 0 (unallocated) masks the whole block.

The value rows of a block are zeroed where NO query row attends them
(null block, or wholly outside every query's window): columns valid for
some row carry real finite K/V, and their masked rows contribute
``0 · finite = 0``. A query row with nothing valid (an idle slot parked
on the null block, or prefill padding) outputs exact zeros instead of 0/0.

Chunked prefill (:func:`paged_prefill`)
---------------------------------------
A slot's prompt SUFFIX chunk (its K/V already scattered into fresh pages)
attends all prior pages in place — including pages attached read-only
from another request's identical prompt prefix (serve/kv.py copy-on-write
sharing) — plus causally within the chunk. This is what makes prefix
reuse free: without it, prefilling the non-shared suffix would first
materialize a contiguous per-slot view (power-of-two bucket padding over
the FULL prompt); with it, prefill reads exactly the resident pages and
writes only the suffix.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # matches models/attention._attend's mask fill


def _kernel(tbl_ref, off_ref, q_ref, k_ref, v_ref, o_ref,
            acc_ref, m_ref, l_ref, *, block_len: int, sq: int, group: int,
            scale: float, softcap: float, window: int):
    s = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    phys = tbl_ref[s, j]                       # physical block id (0 = null)
    off = off_ref[s]                           # first chunk query's position
    q = q_ref[0].astype(jnp.float32) * scale   # (Hkv, rows, hd)
    rows = q.shape[1]
    # (block_len, Hkv, hd) → (Hkv, block_len, hd): heads lead for the
    # batched matmuls
    k = jnp.swapaxes(k_ref[0].astype(jnp.float32), 0, 1)
    v = jnp.swapaxes(v_ref[0].astype(jnp.float32), 0, 1)

    kpos = j * block_len + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_len), 1)                    # (1, block_len)
    qpos = off + jax.lax.broadcasted_iota(
        jnp.int32, (rows, 1), 0) // group                # (rows, 1)
    valid = (kpos <= qpos) & (phys != 0)                 # (rows, block_len)
    if window > 0:
        valid &= (qpos - kpos) < window
    # the same test per key, as a column: some row attends key t iff
    # kpos ≤ the last query's position and the first query is within the
    # window (query positions are consecutive from off)
    kcol = j * block_len + jax.lax.broadcasted_iota(
        jnp.int32, (block_len, 1), 0)                    # (block_len, 1)
    seen = (kcol <= off + sq - 1) & (phys != 0)
    if window > 0:
        seen &= (off - kcol) < window

    sc = jnp.einsum("hrd,htd->hrt", q, k,
                    preferred_element_type=jnp.float32)  # (Hkv, rows, bl)
    if softcap > 0:
        sc = jnp.tanh(sc / softcap) * softcap
    sc = jnp.where(valid[None], sc, NEG_INF)
    v = jnp.where(seen[None], v, 0.0)

    m_prev = m_ref[...]                                  # (Hkv, rows, 1)
    m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    # exp(NEG_INF - m) underflows to 0 only once a real score raised m;
    # while everything so far is masked, sc == m_new == NEG_INF and the
    # exp is 1 — the explicit where is what keeps masked weights at 0.
    p = jnp.where(valid[None], jnp.exp(sc - m_new), 0.0)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + jnp.einsum(
        "hrt,htd->hrd", p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        l = l_ref[...]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0] = jnp.where(l > 0, acc_ref[...] / safe,
                             0.0).astype(o_ref.dtype)


def _paged_call(q, k_pool, v_pool, block_table, offsets, *, sq: int,
                scale: float, softcap: float, window: int,
                interpret: bool):
    """q: (n_slots, Hkv, rows, hd) with rows = sq·group, query-major."""
    n_slots, n_kv, rows, hd = q.shape
    _, block_len, pool_kv, pool_hd = k_pool.shape
    assert (pool_kv, pool_hd) == (n_kv, hd), (k_pool.shape, q.shape)
    bps = block_table.shape[1]
    assert block_table.shape == (n_slots, bps), block_table.shape
    assert offsets.shape == (n_slots,), offsets.shape

    kv_block = pl.BlockSpec((1, block_len, n_kv, hd),
                            # the paged read: the index_map resolves the
                            # slot's j-th LOGICAL block to its physical pool
                            # block before the body runs — this is the line
                            # that replaces kv.gather_view
                            lambda s, j, tbl, off: (tbl[s, j], 0, 0, 0))
    q_block = pl.BlockSpec((1, n_kv, rows, hd),
                           lambda s, j, tbl, off: (s, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_slots, bps),
        in_specs=[q_block, kv_block, kv_block],
        out_specs=q_block,
        scratch_shapes=[
            pltpu.VMEM((n_kv, rows, hd), jnp.float32),   # acc
            pltpu.VMEM((n_kv, rows, 1), jnp.float32),    # running max m
            pltpu.VMEM((n_kv, rows, 1), jnp.float32),    # running sum l
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, block_len=block_len, sq=sq,
                          group=rows // sq, scale=scale, softcap=softcap,
                          window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(block_table, offsets, q, k_pool, v_pool)


@functools.partial(jax.jit, static_argnames=("scale", "softcap", "window",
                                             "interpret"))
def paged_attention(q, k_pool, v_pool, block_table, positions, *,
                    scale: float, softcap: float = 0.0, window: int = 0,
                    interpret: bool):
    """Decode attention over paged pools, no gathered view.

    q: (n_slots, Hkv, group, hd) — one query token per slot, already
    rope'd/normed, grouped by kv head; k_pool/v_pool: (n_blocks,
    block_len, Hkv, hd); block_table: (n_slots, blocks_per_slot) int32;
    positions: (n_slots,) int32 per-slot query positions. Returns
    (n_slots, Hkv, group, hd) in q.dtype (idle slots = exact zeros).
    """
    return _paged_call(q, k_pool, v_pool, block_table, positions, sq=1,
                       scale=scale, softcap=softcap, window=window,
                       interpret=interpret)


@functools.partial(jax.jit, static_argnames=("scale", "softcap", "window",
                                             "interpret"))
def paged_prefill(q, k_pool, v_pool, block_table, offsets, *,
                  scale: float, softcap: float = 0.0, window: int = 0,
                  interpret: bool):
    """Chunked-prefill attention over paged pools, no gathered view.

    q: (n_slots, sq, Hkv, group, hd) — each slot's suffix chunk, already
    rope'd/normed at absolute positions offsets[s] + [0, sq), grouped by
    kv head; k_pool/v_pool: (n_blocks, block_len, Hkv, hd) with the
    chunk's OWN K/V already scattered in (the kernel attends prior pages
    AND the chunk through the same block sweep, causally); block_table:
    (n_slots, blocks_per_slot) int32; offsets: (n_slots,) int32 absolute
    position of each slot's first chunk query (the shared-prefix length).
    Returns (n_slots, sq, Hkv, group, hd) in q.dtype; padding query rows
    and idle slots come back as exact zeros.
    """
    n_slots, sq, n_kv, group, hd = q.shape
    qh = jnp.moveaxis(q, 2, 1).reshape(n_slots, n_kv, sq * group, hd)
    out = _paged_call(qh, k_pool, v_pool, block_table, offsets, sq=sq,
                      scale=scale, softcap=softcap, window=window,
                      interpret=interpret)
    return jnp.moveaxis(out.reshape(n_slots, n_kv, sq, group, hd), 1, 2)
