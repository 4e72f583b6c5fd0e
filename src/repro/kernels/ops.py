"""jit'd wrappers around the Pallas kernels: shape padding, tile-CSR
support preparation, and the custom-VJP SLTrain linear that fuses
``sl_matmul`` forward with the ``sddmm`` backward.

Every wrapper decides the execution mode from the platform once
(:func:`interpret_mode`): the Pallas interpreter on the CPU, where the
tests run, and compiled Mosaic kernels on a TPU. Any other backend raises.
An explicit ``interpret`` argument exists only so a test can compile the
real kernels for a described TPU from a CPU process.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import support as support_lib
from repro.kernels import adam8bit as adam8bit_kernel
from repro.kernels import sddmm as sddmm_kernel
from repro.kernels import sl_matmul as sl_kernel
from repro.obs import trace as obs_trace


def interpret_mode(interpret: bool | None = None) -> bool:
    """``interpret`` if given, else the platform's mode: True on the CPU,
    False on a TPU. No other backend runs these kernels."""
    if interpret is not None:
        return interpret
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(f"Pallas kernels run on 'cpu' (interpreted) or "
                       f"'tpu' (compiled), not on {platform!r}")


# ---------------------------------------------------------------------------
# Tile-CSR support preparation (init-time, host numpy)
# ---------------------------------------------------------------------------

def _tile_index_arrays(rows: np.ndarray, cols: np.ndarray, d_in: int,
                       d_out: int, tile_r: int, tile_c: int,
                       pad: int | None):
    """Shared tile-CSR layout body: pad dims to tile multiples, bucket the
    support, and shape the index arrays. Returns numpy
    (rows_t, cols_t, perm), each (K/tile_r, N/tile_c, E) int32 — the ONE
    place the tile geometry is computed, so value-baking (prepare_tiles)
    and fused index consts (prepare_tile_consts) can never desync."""
    kp = ((d_in + tile_r - 1) // tile_r) * tile_r
    np_ = ((d_out + tile_c - 1) // tile_c) * tile_c
    perm, local, counts, pad = support_lib.tile_layout(
        rows, cols, kp, np_, tile_r, tile_c, pad=pad)
    nkt, nnt = kp // tile_r, np_ // tile_c
    rt = local[:, 0].reshape(nkt, nnt, pad).astype(np.int32)
    ct = local[:, 1].reshape(nkt, nnt, pad).astype(np.int32)
    return rt, ct, perm.reshape(nkt, nnt, pad)


def prepare_tiles(rows: np.ndarray, cols: np.ndarray, v: np.ndarray,
                  d_in: int, d_out: int, tile_r: int = support_lib.TILE,
                  tile_c: int = support_lib.TILE, pad: int | None = None,
                  ) -> Tuple[jnp.ndarray, jnp.ndarray,
                             jnp.ndarray, jnp.ndarray]:
    """COO support + values → 4-tuple (v_t, rows_t, cols_t, perm), each of
    shape (K/tile_r, N/tile_c, E): the layout both kernels consume plus the
    permutation back into COO order (perm == -1 on padding slots, which
    carry v = 0 at local (0, 0)). Dims are padded up to tile multiples.
    ``pad`` forces a deterministic per-tile capacity E (see
    ``support.tile_cap``); by default E is the realized per-tile max."""
    rt, ct, perm = _tile_index_arrays(rows, cols, d_in, d_out, tile_r,
                                      tile_c, pad)
    v_flat = np.asarray(v, dtype=np.float32).reshape(-1)
    vt = np.where(perm >= 0, v_flat[np.maximum(perm, 0)], 0.0
                  ).astype(np.float32)
    return (jnp.asarray(vt), jnp.asarray(rt), jnp.asarray(ct),
            jnp.asarray(perm))


def prepare_tile_consts(rows: np.ndarray, cols: np.ndarray, d_in: int,
                        d_out: int, *, pad: int,
                        tile_r: int = support_lib.TILE,
                        tile_c: int = support_lib.TILE) -> dict:
    """Tile-CSR *index* consts for ``exec_mode="fused"`` training:
    {rows_t, cols_t, perm}, each int32 (K/tile_r, N/tile_c, pad).

    Unlike :func:`prepare_tiles` this bakes NO values: the trainable ``v``
    stays flat in the param tree (optimizer state / checkpoints / the
    sparse decode path stay layout-independent) and is gathered into tile
    order through ``perm`` inside the jit'd forward (``sl_linear``). The
    capacity ``pad`` must be the deterministic ``support.tile_cap`` bound
    so abstract dry-run shapes match concrete init and per-layer consts
    stack; raises ``ValueError`` when the sampled support exceeds it
    (callers re-sample on host). The host build and the transfer are the
    process recorder's ``sl.tile_tables`` span."""
    with obs_trace.get_trace().span("sl.tile_tables", cat="init",
                                    d_in=d_in, d_out=d_out):
        rt, ct, perm = _tile_index_arrays(rows, cols, d_in, d_out, tile_r,
                                          tile_c, pad)
        return {"rows_t": jnp.asarray(rt), "cols_t": jnp.asarray(ct),
                "perm": jnp.asarray(perm)}


def _pad2(x, mult_r, mult_c):
    r = (-x.shape[0]) % mult_r
    c = (-x.shape[1]) % mult_c
    if r or c:
        x = jnp.pad(x, ((0, r), (0, c)))
    return x


# ---------------------------------------------------------------------------
# Forward / backward wrappers
# ---------------------------------------------------------------------------

def _row_block(kernel: str, m: int, tiles, vmem, row_cap):
    """The kernel's row block for a call of ``m`` rows (the row rule,
    ``sl_matmul.row_blocks``), recorded as the process recorder's
    ``sl.row_blocks`` instant when the call is traced."""
    rows, n_blocks = sl_kernel.row_blocks(m, *vmem, cap=row_cap)
    obs_trace.get_trace().instant(
        "sl.row_blocks", cat="kernel", kernel=kernel, m=m, rows=rows,
        row_blocks=n_blocks, tiles=tiles[0] * tiles[1])
    return rows


def sl_matmul(x, B, A, v_t, rows_t, cols_t, scale: float, *,
              row_cap: int | None = None, interpret: bool | None = None):
    """y = x @ (scale·B·A ⊕ V); arbitrary (unpadded) logical shapes.
    ``row_cap`` overrides the VMEM cap on the rows of one row block."""
    interp = interpret_mode(interpret)
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = A.shape[-1]
    m = int(np.prod(lead)) if lead else 1
    bm = _row_block("sl_matmul", m, v_t.shape, sl_kernel.vmem_bytes(
        128, 128, B.shape[-1], v_t.shape[-1], x.dtype.itemsize), row_cap)
    xf = _pad2(x.reshape(-1, k), bm, 128)
    Bp = _pad2(B, 128, 1)
    Ap = _pad2(A, 1, 128)
    y = sl_kernel.sl_matmul(xf, Bp, Ap, v_t, rows_t, cols_t, scale=scale,
                            bm=bm, interpret=interp)
    return y[:m, :n].reshape(*lead, n)


def sddmm(x, dy, rows_t, cols_t, *, row_cap: int | None = None,
          interpret: bool | None = None):
    """dv tiles for support (rows_t, cols_t); x (..., K), dy (..., N).

    Output is f32: the kernel forms each G tile with
    ``preferred_element_type=f32`` and accumulates over the token grid in
    an f32 VMEM scratch, so bf16 inputs never round dv through bf16 (same
    accumulation contract as the sparse-decode fix). Upstream often hands
    f32 cotangents against bf16 activations — align dy to x's dtype here
    (the MXU dot needs matching operand dtypes; accumulation stays f32).
    ``row_cap`` overrides the VMEM cap on the rows of one row block."""
    interp = interpret_mode(interpret)
    k = x.shape[-1]
    n = dy.shape[-1]
    xf = x.reshape(-1, k)
    bm = _row_block("sddmm", xf.shape[0], rows_t.shape,
                    sddmm_kernel.vmem_bytes(128, 128, rows_t.shape[-1],
                                            x.dtype.itemsize), row_cap)
    xf = _pad2(xf, bm, 128)
    dyf = _pad2(dy.reshape(-1, n).astype(x.dtype), bm, 128)
    return sddmm_kernel.sddmm(xf, dyf, rows_t, cols_t, bm=bm,
                              interpret=interp)


# ---------------------------------------------------------------------------
# Fused SLTrain linear: pallas forward + pallas backward, custom VJP
# ---------------------------------------------------------------------------

def _tok_dot(a, b):
    """aᵀ·b contracting the leading (token) axis in place. Spelled as one
    dot_general rather than ``a.T @ b``: XLA's CPU backend folds the
    transpose of a bf16·bf16→f32 product into a dot form it cannot run."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())))


def _fused_grads_dist(x, B, A, v_t, rows_t, cols_t, scale, dy):
    """Distributed fused backward (the shard_map sibling of
    ``core.sltrain._grads_distributed``, for ``exec_mode="fused"``).

    Under pjit-auto with the tile consts sharded over model
    (dist/sharding: rows_t/cols_t/perm shard their nnt axis like A's
    d_out), the fused vjp's contractions would still make XLA assemble
    full-width operands. Tile-CSR is naturally shardable on the column-
    tile axis — a tile's indices are LOCAL to its 128×128 block, so a
    model shard's (nkt, nnt/TP, cap) const slice addresses exactly its
    own dy columns with no index arithmetic. The island runs the same
    eq.-(2) algebra as ``_fused_grads`` on local slices and psums only
    r- and tile-sized results:

      tokens over (pod, data); d_out / A / tile consts over model:
        dA  = psum_bt(scale · (x·B)ᵀ · dy_loc)      — stays model-sharded
        dB  = psum_bt+model(scale · xᵀ · (dy_loc·A_locᵀ))
        dv  = psum_bt(sddmm local tiles)            — stays model-sharded
        dx  = psum_model(sl_matmul(dy_loc, A_locᵀ, Bᵀ, local tilesᵀ))

    Returns (dx, dB, dA, dv_t f32) or None when the geometry doesn't
    shard (no mesh, TP=1, misaligned dims, down-projection) — callers
    fall back to the local path. Same try/except contract as the densify
    island: composition must degrade, never error."""
    from jax.sharding import PartitionSpec as P

    from repro.dist import sharding as dist_sharding
    mesh = dist_sharding.ambient_mesh()
    if mesh is None or getattr(mesh, "empty", False) or x.ndim != 3 \
            or dy.ndim != 3:
        return None
    d_in = x.shape[-1]
    d_out = dy.shape[-1]
    if d_in > d_out:
        # island edge would gather the larger activation — the same wire
        # heuristic as the densify path (§Perf it.9)
        return None
    axes = mesh.axis_names
    bt = tuple(a for a in ("pod", "data") if a in axes)
    nb = int(np.prod([mesh.shape[a] for a in bt])) if bt else 1
    nm = mesh.shape.get("model", 1) if "model" in axes else 1
    nnt = v_t.shape[1]
    if (not bt or nm <= 1 or x.shape[0] % nb
            or d_out % (nm * 128) or nnt % nm):
        return None
    d_out_loc = d_out // nm
    f32 = jnp.float32

    def body(xs, dys, B_r, A_l, vt_l, rt_l, ct_l):
        xl = xs.reshape(-1, d_in)
        dyl = dys.reshape(-1, d_out_loc).astype(xl.dtype)
        xB = jnp.matmul(xl, B_r, preferred_element_type=f32)
        dA = jax.lax.psum(
            scale * _tok_dot(xB, dyl.astype(f32)), bt)
        dyA = jnp.matmul(dyl, A_l.T, preferred_element_type=f32)
        dB = jax.lax.psum(
            scale * _tok_dot(xl.astype(f32), dyA), bt + ("model",))
        dv = jax.lax.psum(sddmm(xl, dyl, rt_l, ct_l), bt)
        dx = sl_matmul(dyl, A_l.T, B_r.T, jnp.swapaxes(vt_l, 0, 1),
                       jnp.swapaxes(ct_l, 0, 1), jnp.swapaxes(rt_l, 0, 1),
                       scale)
        dx = jax.lax.psum(dx.astype(f32), "model")
        return dx, dB, dA, dv

    try:
        dx, dB, dA, dv_t = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(bt, None, None), P(bt, None, "model"),
                      P(None, None), P(None, "model"),
                      P(None, "model", None), P(None, "model", None),
                      P(None, "model", None)),
            out_specs=(P(bt, None), P(None, None), P(None, "model"),
                       P(None, "model", None)),
            check_vma=False)(x, dy, B, A, v_t, rows_t, cols_t)
    except Exception:
        return None
    dx = dx.reshape(x.shape).astype(x.dtype)
    return dx, dB.astype(B.dtype), dA.astype(A.dtype), dv_t


def _fused_grads(x, B, A, v_t, rows_t, cols_t, scale, dy):
    """Shared backward math of the fused linear: (dx, dB, dA, dv_t f32).

    Factored grads via the (token-dim contracted) products — same algebra
    as core.sltrain; the d_in×d_out transient only ever exists per-tile
    inside the sddmm kernel. All chains accumulate in f32 (an xf@B whose
    RESULT is cast to f32 rounds the token contraction through bf16 first
    — the PR-1 sparse-decode bug class); dv_t stays the sddmm kernel's
    f32 accumulator output. When a TP mesh is ambient and the geometry
    divides, the work routes through :func:`_fused_grads_dist` instead
    (local slices + psum'd small results)."""
    out = _fused_grads_dist(x, B, A, v_t, rows_t, cols_t, scale, dy)
    if out is not None:
        return out
    k = x.shape[-1]
    n = dy.shape[-1]
    # backward activations in the model dtype (§Perf it.9), like the
    # densify path — also what lets the MXU dots pair matching dtypes
    dy = dy.astype(x.dtype)
    xf = x.reshape(-1, k)
    dyf = dy.reshape(-1, n)
    # bf16 operands with f32 accumulation (preferred_element_type) — the
    # products are exact in f32, so this equals an upcast matmul at native
    # MXU speed; the second-level dots carry the f32 intermediate
    f32 = jnp.float32
    xB = jnp.matmul(xf, B, preferred_element_type=f32)    # (M, r) f32
    dA = (scale * _tok_dot(xB, dyf.astype(f32))).astype(A.dtype)
    dyA = jnp.matmul(dyf, A.T, preferred_element_type=f32)  # (M, r) f32
    dB = (scale * _tok_dot(xf.astype(f32), dyA)).astype(B.dtype)
    with jax.named_scope("dv"):
        dv_t = sddmm(xf, dyf, rows_t, cols_t)             # f32 tiles
    # dx = dy @ W^T: reuse the fused kernel on the transposed factors. The
    # support transpose is (cols_t, rows_t) tiles transposed in the grid —
    # equivalently run sl_matmul with swapped tile axes.
    with jax.named_scope("dx"):
        vt_T = jnp.swapaxes(v_t, 0, 1)
        rt_T = jnp.swapaxes(cols_t, 0, 1)
        ct_T = jnp.swapaxes(rows_t, 0, 1)
        dx = sl_matmul(dyf, A.T, B.T, vt_T, rt_T, ct_T, scale
                       ).reshape(x.shape).astype(x.dtype)
    return dx, dB, dA, dv_t


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def sl_linear_fused(x, B, A, v_t, rows_t, cols_t, scale):
    return sl_matmul(x, B, A, v_t, rows_t, cols_t, scale)


def _fused_fwd(x, B, A, v_t, rows_t, cols_t, scale):
    y = sl_matmul(x, B, A, v_t, rows_t, cols_t, scale)
    return y, (x, B, A, v_t, rows_t, cols_t)


def _fused_bwd(scale, res, dy):
    x, B, A, v_t, rows_t, cols_t = res
    dx, dB, dA, dv_t = _fused_grads(x, B, A, v_t, rows_t, cols_t, scale, dy)
    return dx, dB, dA, dv_t.astype(v_t.dtype), None, None


sl_linear_fused.defvjp(_fused_fwd, _fused_bwd)


# ---------------------------------------------------------------------------
# Flat-v fused linear (exec_mode="fused" training path)
# ---------------------------------------------------------------------------

def _gather_tiles(v, perm):
    """Flat trainable v → f32 tile values through the layout permutation.
    Padding slots (perm == -1) contribute exactly 0 through the kernel."""
    vf = v.reshape(-1).astype(jnp.float32)
    safe = jnp.clip(perm, 0, vf.shape[0] - 1)
    return jnp.where(perm >= 0, vf[safe], 0.0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def sl_linear(x, B, A, v, rows_t, cols_t, perm, scale):
    """y = x @ (scale·B·A ⊕ V) with the trainable ``v`` in its FLAT layout
    (row-balanced (d_in, k) or COO (nnz,)) — the param-tree leaf the
    optimizer/checkpoints see. The tile gather (fwd) and scatter (bwd)
    happen inside the jit, so only the layout-independent flat v is ever
    state; tile order is a pure function of the int consts from
    ``prepare_tile_consts``. Its device ops carry the pass they belong to
    in their scope: ``fwd``, and in the backward ``dx`` and ``dv``."""
    with jax.named_scope("fwd"):
        return sl_matmul(x, B, A, _gather_tiles(v, perm), rows_t, cols_t,
                         scale)


def _sl_linear_fwd(x, B, A, v, rows_t, cols_t, perm, scale):
    with jax.named_scope("fwd"):
        v_t = _gather_tiles(v, perm)
        y = sl_matmul(x, B, A, v_t, rows_t, cols_t, scale)
    # residuals stay factored-sized (Alg. 1): v_t is nnz+pad floats, never
    # the (d_in, d_out) dense W
    return y, (x, B, A, v, v_t, rows_t, cols_t, perm)


def _sl_linear_bwd(scale, res, dy):
    x, B, A, v, v_t, rows_t, cols_t, perm = res
    dx, dB, dA, dv_t = _fused_grads(x, B, A, v_t, rows_t, cols_t, scale, dy)
    # scatter the f32 tile grads back through perm onto the flat layout;
    # every valid perm entry appears exactly once (tile_layout invariant)
    # so the add is exact, padding rides the clipped index with a 0 value
    with jax.named_scope("dv"):
        pf = perm.reshape(-1)
        flat = jnp.where(pf >= 0, dv_t.reshape(-1), 0.0)
        dv = jnp.zeros((v.size,), jnp.float32).at[
            jnp.clip(pf, 0, v.size - 1)].add(flat)
    return (dx, dB, dA, dv.reshape(v.shape).astype(v.dtype),
            None, None, None)


sl_linear.defvjp(_sl_linear_fwd, _sl_linear_bwd)


# ---------------------------------------------------------------------------
# 8-bit Adam wrapper (flat pytree leaf)
# ---------------------------------------------------------------------------

def adam8bit_update(p, g, m_codes, m_scales, v_codes, v_scales, *,
                    lr, b1, b2, bc1, bc2, eps, wd, q: int = 256,
                    omb1=None, omb2=None, interpret: bool | None = None):
    """One fused 8-bit Adam step on an arbitrary-shape leaf.

    ``omb1``/``omb2`` are the (1 - beta) terms; when the betas are plain
    python floats they default to the full-precision python subtraction,
    matching the ``optim/quant.py`` reference bitwise (an in-kernel f32
    ``1 - b2`` loses ~half the bits of the ~1e-3 difference — ISSUE-4
    audit)."""
    interp = interpret_mode(interpret)
    shape = p.shape
    n = p.size
    if omb1 is None:
        omb1 = 1.0 - b1
    if omb2 is None:
        omb2 = 1.0 - b2

    n_q = -(-n // q)
    # rows per kernel instance: a multiple of 32, the int8 sublane tile; a
    # leaf whose block count it does not divide gets zero blocks appended,
    # which the n_valid mask keeps out of every result
    bb = 64 if n_q % 64 == 0 else 32
    n_rows = n_q + (-n_q) % bb

    def blk(a):
        """Pad a leaf (p, g) or its codes to n_rows whole blocks."""
        f = a.reshape(-1)
        return jnp.pad(f, (0, n_rows * q - f.size)).reshape(n_rows, q)

    def col(s):
        return jnp.pad(s.reshape(-1), (0, n_rows - n_q)).reshape(n_rows, 1)

    scalars = jnp.array([lr, b1, b2, omb1, omb2, bc1, bc2, eps, wd, 0.0],
                        jnp.float32)
    n_valid = jnp.array([n], jnp.int32)
    new_p, mc, ms, vc, vs = adam8bit_kernel.adam8bit_update(
        blk(p), blk(g), blk(m_codes), col(m_scales), blk(v_codes),
        col(v_scales), scalars, n_valid, bb=bb, interpret=interp)
    return (new_p.reshape(-1)[:n].reshape(shape), mc[:n_q], ms[:n_q, 0],
            vc[:n_q], vs[:n_q, 0])


# ---------------------------------------------------------------------------
# Paged-attention decode (serve path: attend over KV block pools in place)
# ---------------------------------------------------------------------------

def paged_attention(q, k_pool, v_pool, block_table, positions, *,
                    scale: float, softcap: float = 0.0, window: int = 0,
                    interpret: bool | None = None):
    """Decode attention directly over the paged K/V pools (serve/kv.py) —
    the ``attn_kernel="paged"`` path of ``models/attention``.

    q: (n_slots, H, hd) — ONE query token per slot, already rope'd; pools
    (n_blocks, block_len, Hkv, hd); block_table (n_slots, blocks_per_slot)
    int32; positions (n_slots,) int32 per-slot query positions. Handles
    GQA by regrouping q to (n_slots, Hkv, H//Hkv, hd) so each kv head's
    block stream serves its whole query group. Returns (n_slots, H, hd)
    in q.dtype. Unlike the gather path this never materializes the
    (n_slots, view_len) per-slot view: HBM K/V traffic is the slots' live
    blocks, not n_slots × view_len.
    """
    from repro.kernels import paged_attention as pa_kernel
    interp = interpret_mode(interpret)
    n_slots, n_heads, hd = q.shape
    n_kv = k_pool.shape[2]
    assert n_heads % n_kv == 0, (n_heads, n_kv)
    q4 = q.reshape(n_slots, n_kv, n_heads // n_kv, hd)
    out = pa_kernel.paged_attention(
        q4, k_pool, v_pool, block_table.astype(jnp.int32),
        positions.astype(jnp.int32), scale=scale, softcap=softcap,
        window=window, interpret=interp)
    return out.reshape(n_slots, n_heads, hd)


def paged_prefill_attention(q, k_pool, v_pool, block_table, offsets, *,
                            scale: float, softcap: float = 0.0,
                            window: int = 0,
                            interpret: bool | None = None):
    """Chunked-prefill attention over paged pools — the prefill sibling of
    :func:`paged_attention` for ``attn_kernel="paged"``.

    q: (n_slots, sq, H, hd) — each slot's SUFFIX chunk, rope'd at absolute
    positions offsets[s] + [0, sq); the chunk's own K/V must already be
    scattered into the pools (the kernel attends prior pages and the
    chunk through one causal block sweep — shared-prefix pages are read
    in place, never re-written). offsets: (n_slots,) int32 absolute
    position of each slot's first chunk token. Returns (n_slots, sq, H,
    hd) in q.dtype; padding rows / idle slots come back as exact zeros.
    """
    from repro.kernels import paged_attention as pa_kernel
    interp = interpret_mode(interpret)
    n_slots, sq, n_heads, hd = q.shape
    n_kv = k_pool.shape[2]
    assert n_heads % n_kv == 0, (n_heads, n_kv)
    q5 = q.reshape(n_slots, sq, n_kv, n_heads // n_kv, hd)
    out = pa_kernel.paged_prefill(
        q5, k_pool, v_pool, block_table.astype(jnp.int32),
        offsets.astype(jnp.int32), scale=scale, softcap=softcap,
        window=window, interpret=interp)
    return out.reshape(n_slots, sq, n_heads, hd)


# ---------------------------------------------------------------------------
# Factored decode path (sparse-only kernel + small low-rank dots)
# ---------------------------------------------------------------------------

def sl_decode(x, B, A, v_t, rows_t, cols_t, scale: float, *,
              interpret: bool | None = None):
    """SLTrain decode matmul without densifying: (x·B)·A·scale + x·S via the
    sparse_decode kernel (DESIGN §3 beyond-paper). Reads factored bytes
    only — the decode HBM term drops by the compression ratio."""
    from repro.kernels import sparse_decode as sd_kernel
    interp = interpret_mode(interpret)
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = A.shape[-1]
    xf = x.reshape(-1, k)
    m = xf.shape[0]
    bm = 16
    pad_m = (-m) % bm
    pad_k = (-k) % 128
    xp = jnp.pad(xf, ((0, pad_m), (0, pad_k)))
    # low-rank term in f32 (bf16 intermediate rounding drifts from the
    # densified path — same accumulation fix as core.sltrain sparse mode)
    y_lr = ((xf.astype(jnp.float32) @ B.astype(jnp.float32))
            @ A.astype(jnp.float32)) * scale
    y_sp = sd_kernel.sparse_matmul(xp, v_t, rows_t, cols_t, bm=bm,
                                   interpret=interp)[:m, :n]
    return (y_lr + y_sp.astype(jnp.float32)).astype(x.dtype).reshape(*lead, n)


def sl_quant_decode(x, B, A, qv_t, rows_q, cols_q, qscale, scale: float, *,
                    interpret: bool | None = None):
    """Quantized SLTrain decode matmul (``exec_mode="quant"``, repro.quant):
    (x·B)·A·scale in f32 + x·dequant(S) through the int8 tile-CSR kernel.
    B/A are the bf16 error-folded factors from quant.calibrate; the sparse
    term reads qv int8 + int16 local indices + the per-channel f32 scale
    vector — ~5·δ B/cell vs the bf16 tile-CSR's 12·δ."""
    from repro.kernels import sparse_decode as sd_kernel
    interp = interpret_mode(interpret)
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = A.shape[-1]
    xf = x.reshape(-1, k)
    m = xf.shape[0]
    bm = 16
    xp = jnp.pad(xf, ((0, (-m) % bm), (0, (-k) % 128)))
    y_lr = ((xf.astype(jnp.float32) @ B.astype(jnp.float32))
            @ A.astype(jnp.float32)) * scale
    y_sp = sd_kernel.quant_sparse_matmul(xp, qv_t, rows_q, cols_q, qscale,
                                         bm=bm, interpret=interp)[:m, :n]
    return (y_lr + y_sp.astype(jnp.float32)).astype(x.dtype).reshape(*lead, n)
