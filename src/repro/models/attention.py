"""GQA attention with RoPE, sliding windows, logit softcap, QK-norm, KV cache.

Prefill/train uses a q-chunked attention (scan over query blocks, full-K
scores per block) so the score transient is O(chunk·S) not O(S²) — required
for the 32k-prefill dry-run cells to fit HBM (DESIGN §6).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.common import (Builder, constrain, rms_norm, rope,
                                 scoped_linear, softcap)


def init_attention(b: Builder, cfg: ModelConfig, cross: bool = False):
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    nh, nkv = cfg.n_heads, cfg.n_kv_heads
    params, consts = {}, {}
    for name, d_out in (("wq", nh * hd), ("wk", nkv * hd), ("wv", nkv * hd)):
        p, c = b.linear(name, d, d_out, adapted=True, bias=cfg.qkv_bias)
        params[name] = p
        if c:
            consts[name] = c
    p, c = b.linear("wo", nh * hd, d, adapted=True)
    params["wo"] = p
    if c:
        consts["wo"] = c
    if cfg.qk_norm:
        params["q_norm"] = b.tensor("q_norm", (hd,), "ones")
        params["k_norm"] = b.tensor("k_norm", (hd,), "ones")
    return params, consts


def _split_heads(x, n_heads, head_dim):
    return x.reshape(*x.shape[:-1], n_heads, head_dim)


def _attend(cfg: ModelConfig, q, k, v, q_pos, k_pos, *, causal, window,
            q_chunk: int = 1024):
    """q: (B,Sq,H,hd); k,v: (B,Sk,Hkv,hd); positions: (Sq,) or (B,Sq) for
    q_pos (per-slot decode positions), (Sk,) for k_pos."""
    bsz, sq, nh, hd = q.shape
    sk, nkv = k.shape[1], k.shape[2]
    group = nh // nkv
    scale = (cfg.query_pre_attn_scalar or hd) ** -0.5
    qg = q.reshape(bsz, sq, nkv, group, hd)
    # SP layout (§Perf it.4): q stays sequence-sharded over "model"; k/v are
    # gathered once (the only per-layer collective); the score tensor is
    # PINNED to q-seq sharding so GSPMD never replicates it (the involuntary
    # full-rematerialization path it otherwise takes for indivisible heads).
    # Decode (sq == 1) is excluded: pinning k/v replicated would undo the
    # seq-sharded KV cache (§Perf C) and re-gather it every step.
    sp = cfg.seq_shard_activations and sq > 1
    batch = ("pod", "data")
    if sp:
        qg = constrain(qg, batch, "model", None, None, None)
        k = constrain(k, batch, None, None, None)
        v = constrain(v, batch, None, None, None)

    def block(q_blk, qpos_blk):
        s = jnp.einsum("bqhgd,bkhd->bhgqk", q_blk.astype(jnp.float32) * scale,
                       k.astype(jnp.float32))
        if sp:
            s = constrain(s, batch, None, None, "model", None)
        if cfg.attn_logit_softcap > 0:
            s = softcap(s, cfg.attn_logit_softcap)
        # (B,Sq) q_pos → per-slot mask (B,q,k); (Sq,) → shared (1,q,k)
        qp = qpos_blk if qpos_blk.ndim == 2 else qpos_blk[None]
        mask = jnp.ones((qp.shape[0], q_blk.shape[1], sk), dtype=bool)
        if causal:
            mask &= qp[:, :, None] >= k_pos[None, None, :]
        if window > 0:
            mask &= (qp[:, :, None] - k_pos[None, None, :]) < window
        s = jnp.where(mask[:, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        if sp:
            p = constrain(p, batch, None, None, "model", None)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p,
                       v.astype(jnp.float32)).astype(q.dtype)
        if sp:
            o = constrain(o, batch, "model", None, None, None)
        return o

    if sp or sq <= q_chunk or q_pos.ndim == 2:
        # under SP the per-shard q length is already sq/|model|; chunking
        # with lax.map would slice across the sharded dim and force gathers
        o = block(qg, q_pos)
    else:
        n_blocks = (sq + q_chunk - 1) // q_chunk
        pad = n_blocks * q_chunk - sq
        qg_p = jnp.pad(qg, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
        pos_p = jnp.pad(q_pos, (0, pad))
        qg_b = qg_p.reshape(bsz, n_blocks, q_chunk, nkv, group, hd).swapaxes(0, 1)
        pos_b = pos_p.reshape(n_blocks, q_chunk)
        o = jax.lax.map(lambda args: block(*args), (qg_b, pos_b))
        o = o.swapaxes(0, 1).reshape(bsz, n_blocks * q_chunk, nkv, group, hd)[:, :sq]
    return o.reshape(bsz, sq, nh * hd)


def apply_attention(cfg: ModelConfig, params, consts, x, *, pos_offset=0,
                    causal: bool = True, window: int = 0,
                    cache: Optional[dict] = None, cache_index=None,
                    kv_source=None, block_table=None, prefill: bool = False):
    """Self- (or cross-, via kv_source) attention.

    cache: {"k","v"}. Contiguous layout (B, S_max, Hkv, hd): decode writes
    k/v at ``cache_index`` — a scalar (one shared write offset) or a (B,)
    vector (each slot writes at its own position) — and attends over the
    whole cache with per-slot causal masking. Paged layout (``block_table``
    (B, blocks_per_slot) given): pools are (n_blocks, block_len, Hkv, hd)
    and writes scatter through the block table; how the READ runs is
    ``cfg.attn_kernel``:

    ==========  ==========================================================
    attn_kernel paged decode read path
    ==========  ==========================================================
    "gather"    materialize the gathered (B, view_len, Hkv, hd) per-slot
                view (``kv.gather_view``; null-block rows zeroed so
                garbage cannot ride 0-weight products) and run the dense
                ``_attend`` over it — HBM traffic O(B · view_len)/layer.
    "paged"     ``kernels/ops.paged_attention``: Pallas kernel streams
                K/V blocks through VMEM with online softmax (null blocks
                and past-position entries masked in-kernel, GQA groups
                broadcast in-kernel) — traffic O(live tokens)/layer. Used
                when decoding (sq == 1) with a per-slot position vector;
                per-slot chunked prefill (sq > 1 at per-slot offsets)
                dispatches the sibling ``paged_prefill_attention`` kernel
                (causal within the chunk, prior pages attended in place);
                remaining shapes (scalar-offset prefill, cross-attn) fall
                back to "gather".
    ==========  ==========================================================

    Both paths are value-equivalent within f32 attention tolerance
    (tests/test_paged_attention.py pins the matrix); "paged" is the
    default since the parity gates baked in CI ("gather" stays
    selectable, and is the automatic fallback whenever the cache is not
    paged).

    ``prefill=True`` runs the whole prompt train-style — attention over the
    just-computed local k/v (O(Sq²), chunked), not the S_max cache — while
    still writing k/v into the cache at positions [0, Sq). Contiguous
    prefill writes every batch row, so it is only safe when ALL rows are
    fresh; the paged path nulls non-admitted rows' table entries instead
    (their writes land in the null block).

    Returns (y, new_cache)."""
    hd = cfg.resolved_head_dim
    nh, nkv = cfg.n_heads, cfg.n_kv_heads
    lin = lambda n, t: scoped_linear(cfg, params, consts, n, t)
    bsz, sq = x.shape[0], x.shape[1]

    q = _split_heads(lin("wq", x), nh, hd)
    kv_in = x if kv_source is None else kv_source
    k = _split_heads(lin("wk", kv_in), nkv, hd)
    v = _split_heads(lin("wv", kv_in), nkv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)

    idx = cache_index if cache_index is not None else pos_offset
    per_slot = getattr(idx, "ndim", 0) == 1          # (B,) position vector
    if per_slot:
        q_pos = idx[:, None] + jnp.arange(sq, dtype=jnp.int32)[None]  # (B,Sq)
    else:
        q_pos = jnp.arange(sq, dtype=jnp.int32) + (
            idx if cache is not None else pos_offset)
    use_rope = cfg.family not in ("whisper",) and kv_source is None
    if use_rope:
        q = rope(q, q_pos if per_slot else q_pos[None], cfg.rope_theta)

    new_cache = cache
    if cache is not None and kv_source is None:
        if use_rope:
            k = rope(k, q_pos if per_slot else q_pos[None], cfg.rope_theta)
        if block_table is not None:
            from repro.serve import kv as kv_lib
            positions = q_pos if per_slot else \
                jnp.broadcast_to(q_pos[None], (bsz, sq))
            ck = kv_lib.scatter(cache["k"], block_table, positions, k)
            cv = kv_lib.scatter(cache["v"], block_table, positions, v)
            new_cache = {"k": ck, "v": cv}
            if not prefill:
                if cfg.attn_kernel == "paged" and sq == 1 and per_slot:
                    from repro.kernels import ops as kernel_ops
                    scale = (cfg.query_pre_attn_scalar or hd) ** -0.5
                    o = kernel_ops.paged_attention(
                        q[:, 0], ck, cv, block_table, idx, scale=scale,
                        softcap=cfg.attn_logit_softcap, window=window)
                    return lin("wo", o.reshape(bsz, 1, nh * hd)), new_cache
                k = kv_lib.gather_view(ck, block_table)
                v = kv_lib.gather_view(cv, block_table)
                # zero rows gathered from the null block: the causal mask
                # makes their softmax weight exactly 0, but 0 · NaN = NaN —
                # garbage in unallocated pages must not ride the p@v matmul
                live = jnp.repeat(block_table != 0, ck.shape[1], axis=1)
                k = jnp.where(live[:, :, None, None], k, 0)
                v = jnp.where(live[:, :, None, None], v, 0)
                k_pos = jnp.arange(k.shape[1], dtype=jnp.int32)
            elif per_slot:
                # chunked (suffix) prefill: slot s's queries sit at absolute
                # positions idx[s] + [0, sq) and must attend the PRIOR pages
                # (e.g. an attached shared prefix) as well as the chunk
                # itself — local-k attention is wrong whenever idx[s] > 0.
                # The chunk's own k/v was just scattered, so both read
                # paths see it through the pools.
                if cfg.attn_kernel == "paged":
                    from repro.kernels import ops as kernel_ops
                    scale = (cfg.query_pre_attn_scalar or hd) ** -0.5
                    o = kernel_ops.paged_prefill_attention(
                        q, ck, cv, block_table, idx, scale=scale,
                        softcap=cfg.attn_logit_softcap, window=window)
                    return lin("wo", o.reshape(bsz, sq, nh * hd)), new_cache
                k = kv_lib.gather_view(ck, block_table)
                v = kv_lib.gather_view(cv, block_table)
                live = jnp.repeat(block_table != 0, ck.shape[1], axis=1)
                k = jnp.where(live[:, :, None, None], k, 0)
                v = jnp.where(live[:, :, None, None], v, 0)
                k_pos = jnp.arange(k.shape[1], dtype=jnp.int32)
            else:
                k_pos = jnp.arange(sq, dtype=jnp.int32) + idx
        elif per_slot:
            rows = jnp.arange(bsz, dtype=jnp.int32)[:, None]
            cols = idx[:, None] + jnp.arange(sq, dtype=jnp.int32)[None]
            ck = cache["k"].at[rows, cols].set(k.astype(cache["k"].dtype))
            cv = cache["v"].at[rows, cols].set(v.astype(cache["v"].dtype))
            new_cache = {"k": ck, "v": cv}
            k, v = ck, cv
            k_pos = jnp.arange(cache["k"].shape[1], dtype=jnp.int32)
        else:
            ck = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), idx, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), idx, axis=1)
            new_cache = {"k": ck, "v": cv}
            if prefill:
                k_pos = q_pos       # attend local k/v, not the S_max cache
            else:
                k, v = ck, cv
                k_pos = jnp.arange(cache["k"].shape[1], dtype=jnp.int32)
    elif kv_source is not None:
        k_pos = jnp.arange(k.shape[1], dtype=jnp.int32)
    else:
        if use_rope:
            k = rope(k, q_pos[None], cfg.rope_theta)
        k_pos = q_pos

    o = _attend(cfg, q, k, v, q_pos, k_pos, causal=causal, window=window)
    return lin("wo", o), new_cache
