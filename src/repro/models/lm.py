"""Decoder-only LM covering the llama / qwen / yi / gemma2 / MoE / VLM
families, with scan-over-layers (stacked params → O(1) HLO in depth) and a
KV-cache decode path.

Heterogeneous layer patterns (gemma2 local/global alternation, deepseek
first-k-dense) are handled by scanning over *pattern periods*: the stacks
are shaped (L/P, P, ...) and the P intra-period blocks are unrolled with
static kinds, so the scan body stays uniform (DESIGN §6).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import attention, mlp
from repro.models.common import (Builder, remat_wrap, rms_norm, softcap,
                                 stack_layers)


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _init_block(b: Builder, cfg: ModelConfig, use_moe: bool, d_ff_dense: int = 0):
    params, consts = {}, {}
    params["ln_attn"] = b.tensor("ln_attn", (cfg.d_model,), "zeros" if cfg.use_post_norms else "ones")
    p, c = attention.init_attention(b.sub("attn"), cfg)
    params["attn"] = p
    if c:
        consts["attn"] = c
    params["ln_mlp"] = b.tensor("ln_mlp", (cfg.d_model,), "zeros" if cfg.use_post_norms else "ones")
    if cfg.use_post_norms:
        params["ln_attn_post"] = b.tensor("ln_attn_post", (cfg.d_model,), "zeros")
        params["ln_mlp_post"] = b.tensor("ln_mlp_post", (cfg.d_model,), "zeros")
    if use_moe:
        p, c = mlp.init_moe(b.sub("moe"), cfg)
        params["moe"] = p
        if c:
            consts["moe"] = c
    else:
        p, c = mlp.init_mlp(b.sub("mlp"), cfg, d_ff=d_ff_dense or cfg.d_ff)
        params["mlp"] = p
        if c:
            consts["mlp"] = c
    return params, consts


def _apply_block(cfg: ModelConfig, p, c, x, *, window: int, cache=None,
                 cache_index=None, pos_offset=0, block_table=None,
                 prefill: bool = False):
    plus_one = cfg.family in ("gemma2", "vlm")
    act = "gelu" if cfg.family in ("gemma2", "vlm") else "silu"
    norm = lambda t, w: rms_norm(t, w, cfg.norm_eps, plus_one=plus_one)
    h = norm(x, p["ln_attn"])
    # scopes name each linear's device ops by its role (attn/wq ...
    # mlp/down); the layers run in a scan, so no layer index is needed
    with jax.named_scope("attn"):
        a, new_cache = attention.apply_attention(
            cfg, p["attn"], c.get("attn", {}), h, pos_offset=pos_offset,
            causal=True, window=window, cache=cache, cache_index=cache_index,
            block_table=block_table, prefill=prefill)
    if cfg.use_post_norms:
        a = norm(a, p["ln_attn_post"])
    x = x + a
    h = norm(x, p["ln_mlp"])
    aux = jnp.float32(0.0)
    if "moe" in p:
        m, aux = mlp.apply_moe(cfg, p["moe"], c.get("moe", {}), h)
    else:
        with jax.named_scope("mlp"):
            m = mlp.apply_mlp(cfg, p["mlp"], c.get("mlp", {}), h, act=act)
    if cfg.use_post_norms:
        m = norm(m, p["ln_mlp_post"])
    return x + m, new_cache, aux


# ---------------------------------------------------------------------------
# Model init
# ---------------------------------------------------------------------------

def _pattern(cfg: ModelConfig):
    pat = cfg.attn_pattern or ("global",)
    assert cfg.n_layers % len(pat) == 0 or cfg.moe.first_k_dense, \
        f"{cfg.name}: n_layers {cfg.n_layers} not divisible by pattern {pat}"
    return pat


def init_lm(cfg: ModelConfig, key=None, seed: int = 0):
    b = Builder(cfg, key, seed=seed)
    params, consts = {}, {}
    params["embed"] = b.tensor("embed", (cfg.padded_vocab, cfg.d_model),
                               "normal", fan_in=cfg.d_model)
    use_moe = cfg.moe.n_experts > 0
    pat = _pattern(cfg)
    n_dense = cfg.moe.first_k_dense if use_moe else 0
    n_rest = cfg.n_layers - n_dense

    if n_dense:
        params["dense_layers"], cd = stack_layers(
            b.sub("dense"), lambda bb: _init_block(bb, cfg, False, cfg.moe.d_ff_dense),
            n_dense, "dl")
        if cd:
            consts["dense_layers"] = cd

    period = len(pat)
    assert n_rest % period == 0

    def init_period(bb: Builder):
        ps, cs = [], []
        for j, kind in enumerate(pat):
            p, c = _init_block(bb.sub(f"k{j}"), cfg, use_moe)
            ps.append(p)
            cs.append(c)
        return {f"k{j}": ps[j] for j in range(period)}, \
               {f"k{j}": cs[j] for j in range(period) if cs[j]}

    params["layers"], cl = stack_layers(b.sub("blocks"), init_period,
                                        n_rest // period, "p")
    if cl:
        consts["layers"] = cl
    params["ln_f"] = b.tensor("ln_f", (cfg.d_model,),
                              "zeros" if cfg.family in ("gemma2", "vlm") else "ones")
    if not cfg.tie_embeddings:
        params["lm_head"] = b.tensor("lm_head", (cfg.d_model, cfg.padded_vocab),
                                     "normal", fan_in=cfg.d_model)
    return params, consts


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------

def _embed_tokens(cfg, params, tokens):
    h = jnp.take(params["embed"], tokens, axis=0)
    if cfg.family in ("gemma2", "vlm"):
        h = h * jnp.asarray(np.sqrt(cfg.d_model), h.dtype)
    return h


def embed_apply(cfg: ModelConfig, params, tokens, patch_embeds=None):
    """The model's input segment: token embed (+ VLM patch splice). Takes
    only the params it reads ({"embed": leaf}) so the per-layer sweep can
    jax.vjp it against exactly that subtree."""
    h = _embed_tokens(cfg, params, tokens)
    if patch_embeds is not None:
        h = jnp.concatenate([patch_embeds.astype(h.dtype),
                             h[:, patch_embeds.shape[1]:]], axis=1)
    return h


def _unembed(cfg, params, h):
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = h @ w.astype(h.dtype)
    if cfg.final_logit_softcap > 0:
        logits = softcap(logits.astype(jnp.float32), cfg.final_logit_softcap)
    return logits


def _window_for(cfg, kind: str) -> int:
    return cfg.sliding_window if kind == "local" else 0


def _sp_constraint(cfg, h):
    """Sequence-parallel residual constraint (§Perf): shard (B, S, d) as
    P(batch_axes, "model", None) when the ambient mesh has those axes and
    the dims divide. No-op on meshes without a model axis (CPU tests).

    All-or-nothing on purpose: if either the batch or the seq dim fails
    to divide, skip the constraint entirely — a partial (seq-only) pin
    would de-shard the surrounding remat region (the §Perf it.6 lesson
    recorded in core/sltrain.py)."""
    if not cfg.seq_shard_activations:
        return h
    from repro.dist import sharding as dist_sharding
    mesh = dist_sharding.ambient_mesh()
    if mesh is None or dist_sharding.MODEL_AXIS not in mesh.axis_names:
        return h
    batch_axes = tuple(a for a in dist_sharding.BATCH_AXES
                       if a in mesh.axis_names)
    nb = dist_sharding.axis_size(mesh, batch_axes)
    nm = dist_sharding.axis_size(mesh, dist_sharding.MODEL_AXIS)
    if h.shape[0] % max(nb, 1) or h.shape[1] % nm:
        return h
    return dist_sharding.constrain(h, batch_axes,
                                   dist_sharding.MODEL_AXIS, None)


def period_apply(cfg: ModelConfig, p, c, x):
    """One scan step of the layer stack: the full attn-pattern period.
    (x, params, consts) → (x', aux). The per-layer backward sweep vjp's
    this exact function, so train forward and sweep recompute cannot
    drift."""
    pat = _pattern(cfg)
    aux = jnp.float32(0.0)
    for j, kind in enumerate(pat):
        x, _, a = _apply_block(cfg, p[f"k{j}"], c.get(f"k{j}", {}), x,
                               window=_window_for(cfg, kind))
        aux = aux + a
    return _sp_constraint(cfg, x), aux


def dense_apply(cfg: ModelConfig, p, c, x):
    """One MoE first-k-dense prefix block. (x, params, consts) → (x', aux)."""
    x, _, a = _apply_block(cfg, p, c, x, window=0)
    return x, a


def head_apply(cfg: ModelConfig, params, h):
    """Final norm + unembed. ``params`` needs only the head leaves:
    {"ln_f", "lm_head"} (untied) or {"ln_f", "embed"} (tied)."""
    h = rms_norm(h, params["ln_f"], cfg.norm_eps,
                 plus_one=cfg.family in ("gemma2", "vlm"))
    return _unembed(cfg, params, h)


def apply_lm(cfg: ModelConfig, params, consts, tokens, *, patch_embeds=None,
             remat: str = "none"):
    """tokens: (B, S[, ]) int32 → (logits (B, S, V), aux losses).

    For VLM, patch_embeds (B, n_patches, d) replace the first n_patches
    positions (the stub frontend's output, DESIGN §5)."""
    h = embed_apply(cfg, params, tokens, patch_embeds)
    aux_total = jnp.float32(0.0)

    period_body = remat_wrap(
        lambda x, layer: period_apply(cfg, layer[0], layer[1], x), remat)

    if "dense_layers" in params:
        def dense_body(x, layer):
            p, c = layer
            return dense_apply(cfg, p, c, x)
        h, aux_d = jax.lax.scan(dense_body, h,
                                (params["dense_layers"],
                                 consts.get("dense_layers", {})))
        aux_total = aux_total + aux_d.sum()

    h, aux = jax.lax.scan(period_body, h,
                          (params["layers"], consts.get("layers", {})))
    aux_total = aux_total + aux.sum()
    return head_apply(cfg, params, h), aux_total


def forward_saving_boundaries(cfg: ModelConfig, params, consts, tokens, *,
                              patch_embeds=None, remat: str = "none"):
    """The SAME forward as :func:`apply_lm` up to the final norm, but each
    scan step additionally emits its INPUT boundary activation — the
    recompute roots the per-layer backward sweep (repro.train.perlayer)
    re-runs one layer at a time from. Saved boundaries are the only
    O(n_layers) activation term; intra-layer residuals are recomputed per
    layer under the configured remat policy.

    Returns a dict:
      h0        — embed output (the first boundary),
      dense_xs  — (n_dense, B, S, d) inputs of the MoE dense prefix (or None),
      xs        — (n_periods, B, S, d) inputs of each period scan step,
      h_top     — final residual (input to the head),
      aux_dense — (n_dense,) per-block aux losses (or None),
      aux       — (n_periods,) per-period aux losses.
    """
    from repro.dist import sharding as dist_sharding
    h0 = embed_apply(cfg, params, tokens, patch_embeds)
    save = lambda x: dist_sharding.constrain_boundary(
        x, seq_sharded=cfg.seq_shard_activations)

    h = h0
    dense_xs = aux_d = None
    if "dense_layers" in params:
        def dense_body(x, layer):
            p, c = layer
            nx, a = dense_apply(cfg, p, c, x)
            return nx, (save(x), a)
        h, (dense_xs, aux_d) = jax.lax.scan(
            dense_body, h, (params["dense_layers"],
                            consts.get("dense_layers", {})))

    def period_body(x, layer):
        p, c = layer
        nx, a = period_apply(cfg, p, c, x)
        return nx, (save(x), a)
    period_body = remat_wrap(period_body, remat)

    h_top, (xs, aux) = jax.lax.scan(period_body, h,
                                    (params["layers"],
                                     consts.get("layers", {})))
    return {"h0": h0, "dense_xs": dense_xs, "xs": xs, "h_top": h_top,
            "aux_dense": aux_d, "aux": aux}


# ---------------------------------------------------------------------------
# Decode (serve_step)
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int, abstract: bool = False,
               *, paged: bool = False, block_len: int = 16, n_blocks: int = 0):
    """Contiguous KV cache (default): leaves (lead, batch, max_len, Hkv, hd).

    ``paged=True`` builds the block-paged layout instead (serve/kv.py):
    leaves are block pools (lead, n_blocks, block_len, Hkv, hd) shared by
    every decode slot through a block table; ``n_blocks`` defaults to full
    capacity (batch slots × max_len) plus the null block."""
    hd = cfg.resolved_head_dim
    pat = _pattern(cfg)
    n_periods = (cfg.n_layers - (cfg.moe.first_k_dense or 0)) // len(pat)
    dt = jnp.dtype(cfg.dtype)

    def mk(shape):
        if abstract:
            return jax.ShapeDtypeStruct(shape, dt)
        return jnp.zeros(shape, dt)

    if paged:
        from repro.serve.kv import PagedLayout
        layout = PagedLayout.plan(batch, max_len, block_len, n_blocks)
        tail = (layout.n_blocks, layout.block_len, cfg.n_kv_heads, hd)
    else:
        tail = (batch, max_len, cfg.n_kv_heads, hd)
    kv = lambda lead: {"k": mk(lead + tail), "v": mk(lead + tail)}
    cache = {"layers": {f"k{j}": kv((n_periods,)) for j in range(len(pat))}}
    if cfg.moe.first_k_dense:
        cache["dense_layers"] = kv((cfg.moe.first_k_dense,))
    return cache


def _cached_forward(cfg: ModelConfig, params, consts, tokens, cache, index,
                    block_table, prefill: bool):
    """Shared layer-stack walk for decode_step and prefill_step — the two
    must stay in lockstep (same dense-prefix scan, same period scan, same
    final norm/unembed), so the walk exists exactly once."""
    h = _embed_tokens(cfg, params, tokens)
    pat = _pattern(cfg)
    blk = lambda x, p, c, kv, window: _apply_block(
        cfg, p, c, x, window=window, cache=kv, cache_index=index,
        block_table=block_table, prefill=prefill)

    if "dense_layers" in params:
        def dense_body(x, layer):
            p, c, kv = layer
            x, nkv, _ = blk(x, p, c, kv, 0)
            return x, nkv
        h, new_kv = jax.lax.scan(dense_body, h,
                                 (params["dense_layers"],
                                  consts.get("dense_layers", {}),
                                  cache["dense_layers"]))
        cache = {**cache, "dense_layers": new_kv}

    def period_body(x, layer):
        p, c, kv = layer
        new_kv = {}
        for j, kind in enumerate(pat):
            x, nk, _ = blk(x, p[f"k{j}"], c.get(f"k{j}", {}), kv[f"k{j}"],
                           _window_for(cfg, kind))
            new_kv[f"k{j}"] = nk
        return x, new_kv

    h, new_layers = jax.lax.scan(period_body, h,
                                 (params["layers"],
                                  consts.get("layers", {}),
                                  cache["layers"]))
    cache = {**cache, "layers": new_layers}
    h = rms_norm(h, params["ln_f"], cfg.norm_eps,
                 plus_one=cfg.family in ("gemma2", "vlm"))
    return _unembed(cfg, params, h), cache


def decode_step(cfg: ModelConfig, params, consts, tokens, cache, index,
                *, block_table=None):
    """One decode step. tokens: (B, 1) int32; index: scalar position shared
    by the batch, or a (B,) vector — each slot writes/attends at its own
    position. ``block_table`` (B, blocks_per_slot) switches the cache leaves
    to the paged-pool layout (serve/kv.py). Returns (logits, new_cache)."""
    return _cached_forward(cfg, params, consts, tokens, cache, index,
                           block_table, prefill=False)


def prefill_step(cfg: ModelConfig, params, consts, tokens, cache,
                 *, block_table=None, offsets=None):
    """Batched prefill: run the whole prompt batch (B, S) through the
    train-style chunked-attention forward ONCE, writing K/V for positions
    [0, S) into the cache as each layer computes them. Returns
    (logits (B, S, V), new_cache) — logits[s, len_s - 1] scores the first
    generated token of slot s.

    All rows start at position 0 (fresh slots). With ``block_table``, rows
    that must not be written (slots mid-decode in the same batch) are
    protected by nulling their table rows — see serve/kv.py. Without a
    block table the contiguous cache is written on EVERY row, so only call
    it when the whole batch is fresh.

    ``offsets`` (B,) int32 (paged only) switches to chunked SUFFIX
    prefill: row s's tokens sit at absolute positions offsets[s] + [0, S)
    and attend the slot's PRIOR pages in place — the shared-prefix path,
    where an admission that attached resident prefix blocks read-only
    prefills only the divergent suffix. logits[s, suffix_len_s - 1] then
    scores the first generated token."""
    index = jnp.int32(0) if offsets is None else offsets.astype(jnp.int32)
    return _cached_forward(cfg, params, consts, tokens, cache, index,
                           block_table, prefill=True)
