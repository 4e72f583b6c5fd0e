"""Feed-forward blocks: SwiGLU / GeGLU / plain-GELU, and top-k MoE with
shared experts (deepseek/qwen3 style), capacity-based dispatch (EP-friendly:
expert-stacked weights shard over the model axis; DESIGN §4)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import (Builder, apply_linear, gelu,
                                 scoped_linear, silu)


# ---------------------------------------------------------------------------
# Dense gated MLP
# ---------------------------------------------------------------------------

def init_mlp(b: Builder, cfg: ModelConfig, d_ff: int = 0, gated: bool = True):
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    params, consts = {}, {}
    names = (("gate", d, f), ("up", d, f), ("down", f, d)) if gated else \
            (("up", d, f), ("down", f, d))
    for name, di, do in names:
        p, c = b.linear(name, di, do)
        params[name] = p
        if c:
            consts[name] = c
    return params, consts


def apply_mlp(cfg: ModelConfig, params, consts, x, act: str = "silu"):
    lin = lambda n, t: scoped_linear(cfg, params, consts, n, t)
    a = {"silu": silu, "gelu": gelu}[act]
    if "gate" in params:
        return lin("down", a(lin("gate", x)) * lin("up", x))
    return lin("down", a(lin("up", x)))


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

def init_moe(b: Builder, cfg: ModelConfig):
    """Router (dense — paper keeps non-linear-layer params full-rank) +
    expert-stacked gated FFN + optional shared experts."""
    m = cfg.moe
    d, fe = cfg.d_model, m.d_ff_expert
    params, consts = {}, {}
    params["router"], _ = b.linear("router", d, m.n_experts, adapted=False)

    def expert(be: Builder):
        return init_mlp(be, cfg, d_ff=fe, gated=True)

    from repro.models.common import stack_layers
    params["experts"], cexp = stack_layers(b, expert, m.n_experts, "expert")
    if cexp:
        consts["experts"] = cexp
    if m.n_shared_experts:
        params["shared"], csh = init_mlp(
            b.sub("shared"), cfg, d_ff=fe * m.n_shared_experts, gated=True)
        if csh:
            consts["shared"] = csh
    return params, consts


def apply_moe(cfg: ModelConfig, params, consts, x, capacity_factor: float = 1.25):
    """Group-local capacity-based top-k dispatch (GShard-style, DESIGN §4).

    Tokens are split into G = cfg.moe_groups groups aligned with the batch
    sharding, routing/cumsum/gather are all GROUP-LOCAL (no cross-shard token
    traffic), expert compute is sharded over the model axis (EP), and the
    combine emits per-expert partials that GSPMD resolves with one
    all-reduce over the model axis. Overflowing tokens are dropped
    (combine weight 0) — standard Switch semantics, shapes static."""
    m = cfg.moe
    bsz, seq, d = x.shape
    n = bsz * seq
    g = max(1, cfg.moe_groups)
    if n % g:
        g = 1
    ng = n // g
    xg = x.reshape(g, ng, d)

    logits = apply_linear(cfg, params["router"], {}, xg, adapted=False)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)      # (G,Ng,E)
    gate_vals, expert_ids = jax.lax.top_k(probs, m.top_k)            # (G,Ng,k)
    gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)

    cap = int(max(1, capacity_factor * ng * m.top_k / m.n_experts))
    cap = min(cap, ng * m.top_k)
    # Position of each (token, k) slot in its expert's group-local queue.
    # Sort-based ranking (§Perf MoE it.2): the naive one-hot cumsum builds a
    # (N·k × E) int tensor — at qwen3 scale 4.3 TB read/written several
    # times per layer, the dominant HBM term of the whole step. Stable-sort
    # by expert id instead: O(N·k) memory, identical positions.
    nk = ng * m.top_k
    flat_e = expert_ids.reshape(g, nk)
    order = jnp.argsort(flat_e, axis=1, stable=True)            # (G, Nk)
    sorted_e = jnp.take_along_axis(flat_e, order, axis=1)
    idx = jnp.broadcast_to(jnp.arange(nk, dtype=jnp.int32)[None], (g, nk))
    is_new = jnp.concatenate(
        [jnp.ones((g, 1), bool), sorted_e[:, 1:] != sorted_e[:, :-1]], axis=1)
    group_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(is_new, idx, 0), axis=1)
    rank = idx - group_start                                     # pos in expert
    pos = jnp.zeros((g, nk), jnp.int32).at[
        jnp.broadcast_to(jnp.arange(g)[:, None], (g, nk)), order
    ].set(rank, mode="drop", unique_indices=True).reshape(g, ng, m.top_k)
    keep = pos < cap
    gate_vals = jnp.where(keep, gate_vals, 0.0)
    slot = jnp.where(keep, pos, cap)                                   # cap = trash

    token_ids = jnp.broadcast_to(jnp.arange(ng, dtype=jnp.int32)[None, :, None],
                                 (g, ng, m.top_k))
    g_iota = jnp.broadcast_to(jnp.arange(g)[:, None], (g, ng * m.top_k))
    gather_idx = jnp.full((g, m.n_experts, cap + 1), ng, dtype=jnp.int32)
    gather_idx = gather_idx.at[
        g_iota, expert_ids.reshape(g, -1), slot.reshape(g, -1)].set(
        token_ids.reshape(g, -1), mode="drop")
    gather_idx = gather_idx[:, :, :cap]                                # (G,E,cap)
    xg_pad = jnp.concatenate([xg, jnp.zeros((g, 1, d), xg.dtype)], axis=1)
    xe = jnp.take_along_axis(xg_pad[:, None], gather_idx[..., None],
                             axis=2)                                   # (G,E,cap,d)
    # NOTE (§Perf MoE it.3, REFUTED): pinning xe/ye to EP×data sharding here
    # forces reshard storms against the seq-sharded gather source — measured
    # t_m 88→122 s, t_x 40→154 s. XLA's replicated-but-local dispatch wins;
    # left unpinned deliberately.

    # expert compute (vmapped over E; sharded over model axis = EP)
    xe_t = xe.transpose(1, 0, 2, 3).reshape(m.n_experts, g * cap, d)
    if "experts" in consts:
        ye_t = jax.vmap(lambda p, c, xi: apply_mlp(cfg, p, c, xi, act="silu"))(
            params["experts"], consts["experts"], xe_t)
    else:
        ye_t = jax.vmap(lambda p, xi: apply_mlp(cfg, p, {}, xi, act="silu"))(
            params["experts"], xe_t)
    ye = ye_t.reshape(m.n_experts, g, cap, d).transpose(1, 0, 2, 3)    # (G,E,cap,d)

    # combine weights per slot
    w_slot = jnp.zeros((g, m.n_experts, cap + 1), jnp.float32)
    w_slot = w_slot.at[g_iota, expert_ids.reshape(g, -1),
                       slot.reshape(g, -1)].set(
        gate_vals.reshape(g, -1).astype(jnp.float32), mode="drop")
    ye = ye.astype(jnp.float32) * w_slot[:, :, :cap, None]

    # scatter back (per-expert partials -> all-reduce over model by GSPMD)
    yf = jnp.zeros((g, ng + 1, d), jnp.float32)
    e_iota = jnp.broadcast_to(jnp.arange(g)[:, None, None],
                              (g, m.n_experts, cap))
    yf = yf.at[e_iota, gather_idx].add(ye, mode="drop")
    y = yf[:, :ng].astype(x.dtype)

    if m.n_shared_experts:
        y = y + apply_mlp(cfg, params["shared"], consts.get("shared", {}), xg)

    # load-balancing aux loss (Switch): E * sum(frac_tokens * frac_probs).
    # scatter-add counts instead of a (N × E) one-hot (§Perf MoE it.2)
    frac_prob = probs.mean(axis=(0, 1))
    counts = jnp.zeros(m.n_experts, jnp.float32).at[
        expert_ids[..., 0].reshape(-1)].add(1.0, mode="drop")
    frac_tok = counts / (g * ng)
    aux = m.n_experts * jnp.sum(frac_prob * frac_tok)
    return y.reshape(bsz, seq, d), aux
