"""train_step / serve_step builders: loss, grad accumulation, remat, and the
jit/sharding glue. Arch-agnostic via the model registry API."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, TrainConfig
from repro.models.registry import ModelApi
from repro.optim.optimizers import Optimizer


def cross_entropy(logits, labels, vocab_size: int):
    """Mean next-token CE in fp32; padded vocab tail masked out."""
    lf = logits.astype(jnp.float32)
    if lf.shape[-1] > vocab_size:
        penalty = jnp.where(jnp.arange(lf.shape[-1]) < vocab_size, 0.0, -1e30)
        lf = lf + penalty
    logz = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def make_loss_fn(cfg: ModelConfig, api: ModelApi, remat: str = "none",
                 aux_coef: float = 0.01):
    def loss_fn(params, consts, batch):
        logits, aux = api.apply(cfg, params, consts, batch, remat=remat)
        toks = batch["tokens"]
        ce = cross_entropy(logits[:, :-1], toks[:, 1:], cfg.vocab_size)
        loss = ce + aux_coef * aux
        if "chaos_scale" in batch:
            # fault injection (repro.resilience): a NaN scale poisons the
            # loss through the real vjp so non-finite detection sees
            # genuine NaN gradients, not a synthetic flag. The key is
            # present every step of a chaos run (value 1.0 off-fault) so
            # the pytree structure — and the compiled program — is stable.
            loss = loss * jnp.mean(batch["chaos_scale"].astype(jnp.float32))
        return loss, {"ce": ce, "aux": aux}
    return loss_fn


def nonfinite_gate(loss, grads, new_state, old_state):
    """Skip-step gate: one fused isfinite reduction over loss + grads;
    when anything is non-finite, every leaf of ``new_state`` (a tuple of
    trees, e.g. (params, opt_state)) is replaced by its ``old_state``
    counterpart. Bit-exact identity when finite (``jnp.where`` on a true
    scalar predicate selects the new operand unchanged). Returns
    (gated_state, nonfinite) with ``nonfinite`` a 0/1 f32 metric."""
    good = jnp.isfinite(loss)
    for g in jax.tree.leaves(grads):
        if jnp.issubdtype(g.dtype, jnp.inexact):
            good = good & jnp.isfinite(g).all()
    gated = jax.tree.map(lambda n, o: jnp.where(good, n, o),
                         new_state, old_state)
    return gated, 1.0 - good.astype(jnp.float32)


def make_train_step(cfg: ModelConfig, api: ModelApi, optimizer: Optimizer,
                    *, remat: str = "none", grad_accum: int = 1,
                    aux_coef: float = 0.01, grad_specs=None):
    """Returns train_step(params, opt_state, consts, batch) ->
    (params, opt_state, metrics). With grad_accum > 1 the global batch is
    split into microbatches scanned sequentially (grads averaged) — the
    schedule point straggler mitigation and PP would hook into (DESIGN §7).

    ``grad_specs`` (a PartitionSpec pytree mirroring params — the fsdp
    param specs from ``dist.sharding.param_specs``) pins the gradient
    tree back to the sharded parameter layout before ``optimizer.update``:
    under fsdp this is what turns the backward's gradient all-reduce into
    reduce-scatter + sharded update (each device updates only its param
    shard) instead of all-reduce + replicated update."""
    from repro.dist.sharding import constrain

    if cfg.param.mode == "sltrain" and cfg.param.exec_mode == "quant":
        raise ValueError(
            "exec_mode='quant' is serve-only (int8 codes are not trainable) "
            "— train with dense/sparse/fused and calibrate afterwards "
            "(python -m repro.quant.calibrate)")

    loss_fn = make_loss_fn(cfg, api, remat, aux_coef)
    vg = jax.value_and_grad(loss_fn, has_aux=True)

    def pin(grads):
        if grad_specs is None:
            return grads
        return jax.tree.map(lambda g, s: constrain(g, *s), grads,
                            grad_specs)

    def train_step(params, opt_state, consts, batch):
        if grad_accum == 1:
            (loss, parts), grads = vg(params, consts, batch)
        else:
            def micro(carry, mb):
                acc, loss_acc, parts_acc = carry
                (l, pt), g = vg(params, consts, mb)
                return (jax.tree.map(jnp.add, acc, g), loss_acc + l,
                        jax.tree.map(jnp.add, parts_acc, pt)), None

            def split(leaf):
                b = leaf.shape[0]
                return leaf.reshape(grad_accum, b // grad_accum, *leaf.shape[1:])
            micro_batches = jax.tree.map(split, batch)
            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            parts0 = {"ce": jnp.float32(0.0), "aux": jnp.float32(0.0)}
            (grads, loss, parts), _ = jax.lax.scan(
                micro, (zeros, jnp.float32(0.0), parts0), micro_batches)
            grads = jax.tree.map(lambda g: g / grad_accum, grads)
            loss = loss / grad_accum
            # average the true ce/aux split like the loss — fabricating
            # aux=0 here hid every MoE router-aux signal under grad accum
            parts = jax.tree.map(lambda x: x / grad_accum, parts)
        grads = pin(grads)
        new_params, new_opt, stats = optimizer.update(grads, opt_state, params)
        # divergence guard (repro.resilience): a non-finite loss/grad must
        # never reach the weights — select the pre-step state instead and
        # report it so the trainer can escalate (skip → rollback)
        (new_params, new_opt), nonfinite = nonfinite_gate(
            loss, grads, (new_params, new_opt), (params, opt_state))
        metrics = {"loss": loss, **parts, **stats, "nonfinite": nonfinite}
        return new_params, new_opt, metrics

    return train_step


def make_serve_step(cfg: ModelConfig, api: ModelApi, *, greedy: bool = True,
                    temperature: float = 1.0):
    """serve_step(params, consts, tokens, cache, index, block_table, rng) ->
    (next_tokens (B,1), logits, new_cache). One batched decode step.

    ``index`` is a scalar (legacy shared offset) or a (B,) per-slot position
    vector; ``block_table`` (B, blocks_per_slot) switches the cache to the
    paged layout (serve/kv.py)."""
    def serve_step(params, consts, tokens, cache, index, block_table=None,
                   rng=None):
        if block_table is None:
            logits, new_cache = api.decode_step(cfg, params, consts, tokens,
                                                cache, index)
        else:
            logits, new_cache = api.decode_step(cfg, params, consts, tokens,
                                                cache, index,
                                                block_table=block_table)
        last = logits[:, -1, :cfg.vocab_size].astype(jnp.float32)
        if greedy:
            nxt = jnp.argmax(last, axis=-1).astype(jnp.int32)
        else:
            nxt = jax.random.categorical(rng, last / temperature).astype(jnp.int32)
        return nxt[:, None], logits, new_cache
    return serve_step


def make_prefill_step(cfg: ModelConfig, api: ModelApi, *, greedy: bool = True,
                      temperature: float = 1.0):
    """prefill_step(params, consts, tokens, cache, lengths, block_table,
    rng) -> (first_tokens (B,1), logits, new_cache).

    One jit'd call runs a whole batch of prompts (B, S) through the
    train-style forward, writes K/V for positions [0, S) and samples each
    slot's FIRST output token from logits[s, lengths[s]-1] — replacing
    O(prompt_len) per-token decode dispatches with O(1) per admitted batch.
    Rows are padded to a shared S; padding positions are never attended by
    valid queries (causal mask) and their pages are overwritten by decode
    before they first become visible.

    ``offsets`` (B,) int32 (paged caches only) switches to chunked SUFFIX
    prefill: row s holds the prompt tokens from position offsets[s] on
    (the shared-prefix length), ``lengths`` are SUFFIX lengths, and the
    forward attends the slot's resident prior pages in place — see
    models/lm.prefill_step."""
    def prefill_step(params, consts, tokens, cache, lengths, block_table=None,
                     rng=None, offsets=None):
        logits, new_cache = api.prefill_step(cfg, params, consts, tokens,
                                             cache, block_table=block_table,
                                             offsets=offsets)
        rows = jnp.arange(tokens.shape[0], dtype=jnp.int32)
        last_idx = jnp.clip(lengths - 1, 0, tokens.shape[1] - 1)
        last = logits[rows, last_idx, :cfg.vocab_size].astype(jnp.float32)
        if greedy:
            nxt = jnp.argmax(last, axis=-1).astype(jnp.int32)
        else:
            nxt = jax.random.categorical(rng, last / temperature).astype(jnp.int32)
        return nxt[:, None], logits, new_cache
    return prefill_step


def make_eval_step(cfg: ModelConfig, api: ModelApi):
    loss_fn = make_loss_fn(cfg, api)

    def eval_step(params, consts, batch):
        loss, parts = loss_fn(params, consts, batch)
        return {"loss": loss, "ppl": jnp.exp(parts["ce"]), **parts}
    return eval_step


def make_compressed_dp_step(cfg: ModelConfig, api: ModelApi,
                            optimizer: Optimizer, mesh, *,
                            pod_axis: str = "pod", block: int = 256,
                            aux_coef: float = 0.01, obs=None):
    """Hierarchical data-parallel train step with int8-compressed cross-pod
    gradient reduction (DESIGN §4: the pod axis is the slow DCI link).

    shard_map over the pod axis: each pod computes grads on its batch shard
    with full precision locally (pjit handles intra-pod sharding inside the
    body on real hardware; here the body is the whole per-pod step), then
    the pods exchange int8-quantized gradients — 4× less DCI wire than f32
    psum, exact local int32 summation of the gathered codes
    (dist/compression.py).

    Params/opt-state are replicated across pods (DP); the batch shards.

    ``obs`` (an ``obs.metrics.Registry``) threads through to
    :func:`repro.dist.compression.psum_tree`, recording the modeled wire
    bytes of every gradient reduction on ``dist.collective_bytes``
    (labeled by compression) — surfaced in the trainer's metrics JSONL.
    """
    from jax.sharding import PartitionSpec as P

    from repro.dist.compression import psum_tree

    loss_fn = make_loss_fn(cfg, api, "none", aux_coef)
    n_pods = mesh.shape[pod_axis]

    def body(params, opt_state, consts, batch):
        (loss, parts), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, consts, batch)
        grads = psum_tree(grads, pod_axis, compress=True, block=block,
                          obs=obs, n_participants=n_pods)
        grads = jax.tree.map(lambda g: g / n_pods, grads)
        loss = jax.lax.pmean(loss, pod_axis)
        new_params, new_opt, stats = optimizer.update(grads, opt_state,
                                                      params)
        # post-psum grads are identical on every pod, so the gate (and its
        # skip decision) is replicated — no pod diverges from the others
        (new_params, new_opt), nonfinite = nonfinite_gate(
            loss, grads, (new_params, new_opt), (params, opt_state))
        return new_params, new_opt, {"loss": loss, **stats,
                                     "nonfinite": nonfinite}

    rep = P()  # replicated across the pod axis

    def specs_like(tree, leading_batch=False):
        def spec(leaf):
            if leading_batch:
                return P(pod_axis, *([None] * (leaf.ndim - 1)))
            return P(*([None] * leaf.ndim))
        return jax.tree.map(spec, tree)

    def step(params, opt_state, consts, batch):
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(specs_like(params), specs_like(opt_state),
                      specs_like(consts), specs_like(batch, True)),
            out_specs=(specs_like(params), specs_like(opt_state),
                       {"loss": rep, "grad_norm": rep, "lr": rep,
                        "nonfinite": rep}),
            check_vma=False,
        )(params, opt_state, consts, batch)

    return step
