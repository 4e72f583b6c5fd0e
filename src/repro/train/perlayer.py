"""repro.train.perlayer — layer-wise backward with in-sweep optimizer
updates (the paper's "per-layer updates" memory path, §5.1 / Appendix F).

The global train step (``train/step.py``) materializes the FULL-model
gradient tree (f32 after clipping) before one ``optimizer.update`` — peak
grad+optimizer-transient HBM is O(P_trainable) no matter how lean the
parameterization is. This engine removes that term:

  1. **Forward once** over the stacked layer scan, saving only the
     per-layer boundary activations (``lm.forward_saving_boundaries``; the
     existing remat policies govern intra-layer residuals).
  2. **Norm sweep** (reverse): re-run one layer's vjp at a time, reduce its
     gradients to a squared-norm contribution immediately, and keep only
     the boundary cotangent. This recovers the exact global gradient norm
     the clip/stat needs *before any update* — the LOMO two-pass trick
     (PAPERS: Lv et al.); it trades one extra backward recompute for never
     holding two layers' grads at once.
  3. **Update sweep** (reverse): re-run each layer's vjp and immediately
     apply that layer's optimizer update through the per-layer slice API
     (``Optimizer.update_slice``, dispatching to the fused ``adam8bit``
     Pallas kernel when ``fused_opt`` — default when the model's
     ``exec_mode == "fused"`` — or the XLA reference otherwise) before the
     next layer's grads exist. Co-resident state is O(one layer) of grads
     + f32 transients instead of O(model).

Update order inside a step is head → layers (top to bottom) → embed; for
Adam-family optimizers this is value-identical to the global step because
no layer's update feeds another layer's gradient within the step (all vjps
re-run from the pre-step params saved in the forward), and the clip scale
comes from the dedicated norm sweep. Checkpoints stay layout-identical to
``update_mode="global"``: params and optimizer state trees are untouched —
only the order in which their leaves are written differs.

Leaves whose optimizer state cannot be sliced along the layer axis
(``stack_state`` returns None: 8-bit quantization blocks straddling layer
boundaries, GaLore projected leaves) take a deferred path — their full
stacked gradient is accumulated through the sweep (as scan outputs) and
updated once at the end, exactly like global mode. These are the small
leaves (norms, odd-sized supports); the big matrices slice.

Tied embeddings are supported without widening the sweep's working set:
the head vjp closes the tied embedding over as a CONSTANT (so the
boundary cotangent is the only thing carried through the layers), and
the head's embed cotangent is recomputed by a dedicated embed-only vjp
at the embed step of each pass — one extra head recompute instead of
holding a V × d f32 cotangent across every layer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import remat_wrap
from repro.models.registry import ModelApi
from repro.optim.optimizers import Optimizer
from repro.train.step import cross_entropy


def _pk(path):
    """Tree path -> tuple of plain str dict keys."""
    out = []
    for k in path:
        key = getattr(k, "key", None)
        out.append(str(key) if key is not None else str(k))
    return tuple(out)


def _sq(tree):
    return sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
               for g in jax.tree.leaves(tree))


def make_perlayer_train_step(cfg: ModelConfig, api: ModelApi,
                             optimizer: Optimizer, *, remat: str = "none",
                             grad_accum: int = 1, aux_coef: float = 0.01,
                             fused_opt: bool | None = None,
                             grad_specs=None):
    """Returns train_step(params, opt_state, consts, batch) ->
    (params, opt_state, metrics) with per-layer in-sweep updates.

    ``fused_opt`` routes sliced updates through
    ``optimizer.update_slice_fused`` (the Pallas adam8bit kernel) when the
    optimizer provides it; default follows the model's exec mode
    (``cfg.param.exec_mode == "fused"``).

    ``grad_accum > 1`` runs the IN-SWEEP microbatch accumulator: the batch
    splits into microbatches, the forward saves boundaries per microbatch
    (one extra leading axis on the saves), and both reverse sweeps carry
    the STACK of boundary cotangents — at each layer an inner scan re-runs
    that layer's vjp once per microbatch and sums the layer-sized gradient
    before it is reduced to a norm (pass 1) or consumed by the update
    (pass 2). The full gradient tree is never materialized: co-resident
    grads stay O(P_layer), exactly as at grad_accum == 1, and the result
    is token-for-token the global + grad_accum step (sum of per-microbatch
    grads / n_mb, clip norm of the averaged tree).

    ``grad_specs`` (PartitionSpec pytree mirroring params, usually the
    fsdp param specs) pins each layer's sliced gradient to the sliced
    param layout (the stacked leaf's spec minus its layer dim) before the
    in-sweep update — under fsdp the update-sweep's per-layer grads
    reduce-scatter instead of all-reducing, and each device updates only
    its shard. Head/embed whole-leaf grads pin the same way."""
    plapi = api.perlayer
    if plapi is None:
        raise ValueError(f"update_mode='per_layer' needs the per-layer "
                         f"model API; family {cfg.family!r} does not "
                         f"expose one")
    for fn in ("prepare", "update_slice", "leaf_state", "with_leaf_state",
               "stack_state", "unstack_state", "finish"):
        if getattr(optimizer, fn) is None:
            raise ValueError(f"optimizer lacks the per-layer slice API "
                             f"({fn}); update_mode='per_layer' supports "
                             f"adamw, adam8bit and galore_adamw")
    if fused_opt is None:
        fused_opt = cfg.param.exec_mode == "fused"
    upd = optimizer.update_slice
    if fused_opt and optimizer.update_slice_fused is not None:
        upd = optimizer.update_slice_fused
    aux_ct = jnp.float32(aux_coef)
    tied = cfg.tie_embeddings
    n_mb = grad_accum

    from repro.dist.sharding import constrain

    def _spec_of(tree_path):
        """grad spec for a full tree path, or None."""
        if grad_specs is None:
            return None
        node = grad_specs
        for k in tree_path:
            if not isinstance(node, dict) or k not in node:
                return None
            node = node[k]
        return node if isinstance(node, tuple) else None

    def pin_full(g, tree_path):
        s = _spec_of(tree_path)
        return constrain(g, *s) if s is not None else g

    def head_params_of(params):
        """Only the UNTIED head leaves — the tied embedding enters
        head_ce as a separate argument so the sweep can treat it as a
        constant (see the tied-embeddings note in the module docstring)."""
        hp = {"ln_f": params["ln_f"]}
        if not tied:
            hp["lm_head"] = params["lm_head"]
        return hp

    def head_ce(hp, emb, h_top, tokens, scale=None):
        full = dict(hp)
        if tied:
            full["embed"] = emb
        logits = plapi.head(cfg, full, h_top)
        ce = cross_entropy(logits[:, :-1], tokens[:, 1:], cfg.vocab_size)
        # chaos poison (repro.resilience): a NaN scale flows through the
        # head vjp into every boundary cotangent, so BOTH sweeps see
        # genuinely non-finite gradients (and gnorm goes NaN with them)
        return ce if scale is None else ce * scale

    def stack_fns(group):
        """(layer_fn, params_key) for one stacked group."""
        seg = plapi.period if group == "layers" else plapi.dense

        def factory(c_i):
            return remat_wrap(lambda p, x: seg(cfg, p, c_i, x), remat)
        return factory

    def sweep(group, params, consts, bxs, dh, ctx, state):
        """Reverse-scan one stacked group.

        ctx/state None  → norm sweep: returns (dh_bottom, sq_norm_sum).
        ctx/state given → update sweep: applies sliced updates in-scan,
        defers non-sliceable leaves; returns
        (dh_bottom, new_group_params, new_state)."""
        p_sub = params[group]
        c_sub = consts.get(group, {})
        factory = stack_fns(group)
        flat, treedef = jax.tree_util.tree_flatten_with_path(p_sub)
        paths = [_pk(p) for p, _ in flat]
        leaves = [l for _, l in flat]
        n = leaves[0].shape[0]
        norm_pass = ctx is None

        g_specs = None
        if grad_specs is not None and group in grad_specs:
            sflat = jax.tree_util.tree_flatten_with_path(
                grad_specs[group], is_leaf=lambda x: isinstance(x, tuple))[0]
            by = {_pk(p): s for p, s in sflat}
            g_specs = [by.get(p) for p in paths]

        stacked_ls, sliceable = [], []
        if not norm_pass:
            for path, leaf in zip(paths, leaves):
                ls = optimizer.leaf_state(state, (group,) + path)
                st = optimizer.stack_state(ls, leaf, n)
                sliceable.append(st is not None)
                if st is not None:
                    stacked_ls.append(st)
        xs = (p_sub, c_sub, bxs, tuple(stacked_ls))

        def body(carry, xs_i):
            p_i, c_i, x_i, ls_i = xs_i
            f = factory(c_i)
            if norm_pass:
                dh_c, acc = carry
            else:
                dh_c = carry
            if n_mb == 1:
                _, pull = jax.vjp(f, p_i, x_i)
                dp, dx = pull((dh_c, aux_ct))
            else:
                # in-sweep microbatch accumulation: x_i / dh_c carry a
                # leading (n_mb, ...) axis; re-run THIS layer's vjp once
                # per microbatch and sum the layer-sized gradient in f32 —
                # co-resident grads stay O(P_layer), never the full tree
                def mb_body(g_acc, mb):
                    x_m, dh_m = mb
                    _, pull_m = jax.vjp(f, p_i, x_m)
                    dp_m, dx_m = pull_m((dh_m, aux_ct))
                    g_acc = jax.tree.map(
                        lambda a, g: a + g.astype(jnp.float32), g_acc, dp_m)
                    return g_acc, dx_m
                zeros = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), p_i)
                dp, dx = jax.lax.scan(mb_body, zeros, (x_i, dh_c))
                dp = jax.tree.map(lambda g: g / n_mb, dp)
            if norm_pass:
                return (dx, acc + _sq(dp)), None
            p_leaves = treedef.flatten_up_to(p_i)
            g_leaves = treedef.flatten_up_to(dp)
            if g_specs is not None:
                # pin the sliced grad to the sliced param layout (stacked
                # spec minus the layer dim): fsdp reduce-scatter point
                g_leaves = [
                    constrain(g, *s[1:]) if s is not None else g
                    for g, s in zip(g_leaves, g_specs)]
            new_p, new_ls, res_g, k = [], [], [], 0
            for j, path in enumerate(paths):
                if sliceable[j]:
                    np_, nls = upd(ctx, p_leaves[j], g_leaves[j], ls_i[k],
                                   full_ndim=leaves[j].ndim)
                    new_p.append(np_)
                    new_ls.append(nls)
                    k += 1
                else:
                    new_p.append(p_leaves[j])
                    res_g.append(g_leaves[j].astype(jnp.float32))
            return dx, (tuple(new_p), tuple(new_ls), tuple(res_g))

        if norm_pass:
            (dh, acc), _ = jax.lax.scan(body, (dh, jnp.float32(0.0)), xs,
                                        reverse=True)
            return dh, acc

        dh, (new_p, new_ls, res_g) = jax.lax.scan(body, dh, xs, reverse=True)
        # write back: scan stacks ys at their original layer index, so the
        # sliceable outputs already ARE the updated stacked leaves
        out_leaves, k, r = [], 0, 0
        for j, path in enumerate(paths):
            full = (group,) + path
            if sliceable[j]:
                out_leaves.append(new_p[j])
                ls = optimizer.unstack_state(new_ls[k], leaves[j], n)
                state = optimizer.with_leaf_state(state, full, ls)
                k += 1
            else:
                # deferred: the stacked gradient was accumulated through
                # the sweep; update the whole leaf exactly like global mode
                ls = optimizer.leaf_state(state, full)
                np_, nls = upd_full(ctx, leaves[j], res_g[r], ls)
                out_leaves.append(np_)
                state = optimizer.with_leaf_state(state, full, nls)
                r += 1
        return dh, treedef.unflatten(out_leaves), state

    def upd_full(ctx, p, g, ls):
        """Whole-leaf update (head / embed / deferred leaves): a whole
        leaf is its own 'slice', through the same dispatch as the sweep —
        under ``fused_opt`` the Pallas kernel handles these too (its
        wrapper pads arbitrary shapes to whole q-blocks), which is what
        the memory model's zero-HBM-transient claim assumes. GaLore's
        projected leaves only ever land here and galore has no fused
        variant, so they always take the reference path."""
        return upd(ctx, p, g, ls)

    def train_step(params, opt_state, consts, batch):
        tokens = batch["tokens"]
        patches = batch.get("patches")
        chaos_scale = None
        if "chaos_scale" in batch:
            chaos_scale = jnp.mean(batch["chaos_scale"].astype(jnp.float32))

        # ---- forward, saving per-layer boundaries -----------------------
        # grad_accum == 1: one forward, saves are (n_layers, B, S, d).
        # grad_accum > 1: the batch splits into n_mb microbatches scanned
        # sequentially — saves gain a leading mb axis which is then moved
        # INSIDE the layer axis ((n_layers, n_mb, B/n_mb, S, d)) so the
        # reverse sweeps still scan layers on the leading dim.
        if n_mb == 1:
            bnd = plapi.forward_boundaries(cfg, params, consts, batch,
                                           remat=remat)
            tokens_mb = patches_mb = None
        else:
            def split(leaf):
                b = leaf.shape[0]
                return leaf.reshape(n_mb, b // n_mb, *leaf.shape[1:])
            mbs = jax.tree.map(split, batch)
            tokens_mb = mbs["tokens"]
            patches_mb = mbs.get("patches")

            def fwd(_, mb):
                return 0, plapi.forward_boundaries(cfg, params, consts, mb,
                                                   remat=remat)
            _, bnd = jax.lax.scan(fwd, 0, mbs)
            bnd = dict(bnd)
            for k in ("xs", "dense_xs"):
                if bnd.get(k) is not None:
                    bnd[k] = jax.tree.map(lambda a: jnp.moveaxis(a, 0, 1),
                                          bnd[k])
        aux_total = jnp.float32(0.0)
        if bnd["aux_dense"] is not None:
            aux_total = aux_total + bnd["aux_dense"].sum()
        aux_total = aux_total + bnd["aux"].sum()
        if n_mb > 1:
            aux_total = aux_total / n_mb   # mean over microbatches, like
            # the global microbatch scan's parts averaging

        # tied: embed enters the head as a closed-over constant — the
        # head vjp then yields only untied-leaf + boundary cotangents,
        # and the embed's head cotangent is recomputed at the embed step
        # (head_embed_cotangent) instead of being carried down the sweep
        emb0 = params["embed"] if tied else None
        hp = head_params_of(params)

        if n_mb == 1:
            ce, head_pull = jax.vjp(
                lambda hp_, h_: head_ce(hp_, emb0, h_, tokens, chaos_scale),
                hp, bnd["h_top"])

            def head_grads():
                d_head, dh = head_pull(jnp.float32(1.0))
                return d_head, dh
        else:
            def head_grads():
                """Per-microbatch head vjp, summed head-leaf grads / n_mb
                and the STACKED boundary cotangent the sweeps carry."""
                def hb(carry, mb):
                    h_m, t_m = mb
                    g_acc, ce_acc = carry
                    ce_m, pull = jax.vjp(
                        lambda hp_, h_: head_ce(hp_, emb0, h_, t_m,
                                                chaos_scale), hp, h_m)
                    dhp_m, dh_m = pull(jnp.float32(1.0))
                    g_acc = jax.tree.map(
                        lambda a, g: a + g.astype(jnp.float32), g_acc,
                        dhp_m)
                    return (g_acc, ce_acc + ce_m), dh_m
                zeros = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), hp)
                (g, ce_sum), dh = jax.lax.scan(
                    hb, (zeros, jnp.float32(0.0)),
                    (bnd["h_top"], tokens_mb))
                return (jax.tree.map(lambda a: a / n_mb, g), dh,
                        ce_sum / n_mb)
            _, _, ce = head_grads()
        loss = ce + aux_coef * aux_total

        def head_embed_cotangent():
            if n_mb == 1:
                _, pull = jax.vjp(
                    lambda e: head_ce(hp, e, bnd["h_top"], tokens,
                                      chaos_scale),
                    params["embed"])
                return pull(jnp.float32(1.0))[0]

            def hb(acc, mb):
                h_m, t_m = mb
                _, pull = jax.vjp(lambda e: head_ce(hp, e, h_m, t_m,
                                                    chaos_scale),
                                  params["embed"])
                return acc + pull(jnp.float32(1.0))[0].astype(jnp.float32), None
            zeros = jnp.zeros(params["embed"].shape, jnp.float32)
            acc, _ = jax.lax.scan(hb, zeros, (bnd["h_top"], tokens_mb))
            return acc / n_mb

        def embed_grad(dh_bottom):
            """Embedding gradient from the bottom boundary cotangent(s)."""
            if n_mb == 1:
                _, pull = jax.vjp(
                    lambda ep: plapi.embed(cfg, ep, tokens, patches),
                    {"embed": params["embed"]})
                return pull(dh_bottom)[0]["embed"]

            def eb(acc, mb):
                if patches_mb is None:
                    t_m, dh_m = mb
                    p_m = None
                else:
                    t_m, p_m, dh_m = mb
                _, pull = jax.vjp(
                    lambda ep: plapi.embed(cfg, ep, t_m, p_m),
                    {"embed": params["embed"]})
                g = pull(dh_m)[0]["embed"].astype(jnp.float32)
                return acc + g, None
            zeros = jnp.zeros(params["embed"].shape, jnp.float32)
            xs_mb = ((tokens_mb, dh_bottom) if patches_mb is None
                     else (tokens_mb, patches_mb, dh_bottom))
            acc, _ = jax.lax.scan(eb, zeros, xs_mb)
            return acc / n_mb

        # ---- pass 1: exact global grad norm (LOMO-style norm sweep) -----
        hg = head_grads()
        d_head, dh = hg[0], hg[1]
        total_sq = _sq(d_head)
        dh1 = dh
        if "layers" in params:
            dh1, acc = sweep("layers", params, consts, bnd["xs"], dh1,
                             None, None)
            total_sq = total_sq + acc
        if "dense_layers" in params:
            dh1, acc = sweep("dense_layers", params, consts,
                             bnd["dense_xs"], dh1, None, None)
            total_sq = total_sq + acc
        d_embed = embed_grad(dh1)
        if tied:
            d_embed = d_embed.astype(jnp.float32) + head_embed_cotangent()
        total_sq = total_sq + _sq(d_embed)
        gnorm = jnp.sqrt(total_sq)

        # ---- pass 2: update sweep (grads exist one layer at a time) -----
        ctx, stats = optimizer.prepare(opt_state, gnorm)
        state = opt_state
        new_params = dict(params)

        hg = head_grads()   # recompute: don't hold head grads across pass 1
        d_head, dh = hg[0], hg[1]
        for key, g in d_head.items():
            g = pin_full(g, (key,))
            ls = optimizer.leaf_state(state, (key,))
            np_, nls = upd_full(ctx, params[key], g, ls)
            new_params[key] = np_
            state = optimizer.with_leaf_state(state, (key,), nls)

        if "layers" in params:
            dh, new_params["layers"], state = sweep(
                "layers", params, consts, bnd["xs"], dh, ctx, state)
        if "dense_layers" in params:
            dh, new_params["dense_layers"], state = sweep(
                "dense_layers", params, consts, bnd["dense_xs"], dh, ctx,
                state)

        d_embed = embed_grad(dh)
        if tied:
            d_embed = d_embed.astype(jnp.float32) + head_embed_cotangent()
        d_embed = pin_full(d_embed, ("embed",))
        ls = optimizer.leaf_state(state, ("embed",))
        np_, nls = upd_full(ctx, params["embed"], d_embed, ls)
        new_params["embed"] = np_
        state = optimizer.with_leaf_state(state, ("embed",), nls)

        state = optimizer.finish(state, ctx)
        # divergence guard (repro.resilience): gnorm comes from the norm
        # sweep's exact global reduction, so it is non-finite iff ANY
        # layer's gradient is — together with the loss that is the whole
        # detection, two scalar isfinite ops. The in-sweep updates already
        # happened, so select every leaf back to its pre-step value.
        good = jnp.isfinite(loss) & jnp.isfinite(gnorm)
        sel = lambda n, o: jnp.where(good, n, o)                 # noqa: E731
        new_params = jax.tree.map(sel, new_params, params)
        state = jax.tree.map(sel, state, opt_state)
        metrics = {"loss": loss, "ce": ce, "aux": aux_total, **stats,
                   "nonfinite": 1.0 - good.astype(jnp.float32)}
        return new_params, state, metrics

    return train_step
