"""End-to-end + unit tests for ``update_mode="per_layer"`` (ISSUE 4):
repro.train.perlayer layer-wise backward with in-sweep optimizer updates,
the Optimizer per-layer slice API, the Appendix-F memory estimator
extension, and the grad-accum metrics bugfix."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import OptimizerConfig
from repro.core import memory
from repro.data.pipeline import SyntheticC4
from repro.models import registry
from repro.optim import optimizers
from repro.train import perlayer, step as step_lib


def _smoke_cfg(exec_mode="dense", arch="llama_60m"):
    base = registry.get_smoke_config(arch)
    return dataclasses.replace(
        base, dtype="float32",
        param=dataclasses.replace(base.param, mode="sltrain",
                                  exec_mode=exec_mode))


def _run_training(cfg, steps, *, update_mode, opt_name="adamw",
                  fused_opt=None, remat="none"):
    api = registry.get_api(cfg)
    params, consts = api.init(cfg, jax.random.PRNGKey(42), seed=42)
    opt = optimizers.make(OptimizerConfig(name=opt_name, lr=1e-3,
                                          warmup_steps=2, total_steps=steps))
    opt_state = opt.init(params)
    if update_mode == "per_layer":
        fn = jax.jit(perlayer.make_perlayer_train_step(
            cfg, api, opt, remat=remat, fused_opt=fused_opt))
    else:
        fn = jax.jit(step_lib.make_train_step(cfg, api, opt, remat=remat))
    data = SyntheticC4(cfg.vocab_size, 32, 4, seed=0)
    losses, gnorms = [], []
    for _ in range(steps):
        batch = {k: jnp.asarray(v) for k, v in data.next_batch().items()}
        params, opt_state, metrics = fn(params, opt_state, consts, batch)
        losses.append(float(metrics["loss"]))
        gnorms.append(float(metrics["grad_norm"]))
    return np.asarray(losses), np.asarray(gnorms), (params, opt_state)


# ---------------------------------------------------------------------------
# Acceptance: 20-step token-for-token parity vs the global update
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("exec_mode", ["dense", "fused"])
def test_perlayer_matches_global_adamw(exec_mode):
    """Same seed, same data, 20 steps, dense AND fused exec: the per-layer
    sweep (per-layer vjp grads, LOMO-style two-pass clip, in-sweep slice
    updates) must track the monolithic value_and_grad + global update to
    float-noise — every step."""
    steps = 20
    cfg = _smoke_cfg(exec_mode)
    loss_g, gn_g, _ = _run_training(cfg, steps, update_mode="global")
    loss_p, gn_p, _ = _run_training(cfg, steps, update_mode="per_layer")
    np.testing.assert_allclose(loss_p, loss_g, rtol=0, atol=2e-5)
    np.testing.assert_allclose(gn_p, gn_g, rtol=1e-5, atol=0)


def test_perlayer_matches_global_adam8bit():
    """Quantized state slices along the layer axis (whole q-blocks per
    layer) must be bitwise-equivalent to the global 8-bit update; the
    misaligned leaves (norms, odd supports) take the deferred path and
    must also agree."""
    steps = 8
    cfg = _smoke_cfg("dense")
    loss_g, _, (pg, sg) = _run_training(cfg, steps, update_mode="global",
                                        opt_name="adam8bit")
    loss_p, _, (pp, sp) = _run_training(cfg, steps, update_mode="per_layer",
                                        opt_name="adam8bit")
    np.testing.assert_allclose(loss_p, loss_g, rtol=0, atol=2e-5)
    # end-state parity: params and quantized optimizer state trees agree
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32),
        rtol=1e-4, atol=1e-5), pg, pp)
    assert jax.tree.structure(sg) == jax.tree.structure(sp)


def test_perlayer_fused_kernel_dispatch_matches_global():
    """Under exec_mode="fused", sliced adam8bit updates route through the
    Pallas kernel (the default fused_opt wiring); after the ISSUE-4 scalar
    fix the kernel tracks the XLA reference to ulp, so parity with the
    global (XLA) update must hold."""
    steps = 6
    cfg = _smoke_cfg("fused")
    loss_g, _, _ = _run_training(cfg, steps, update_mode="global",
                                 opt_name="adam8bit")
    loss_p, _, _ = _run_training(cfg, steps, update_mode="per_layer",
                                 opt_name="adam8bit")  # fused_opt defaults on
    np.testing.assert_allclose(loss_p, loss_g, rtol=0, atol=2e-5)


def test_perlayer_moe_dense_prefix_and_aux():
    """MoE coverage: first-k-dense prefix sweeps through the dense stack,
    router aux flows into loss/metrics identically to global mode."""
    steps = 4
    cfg = _smoke_cfg(arch="deepseek_moe_16b")
    loss_g, gn_g, _ = _run_training(cfg, steps, update_mode="global")
    loss_p, gn_p, _ = _run_training(cfg, steps, update_mode="per_layer")
    np.testing.assert_allclose(loss_p, loss_g, rtol=0, atol=3e-5)
    np.testing.assert_allclose(gn_p, gn_g, rtol=2e-5, atol=0)


@pytest.mark.parametrize("opt_name", ["adamw", "adam8bit"])
def test_perlayer_tied_embeddings_fold_head_cotangent(opt_name):
    """Tied configs close the embedding over as a constant in the head
    vjp and recompute the unembed's embed-cotangent at the embed step of
    each pass (instead of carrying a V x d f32 cotangent down the sweep)
    — the fold must still be value-identical to global autodiff
    accumulation: losses AND grad norms track the global step."""
    steps = 3
    cfg = dataclasses.replace(_smoke_cfg("dense"), tie_embeddings=True)
    loss_g, gn_g, _ = _run_training(cfg, steps, update_mode="global",
                                    opt_name=opt_name)
    loss_p, gn_p, _ = _run_training(cfg, steps, update_mode="per_layer",
                                    opt_name=opt_name)
    np.testing.assert_allclose(loss_p, loss_g, rtol=0, atol=2e-5)
    # the grad norm folds the embed cotangent too (norm sweep recompute)
    np.testing.assert_allclose(gn_p, gn_g, rtol=2e-5, atol=0)


def test_perlayer_galore_runs_and_tracks_global():
    steps = 4
    cfg = _smoke_cfg("dense")
    loss_g, _, _ = _run_training(cfg, steps, update_mode="global",
                                 opt_name="galore_adamw")
    loss_p, _, _ = _run_training(cfg, steps, update_mode="per_layer",
                                 opt_name="galore_adamw")
    np.testing.assert_allclose(loss_p, loss_g, rtol=0, atol=2e-5)


def test_perlayer_rejects_nonlm():
    opt = optimizers.make(OptimizerConfig())
    xl = registry.get_smoke_config("xlstm_350m")
    with pytest.raises(ValueError, match="per-layer"):
        perlayer.make_perlayer_train_step(
            xl, registry.get_api(xl), opt)


@pytest.mark.parametrize("exec_mode", ["dense", "fused"])
def test_perlayer_grad_accum_matches_global_grad_accum(exec_mode):
    """ISSUE 8 acceptance: 20-step per_layer + grad_accum=2 must be
    token-for-token equal to global + grad_accum=2 (dense AND fused) —
    the in-sweep microbatch accumulator reproduces sum-then-divide grads
    and the clip norm of the averaged tree without ever materializing
    the full gradient tree."""
    steps = 20
    cfg = _smoke_cfg(exec_mode)
    api = registry.get_api(cfg)
    opt_cfg = OptimizerConfig(name="adamw", lr=1e-3, warmup_steps=2,
                              total_steps=steps)
    data_g = SyntheticC4(cfg.vocab_size, 32, 4, seed=0)
    data_p = SyntheticC4(cfg.vocab_size, 32, 4, seed=0)

    opt = optimizers.make(opt_cfg)
    fn_g = jax.jit(step_lib.make_train_step(cfg, api, opt, grad_accum=2))
    fn_p = jax.jit(perlayer.make_perlayer_train_step(cfg, api, opt,
                                                     grad_accum=2))
    pg, cg = api.init(cfg, jax.random.PRNGKey(42), seed=42)
    pp, cp = api.init(cfg, jax.random.PRNGKey(42), seed=42)
    sg, sp = opt.init(pg), opt.init(pp)
    loss_g, loss_p, gn_g, gn_p = [], [], [], []
    for _ in range(steps):
        bg = {k: jnp.asarray(v) for k, v in data_g.next_batch().items()}
        bp = {k: jnp.asarray(v) for k, v in data_p.next_batch().items()}
        pg, sg, mg = fn_g(pg, sg, cg, bg)
        pp, sp, mp = fn_p(pp, sp, cp, bp)
        loss_g.append(float(mg["loss"]))
        loss_p.append(float(mp["loss"]))
        gn_g.append(float(mg["grad_norm"]))
        gn_p.append(float(mp["grad_norm"]))
    np.testing.assert_allclose(loss_p, loss_g, rtol=0, atol=2e-5)
    np.testing.assert_allclose(gn_p, gn_g, rtol=2e-5, atol=0)


def test_perlayer_grad_accum_tied_and_moe():
    """grad_accum=2 through the tied-embedding head fold and the MoE
    dense-prefix + router-aux paths (the stacked-cotangent sweeps)."""
    for arch, tie in (("llama_60m", True), ("deepseek_moe_16b", False)):
        cfg = _smoke_cfg(arch=arch)
        if tie:
            cfg = dataclasses.replace(cfg, tie_embeddings=True)
        api = registry.get_api(cfg)
        opt = optimizers.make(OptimizerConfig(name="adamw", lr=1e-3,
                                              warmup_steps=2, total_steps=4))
        fn_g = jax.jit(step_lib.make_train_step(cfg, api, opt, grad_accum=2))
        fn_p = jax.jit(perlayer.make_perlayer_train_step(cfg, api, opt,
                                                         grad_accum=2))
        params, consts = api.init(cfg, jax.random.PRNGKey(1), seed=1)
        st = opt.init(params)
        data = SyntheticC4(cfg.vocab_size, 32, 4, seed=3)
        batch = {k: jnp.asarray(v) for k, v in data.next_batch().items()}
        _, _, mg = fn_g(params, st, consts, batch)
        _, _, mp = fn_p(params, st, consts, batch)
        np.testing.assert_allclose(float(mp["loss"]), float(mg["loss"]),
                                   rtol=0, atol=3e-5)
        np.testing.assert_allclose(float(mp["grad_norm"]),
                                   float(mg["grad_norm"]), rtol=2e-5)


# ---------------------------------------------------------------------------
# Unit: Optimizer per-layer slice API on stacked params
# ---------------------------------------------------------------------------

def _stacked_tree(key, n=4):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "layers": {
            # per-layer flat size 8*32=256: whole q-blocks -> sliceable
            "w": jax.random.normal(k1, (n, 8, 32), jnp.float32),
            # per-layer size 24: straddles q-blocks -> deferred path
            "b": jax.random.normal(k2, (n, 24), jnp.float32),
        },
        "out": jax.random.normal(k3, (16, 16), jnp.float32),
    }


@pytest.mark.parametrize("name", ["adamw", "adam8bit", "galore_adamw"])
def test_update_slice_api_matches_global_update(name):
    """Driving prepare/stack_state/update_slice/finish by hand — slicing
    layer by layer like the sweep does — must reproduce optimizer.update
    exactly on a stacked tree, for every optimizer."""
    oc = OptimizerConfig(name=name, lr=0.01, warmup_steps=2, total_steps=10,
                         weight_decay=0.01, galore_rank=4)
    opt = optimizers.make(oc)
    params = _stacked_tree(jax.random.PRNGKey(0))
    grads = _stacked_tree(jax.random.PRNGKey(1))
    state = opt.init(params)

    ref_p, ref_s, ref_stats = opt.update(grads, state, params)

    n = 4
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in jax.tree.leaves(grads)))
    ctx, stats = opt.prepare(state, gnorm)
    new_p = {"layers": {}, "out": None}
    st = state
    for path, leaf, g in [
            (("layers", "w"), params["layers"]["w"], grads["layers"]["w"]),
            (("layers", "b"), params["layers"]["b"], grads["layers"]["b"]),
            (("out",), params["out"], grads["out"])]:
        ls = opt.leaf_state(st, path)
        stacked = len(path) == 2
        sliced = opt.stack_state(ls, leaf, n) if stacked else None
        if sliced is not None:
            ps, ss = [], []
            for i in range(n):
                ls_i = jax.tree.map(lambda l: l[i], sliced)
                np_, nls = opt.update_slice(ctx, leaf[i], g[i], ls_i,
                                            full_ndim=leaf.ndim)
                ps.append(np_)
                ss.append(nls)
            new_leaf = jnp.stack(ps)
            new_ls = opt.unstack_state(
                jax.tree.map(lambda *xs: jnp.stack(xs), *ss), leaf, n)
        else:
            new_leaf, new_ls = opt.update_slice(ctx, leaf, g, ls)
        st = opt.with_leaf_state(st, path, new_ls)
        if len(path) == 2:
            new_p["layers"][path[1]] = new_leaf
        else:
            new_p["out"] = new_leaf
    st = opt.finish(st, ctx)

    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32),
        rtol=1e-6, atol=1e-7), ref_p, new_p)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32),
        rtol=1e-6, atol=1e-7), ref_s, st)
    assert float(stats["grad_norm"]) == pytest.approx(
        float(ref_stats["grad_norm"]))


def test_adam8bit_stack_state_alignment_rules():
    oc = OptimizerConfig(name="adam8bit")
    opt = optimizers.make(oc)
    p_ok = jnp.zeros((4, 8, 32))      # 256/layer: aligned
    p_bad = jnp.zeros((4, 24))        # 24/layer: straddles blocks
    st = opt.init({"a": p_ok, "b": p_bad})
    ok = opt.stack_state(opt.leaf_state(st, ("a",)), p_ok, 4)
    assert ok is not None
    assert ok["mu"]["codes"].shape == (4, 1, 256)
    assert opt.stack_state(opt.leaf_state(st, ("b",)), p_bad, 4) is None


# ---------------------------------------------------------------------------
# Memory estimator: Appendix-F gradient + transient residency, the 73%
# ---------------------------------------------------------------------------

def test_training_estimate_perlayer_shrinks_residency():
    cfg = dict(memory.PAPER_LLAMA["7b"])
    rank = cfg.pop("rank")
    inv = memory.llama_inventory(**cfg)
    kw = dict(optimizer="adam8bit", rank=rank, delta=0.05, index_bytes=4)
    g = memory.training_estimate(inv, "sltrain", update_mode="global", **kw)
    p = memory.training_estimate(inv, "sltrain", update_mode="per_layer",
                                 **kw)
    # O(P_trainable) -> O(P_layer-ish): the biggest update group at 7B is
    # the (untied) embedding, ~4% of the trainable count
    assert p.resident_count < 0.05 * g.resident_count
    assert (p.grad_bytes + p.transient_bytes) \
        < 0.05 * (g.grad_bytes + g.transient_bytes)
    # params + optimizer state are residency-invariant (layout-identical)
    assert p.param_bytes == g.param_bytes
    assert p.optim_bytes == g.optim_bytes


def test_memory_reproduces_paper_73_percent_7b():
    """sltrain + adam8bit(fused) + per_layer vs full-rank AdamW on LLaMA 7B
    must reproduce the paper's headline 'up to 73%' memory reduction:
    73.6% with the framework's int32 on-device indices, 71.2% with the
    paper's int64 accounting."""
    r32 = memory.paper_f_reduction("7b", index_bytes=4)
    r64 = memory.paper_f_reduction("7b", index_bytes=8)
    assert r32["reduction"] == pytest.approx(0.736, abs=0.01)
    assert r64["reduction"] == pytest.approx(0.712, abs=0.01)
    assert r32["resident_ratio"] < 0.05


# ---------------------------------------------------------------------------
# Boundary-activation sharding specs
# ---------------------------------------------------------------------------

def test_boundary_save_specs():
    from jax.sharding import PartitionSpec as P

    from repro.dist import sharding as shl

    class _Mesh:  # spec engine only reads axis_names/shape (test_dist idiom)
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    mesh = _Mesh()
    xs = jnp.zeros((8, 32, 64, 512))           # (L, B, S, d)
    spec = shl.boundary_save_specs(xs, mesh)
    assert spec == P(None, ("data",), None, None)
    spec_sp = shl.boundary_save_specs(xs, mesh, seq_sharded=True)
    assert spec_sp == P(None, ("data",), ("model",), None)
    # off-mesh constrain degrades to a no-op
    y = shl.constrain_boundary(jnp.zeros((2, 4, 8)), seq_sharded=True)
    assert y.shape == (2, 4, 8)


# ---------------------------------------------------------------------------
# Satellite: grad-accum metrics keep the true ce/aux split
# ---------------------------------------------------------------------------

def test_grad_accum_metrics_keep_aux_split():
    """The grad_accum > 1 branch used to fabricate aux=0 (parts were
    discarded); with a router-aux MoE config the accumulated metrics must
    carry the true split and match the single-shot step."""
    cfg = _smoke_cfg(arch="deepseek_moe_16b")
    api = registry.get_api(cfg)
    params, consts = api.init(cfg, jax.random.PRNGKey(0), seed=0)
    opt = optimizers.make(OptimizerConfig(lr=1e-3, warmup_steps=2,
                                          total_steps=4))
    data = SyntheticC4(cfg.vocab_size, 32, 4, seed=0)
    batch = {k: jnp.asarray(v) for k, v in data.next_batch().items()}

    fn1 = jax.jit(step_lib.make_train_step(cfg, api, opt))
    fn2 = jax.jit(step_lib.make_train_step(cfg, api, opt, grad_accum=2))
    _, _, m1 = fn1(params, opt.init(params), consts, batch)
    _, _, m2 = fn2(params, opt.init(params), consts, batch)

    assert float(m2["aux"]) > 0.0, "MoE router aux vanished under accum"
    # loss decomposes: loss == ce + aux_coef * aux (coef 0.01 default)
    assert float(m2["loss"]) == pytest.approx(
        float(m2["ce"]) + 0.01 * float(m2["aux"]), rel=1e-5)
    # microbatch-averaged split tracks the single-shot split
    assert float(m2["aux"]) == pytest.approx(float(m1["aux"]), rel=0.2)
