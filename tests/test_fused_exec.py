"""End-to-end tests for ``exec_mode="fused"`` (ISSUE 3): config →
Builder.linear → apply_linear → core.sltrain → Pallas custom-VJP kernels,
plus the kernel-wrapper bug-batch regressions (bf16 dv accumulation,
deterministic tile capacity, blocked support sampling)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import OptimizerConfig
from repro.core import sltrain, support
from repro.data.pipeline import SyntheticC4
from repro.kernels import ops
from repro.models import registry
from repro.optim import optimizers
from repro.train import step as step_lib


def _fused_smoke_cfg(dtype="float32"):
    base = registry.get_smoke_config("llama_60m")
    return dataclasses.replace(
        base, dtype=dtype,
        param=dataclasses.replace(base.param, mode="sltrain",
                                  exec_mode="fused"))


# ---------------------------------------------------------------------------
# Acceptance: token-for-token train parity with the densify path
# ---------------------------------------------------------------------------

def _run_training(cfg, steps):
    api = registry.get_api(cfg)
    params, consts = api.init(cfg, jax.random.PRNGKey(42), seed=42)
    opt = optimizers.make(OptimizerConfig(lr=1e-3, warmup_steps=2,
                                          total_steps=steps))
    opt_state = opt.init(params)
    fn = jax.jit(step_lib.make_train_step(cfg, api, opt))
    data = SyntheticC4(cfg.vocab_size, 32, 4, seed=0)
    losses = []
    for _ in range(steps):
        batch = {k: jnp.asarray(v) for k, v in data.next_batch().items()}
        params, opt_state, metrics = fn(params, opt_state, consts, batch)
        losses.append(float(metrics["loss"]))
    return np.asarray(losses)


def test_fused_trains_to_loss_parity_with_dense():
    """Same seed, same data, 20 steps: the fused Pallas path must track the
    densify path token for token — a few f32 ulp of loss, every step."""
    steps = 20
    cfg_f = _fused_smoke_cfg()
    cfg_d = dataclasses.replace(
        cfg_f, param=dataclasses.replace(cfg_f.param, exec_mode="dense"))
    loss_d = _run_training(cfg_d, steps)
    loss_f = _run_training(cfg_f, steps)
    # ulp(loss≈7) in f32 is ~4.8e-7; allow a handful per step
    np.testing.assert_allclose(loss_f, loss_d, rtol=0, atol=5e-6)


# ---------------------------------------------------------------------------
# Acceptance: abstract dry-run twin matches concrete init exactly
# ---------------------------------------------------------------------------

def test_fused_abstract_init_matches_concrete_shapes():
    """The no-alloc dry-run must build fused-mode trees (including the
    layer-stacked tile consts) whose shapes/dtypes exactly match concrete
    init — this is what the deterministic tile_cap buys."""
    cfg = _fused_smoke_cfg()
    api = registry.get_api(cfg)
    params_c, consts_c = api.init(cfg, jax.random.PRNGKey(0), seed=0)
    params_a, consts_a = api.init(cfg, key=None)

    def check(c, a):
        assert tuple(c.shape) == tuple(a.shape), (c.shape, a.shape)
        assert jnp.dtype(c.dtype) == jnp.dtype(a.dtype)

    jax.tree.map(check, params_c, params_a)
    jax.tree.map(check, consts_c, consts_a)
    # and the fused consts are actually there
    flat = jax.tree_util.tree_flatten_with_path(consts_a)[0]
    names = {str(getattr(p[-1], "key", p[-1])) for p, _ in flat}
    assert {"rows_t", "cols_t", "perm"} <= names


def test_fused_params_identical_to_dense_params():
    """exec_mode changes execution, not state: the trainable tree (and the
    sampled support) must be identical to a dense-mode init with the same
    seed — checkpoints/optimizer state stay layout-independent."""
    cfg_f = _fused_smoke_cfg()
    cfg_d = dataclasses.replace(
        cfg_f, param=dataclasses.replace(cfg_f.param, exec_mode="dense"))
    api = registry.get_api(cfg_f)
    params_f, consts_f = api.init(cfg_f, jax.random.PRNGKey(1), seed=1)
    params_d, consts_d = api.init(cfg_d, jax.random.PRNGKey(1), seed=1)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), params_f, params_d)
    # dense consts (cols) are a subtree of the fused consts
    flat_d = {tuple(str(getattr(k, "key", k)) for k in p): l for p, l in
              jax.tree_util.tree_flatten_with_path(consts_d)[0]}
    flat_f = {tuple(str(getattr(k, "key", k)) for k in p): l for p, l in
              jax.tree_util.tree_flatten_with_path(consts_f)[0]}
    for path, leaf in flat_d.items():
        np.testing.assert_array_equal(np.asarray(leaf),
                                      np.asarray(flat_f[path]))


# ---------------------------------------------------------------------------
# Satellite: bf16 dv must accumulate in f32 (fused == dense gather grad)
# ---------------------------------------------------------------------------

def test_fused_dv_bf16_matches_dense_take_along_axis_grad():
    d_in, d_out, r, m = 256, 384, 16, 96
    params, consts = sltrain.init_params(
        jax.random.PRNGKey(3), d_in, d_out, r, 0.03, jnp.bfloat16,
        "row_balanced", seed=11, exec_mode="fused")
    params["B"] = (jax.random.normal(jax.random.PRNGKey(4),
                                     params["B"].shape) * 0.1
                   ).astype(jnp.bfloat16)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((m, d_in)), jnp.bfloat16)
    # f32 cotangent on purpose: upstream (norm/softmax bwd) hands f32, the
    # wrapper must align dtypes rather than crash or round-trip through bf16
    dy = jnp.asarray(rng.standard_normal((m, d_out)), jnp.float32)

    def loss(p, mode):
        return jnp.sum(sltrain.sl_matmul(x, p, consts, 0.5, mode)
                       .astype(jnp.float32) * dy)

    gd = jax.grad(lambda p: loss(p, "dense"))(params)
    gf = jax.grad(lambda p: loss(p, "fused"))(params)
    # both sides accumulate the token contraction in f32 and round ONCE to
    # bf16 — they must agree to ~1 bf16 ulp, not bf16 drift
    dv_d = np.asarray(gd["v"], np.float32)
    dv_f = np.asarray(gf["v"], np.float32)
    scale_ref = np.abs(dv_d).max()
    np.testing.assert_allclose(dv_f, dv_d, rtol=1e-2,
                               atol=1e-2 * scale_ref)


# ---------------------------------------------------------------------------
# v between its flat layout and tile order: key sorts, exact
# ---------------------------------------------------------------------------

# whole row tiles, and d_in 300: a last row tile of 44 rows
PERMUTE_CASES = [
    pytest.param(kind, layers, d_in, d_out,
                 id=f"{kind}-{layers}layer-{d_in}x{d_out}")
    for kind, layers, d_in, d_out in (
        ("row_balanced", 1, 256, 384), ("row_balanced", 2, 256, 384),
        ("iid", 1, 256, 384), ("iid", 2, 256, 384),
        ("row_balanced", 2, 300, 200), ("iid", 1, 300, 200))]


def _stacked_layers(kind, layers, d_in, d_out, r=16):
    """``layers`` fused SL linears of one shape, stacked on a leading axis
    as the model's scan stacks them. Every layer has padding slots."""
    ps, cs = zip(*[sltrain.init_params(
        jax.random.PRNGKey(20 + i), d_in, d_out, r, 0.03, jnp.bfloat16,
        kind, seed=30 + i, exec_mode="fused") for i in range(layers)])
    params = jax.tree.map(lambda *a: jnp.stack(a), *ps)
    consts = jax.tree.map(lambda *a: jnp.stack(a), *cs)
    assert bool((consts["perm"] < 0).any(axis=(1, 2, 3)).all())
    return params, consts


def _per_layer(fn, *stacked):
    """``fn`` over the stacked layers inside a jit'd ``lax.scan``."""
    def run(*xs):
        return jax.lax.scan(lambda c, sl: (c, fn(*sl)), None, xs)[1]
    return jax.jit(run)(*stacked)


def _gather_oracle(v, perm):
    """The layout's definition: slot s holds v[perm[s]], pads hold 0."""
    vf = np.asarray(v, np.float32).reshape(-1)
    p = np.asarray(perm)
    return np.where(p >= 0, vf[np.maximum(p, 0)], 0.0)


def _scatter_add_oracle(dv_t, perm, nnz):
    """dv by a scatter-add of the valid slots onto zeros."""
    p = np.asarray(perm).reshape(-1)
    d = np.asarray(dv_t, np.float32).reshape(-1)
    out = np.zeros(nnz, np.float32)
    np.add.at(out, p[p >= 0], d[p >= 0])
    return out


@pytest.mark.parametrize("kind,layers,d_in,d_out", PERMUTE_CASES)
def test_sort_to_tiles_equals_the_gather(kind, layers, d_in, d_out):
    params, consts = _stacked_layers(kind, layers, d_in, d_out)
    v_t = _per_layer(ops._to_tiles, params["v"], consts["perm"])
    assert v_t.dtype == jnp.float32
    for i in range(layers):
        # exact, and array_equal holds -0.0 == 0.0 on the pads
        assert np.array_equal(
            np.asarray(v_t[i]),
            _gather_oracle(params["v"][i], consts["perm"][i]))


@pytest.mark.parametrize("kind,layers,d_in,d_out", PERMUTE_CASES)
def test_sort_to_flat_equals_a_scatter_add(kind, layers, d_in, d_out):
    params, consts = _stacked_layers(kind, layers, d_in, d_out)
    perm = consts["perm"]
    # nonzero grads on the pad slots too, as sddmm leaves them
    dv_t = jax.random.normal(jax.random.PRNGKey(9), perm.shape, jnp.float32)
    nnz = params["v"][0].size
    dv = _per_layer(lambda d, p, v: ops._to_flat(d, p, v).reshape(-1),
                    dv_t, perm, params["v"])
    for i in range(layers):
        np.testing.assert_array_equal(
            np.asarray(dv[i]), _scatter_add_oracle(dv_t[i], perm[i], nnz))


@pytest.mark.parametrize("kind,layers,d_in,d_out", PERMUTE_CASES)
def test_sl_linear_grad_matches_dense_reference(kind, layers, d_in, d_out):
    """jax.grad through the fused linear, layers in a scan, against the
    dense path (the take_along_axis gather for row-balanced v, the COO
    densify for iid), with the bound of the single-layer bf16 test."""
    params, consts = _stacked_layers(kind, layers, d_in, d_out)
    params["B"] = (jax.random.normal(jax.random.PRNGKey(4),
                                     params["B"].shape) * 0.1
                   ).astype(jnp.bfloat16)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((96, d_in)), jnp.bfloat16)
    dy = jnp.asarray(rng.standard_normal((layers, 96, d_out)), jnp.float32)

    def loss(p, mode):
        def layer(c, xs):
            pl, cl, dyl = xs
            y = sltrain.sl_matmul(x, pl, cl, 0.5, mode).astype(jnp.float32)
            return c + jnp.sum(y * dyl), None
        return jax.lax.scan(layer, 0.0, (p, consts, dy))[0]

    gd = jax.jit(jax.grad(lambda p: loss(p, "dense")))(params)
    gf = jax.jit(jax.grad(lambda p: loss(p, "fused")))(params)
    for name in ("v", "B", "A"):
        d = np.asarray(gd[name], np.float32)
        f = np.asarray(gf[name], np.float32)
        np.testing.assert_allclose(f, d, rtol=1e-2,
                                   atol=1e-2 * np.abs(d).max())


def test_fused_step_records_one_sort_permute_per_pass_per_linear():
    """Tracing one fused training step records ``sl.permute`` instants for
    the forward and for dv of every SL linear, each a sort, one per trace
    of the pass: dv once, the scanned layer body's forward as often as
    JAX traces it (the same count for every linear)."""
    import collections

    import repro.obs
    cfg = _fused_smoke_cfg()
    api = registry.get_api(cfg)
    params, consts = api.init(cfg, jax.random.PRNGKey(0), seed=0)
    opt = optimizers.make(OptimizerConfig(lr=1e-3))
    batch = {k: jnp.asarray(v) for k, v in
             SyntheticC4(cfg.vocab_size, 32, 2, seed=0).next_batch().items()}

    def permutes():
        return [e["args"] for e in repro.obs.get_trace().events
                if e["name"] == "sl.permute"]

    n0 = len(permutes())
    jax.jit(step_lib.make_train_step(cfg, api, opt)).lower(
        params, opt.init(params), consts, batch)
    got = permutes()[n0:]
    # slots of one layer of each SL linear in the layer stack
    slots = collections.Counter(
        leaf[0].size for p, leaf in jax.tree_util.tree_flatten_with_path(
            consts["layers"])[0] if str(getattr(p[-1], "key", "")) == "perm")
    n_linears = sum(slots.values())
    assert n_linears == 7
    assert all(a["how"] == "sort" and a["slots"] >= a["nnz"] > 0
               for a in got)
    for pass_, traces in (("fwd", None), ("dv", 1)):
        seen = collections.Counter(a["slots"] for a in got
                                   if a["pass"] == pass_)
        n = traces or sum(seen.values()) // n_linears
        assert n >= 1
        assert seen == collections.Counter(
            {s: c * n for s, c in slots.items()}), (pass_, seen)


# ---------------------------------------------------------------------------
# Satellite: deterministic tile capacity + host re-sample fallback
# ---------------------------------------------------------------------------

def test_tile_layout_fixed_pad_raises_on_overflow():
    rows, cols = support.sample_support(0, 256, 256, 0.05, "row_balanced")
    with pytest.raises(ValueError, match="re-sample"):
        support.tile_layout(rows, cols, 256, 256, pad=8)


def test_tile_cap_bounds_realized_max():
    for seed in range(5):
        for (d_in, d_out, delta) in [(64, 96, 0.05), (300, 200, 0.03),
                                     (512, 128, 0.1)]:
            rows, cols = support.sample_support(seed, d_in, d_out, delta,
                                                "row_balanced")
            cap = support.tile_cap(d_in, d_out, delta)
            kp = ((d_in + 127) // 128) * 128
            np_ = ((d_out + 127) // 128) * 128
            _, _, counts, _ = support.tile_layout(rows, cols, kp, np_)
            assert int(counts.max()) <= cap, (d_in, d_out, delta, seed)


def test_fused_init_resample_fallback_raises_loudly(monkeypatch):
    """When the deterministic bound is (artificially) impossible, init must
    re-sample deterministically and then fail loudly, not loop forever or
    emit ragged consts."""
    monkeypatch.setattr(support, "tile_cap", lambda *a, **k: 8)
    with pytest.raises(ValueError, match="re-samples"):
        sltrain.init_params(jax.random.PRNGKey(0), 256, 256, 8, 0.05,
                            jnp.float32, "row_balanced", seed=0,
                            exec_mode="fused")


def test_fused_without_tile_consts_raises():
    params, consts = sltrain.init_params(
        jax.random.PRNGKey(0), 64, 64, 4, 0.05, jnp.float32, seed=0)
    x = jnp.zeros((2, 64), jnp.float32)
    with pytest.raises(ValueError, match="fused"):
        sltrain.sl_matmul(x, params, consts, 0.5, exec_mode="fused")


# ---------------------------------------------------------------------------
# Satellite: blocked support sampler agrees with the dense-keys branch
# ---------------------------------------------------------------------------

def test_sample_support_blocked_branch_matches_dense_branch(monkeypatch):
    """The row-blocked large-matrix fallback must produce the exact support
    of the full-key-matrix branch (same PRNG stream) — shrink the
    threshold so a small shape straddles it."""
    d_in, d_out, delta = 96, 130, 0.05
    full_r, full_c = support.sample_support(7, d_in, d_out, delta,
                                            "row_balanced")
    # force the blocked branch: threshold below d_in*d_out but above d_out
    monkeypatch.setattr(support, "DENSE_KEYS_ELEMS", 4 * d_out)
    blk_r, blk_c = support.sample_support(7, d_in, d_out, delta,
                                          "row_balanced")
    np.testing.assert_array_equal(full_r, blk_r)
    np.testing.assert_array_equal(full_c, blk_c)


# ---------------------------------------------------------------------------
# Sharding specs + modeled HBM
# ---------------------------------------------------------------------------

def test_fused_tile_consts_shard_nnt_over_model():
    """ISSUE 8: tile consts shard their nnt (d_out-tile) axis over the
    model axis — the same layout as A's d_out — so the distributed fused
    vjp reads only local column tiles; every other dim (layer stack, nkt,
    cap) stays replicated, and a non-dividing nnt replicates entirely."""
    from jax.sharding import PartitionSpec as P

    from repro.dist import sharding as shl
    mesh = shl.make_local_mesh()
    cfg = _fused_smoke_cfg()
    _, consts_abs = registry.get_api(cfg).init(cfg, key=None)
    specs = shl.param_specs(consts_abs, mesh)
    flat = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, P))[0]
    seen = set()
    for path, spec in flat:
        name = str(getattr(path[-1], "key", path[-1]))
        if name in ("rows_t", "cols_t", "perm"):
            seen.add(name)
            # spec covers (…stack, nkt, nnt, cap): only nnt carries model
            assert spec[-2] in ("model", None), (path, spec)
            assert all(s is None for i, s in enumerate(spec)
                       if i != len(spec) - 2), (path, spec)
    assert seen == {"rows_t", "cols_t", "perm"}

    class _TPMesh:  # spec logic only reads axis_names/shape
        axis_names = ("data", "model")
        shape = {"data": 1, "model": 7}   # 7 never divides nnt

    specs7 = shl.param_specs(consts_abs, _TPMesh())
    for path, spec in jax.tree_util.tree_flatten_with_path(
            specs7, is_leaf=lambda x: isinstance(x, P))[0]:
        name = str(getattr(path[-1], "key", path[-1]))
        if name in ("rows_t", "cols_t", "perm"):
            assert all(s is None for s in spec), (path, spec)


def test_modeled_hbm_fused_beats_densify_by_compression():
    """Acceptance: the fused train step's modeled parameter HBM traffic
    beats the densify path by at least the paper's compression ratio."""
    from benchmarks.kernel_bench import _sltrain_traffic_model
    cfg = _fused_smoke_cfg()
    params_abs, consts_abs = registry.get_api(cfg).init(cfg, key=None)
    densify, fused, compression = _sltrain_traffic_model(params_abs,
                                                         consts_abs)
    assert compression > 1.0
    assert densify / fused >= compression, (densify, fused, compression)
