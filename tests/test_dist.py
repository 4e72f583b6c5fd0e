"""Tests for the repro.dist subsystem: mesh construction, spec engine,
compressed collectives, and the compressed-DP step round trip."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist import compression, sharding as shl
from repro.models import registry


def _pod_mesh():
    return jax.make_mesh((1,), ("pod",),
                         axis_types=(jax.sharding.AxisType.Auto,))


# ---------------------------------------------------------------------------
# spec engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mesh2():
    return shl.make_local_mesh()


class _Key:
    def __init__(self, key):
        self.key = key


def _spec(path_names, shape, mesh, dtype=jnp.bfloat16):
    leaf = jax.ShapeDtypeStruct(shape, dtype)
    return shl.spec_for_param(tuple(_Key(k) for k in path_names), leaf, mesh)


def test_spec_sltrain_factor_leaves(mesh2):
    # B replicated; A output-sharded over model; support row-sharded
    assert _spec(("layers", "k0", "attn", "wq", "B"), (4, 64, 8),
                 mesh2) == P(None, None, None)
    sA = _spec(("layers", "k0", "attn", "wq", "A"), (4, 8, 64), mesh2)
    assert sA[-1] == "model"
    sv = _spec(("layers", "k0", "attn", "wq", "v"), (4, 64, 3), mesh2)
    assert sv[1] == "model"
    sc = _spec(("layers", "k0", "attn", "wq", "cols"), (4, 64, 3), mesh2,
               jnp.int32)
    assert sc[1] == "model"


def test_spec_dense_and_replicated_leaves(mesh2):
    sw = _spec(("layers", "k0", "mlp", "down", "w"), (4, 128, 64), mesh2)
    assert sw == P(None, None, ("model",))
    assert _spec(("embed",), (512, 64), mesh2) == P(None, None)
    assert _spec(("layers", "k0", "ln_attn"), (4, 64), mesh2) == P(None, None)
    assert _spec(("layers", "k0", "moe", "router", "w"), (4, 64, 8),
                 mesh2) == P(None, None, None)


def test_spec_moe_expert_stack_on_model_axis(mesh2):
    # (L, E, d_in, d_out): expert dim takes the model axis (EP), matrix
    # dims stay unsharded so the axis is not used twice
    se = _spec(("layers", "k0", "moe", "experts", "gate", "w"),
               (4, 8, 64, 128), mesh2)
    assert se == P(None, ("model",), None, None)
    sb = _spec(("layers", "k0", "moe", "experts", "gate", "B"),
               (4, 8, 64, 4), mesh2)
    assert sb == P(None, ("model",), None, None)


def test_param_specs_iid_support_not_row_sharded(mesh2):
    # layer-stacked iid COO support is (L, nnz) — shape-identical to
    # row-balanced (d_in, k); the sibling "rows" leaf must force the COO
    # rule (replicated) instead of sharding the layer dim over model
    sds = jax.ShapeDtypeStruct
    consts = {"layers": {"wq": {
        "rows": sds((4, 512), jnp.int32),
        "cols": sds((4, 512), jnp.int32),
    }}}
    params = {"layers": {"wq": {"v": sds((4, 512), jnp.bfloat16)}}}
    merged = {"layers": {"wq": {**consts["layers"]["wq"],
                                **params["layers"]["wq"]}}}
    specs = shl.param_specs(merged, mesh2)
    for leaf_name in ("rows", "cols", "v"):
        spec = specs["layers"]["wq"][leaf_name]
        assert all(s is None for s in spec), (leaf_name, spec)
    # the row-balanced form (no rows sibling) still row-shards
    rb = shl.param_specs({"wq": {"v": sds((64, 3), jnp.bfloat16),
                                 "cols": sds((64, 3), jnp.int32)}}, mesh2)
    assert rb["wq"]["v"][0] == "model"


def test_param_specs_match_tree_and_cover_moe():
    mesh = shl.make_local_mesh()
    cfg = registry.get_config("deepseek_moe_16b")
    api = registry.get_api(cfg)
    params, consts = api.init(cfg, key=None)  # abstract, no alloc
    specs = shl.param_specs(params, mesh)
    flat_p = jax.tree_util.tree_flatten_with_path(params)[0]
    flat_s = jax.tree_util.tree_flatten_with_path(specs)[0]
    assert len(flat_p) == len(flat_s)
    for (path, leaf), (_, spec) in zip(flat_p, flat_s):
        assert isinstance(spec, P)
        assert len(spec) <= leaf.ndim, (path, spec, leaf.shape)


def test_opt_state_specs_mirror_params():
    mesh = shl.make_local_mesh()
    cfg = registry.get_smoke_config("llama_60m")
    api = registry.get_api(cfg)
    params, _ = api.init(cfg, key=None)
    from repro.configs.base import OptimizerConfig
    from repro.optim import optimizers
    opt = optimizers.make(OptimizerConfig())
    opt_abs = jax.eval_shape(opt.init, params)
    p_specs = shl.param_specs(params, mesh)
    o_specs = shl.opt_state_specs(opt_abs, p_specs, mesh)
    # mu mirrors params: same spec on a factor-A leaf; scalars replicated
    flat_p = {shl._path_keys(p): s for p, s in
              jax.tree_util.tree_flatten_with_path(
                  p_specs, is_leaf=lambda x: isinstance(x, P))[0]}
    flat_o = {shl._path_keys(p): s for p, s in
              jax.tree_util.tree_flatten_with_path(
                  o_specs, is_leaf=lambda x: isinstance(x, P))[0]}
    for keys, spec in flat_p.items():
        assert flat_o[("mu",) + keys] == spec
    assert flat_o[("step",)] == P()


def test_cache_specs_batch_and_heads():
    mesh = shl.make_local_mesh()
    cfg = registry.get_smoke_config("llama_60m")
    api = registry.get_api(cfg)
    cache = api.init_cache(cfg, 2, 16, abstract=True)
    specs = shl.cache_specs(cache, mesh, batch_axes=("data",))
    for _, spec in jax.tree_util.tree_flatten_with_path(
            specs, is_leaf=lambda x: isinstance(x, P))[0]:
        assert spec[-4] == "data"       # batch dim sharded
        assert spec[-3] is None            # seq replicated (not seq_sharded)


def test_cache_specs_paged_heads_sharded_blocks_replicated():
    """Paged pools (lead, n_blocks, block_len, heads, hd): heads take the
    model axis (TP attention layout carries over to the gathered view);
    the block and block_len dims stay replicated."""
    mesh = shl.make_local_mesh()
    cfg = registry.get_smoke_config("llama_60m")
    api = registry.get_api(cfg)
    cache = api.init_cache(cfg, 2, 32, abstract=True, paged=True,
                           block_len=8)
    specs = shl.cache_specs(cache, mesh, paged=True)
    flat = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, P))[0]
    assert flat, "empty paged cache spec tree"
    for path, spec in flat:
        assert spec[-2] == "model", (path, spec)   # heads sharded (TP)
        assert spec[-4] is None and spec[-3] is None  # pages replicated
        assert spec[-1] is None
    # indivisible heads fall back to replication, never an error. The spec
    # engine only reads axis_names/shape, so a 2-wide stand-in mesh works
    # on a 1-device CPU.
    import dataclasses

    class _TPMesh:
        axis_names = ("data", "model")
        shape = {"data": 1, "model": 2}

    cfg3 = dataclasses.replace(cfg, n_heads=3, n_kv_heads=3, d_model=48)
    cache3 = api.init_cache(cfg3, 2, 32, abstract=True, paged=True,
                            block_len=8)
    for _, spec in jax.tree_util.tree_flatten_with_path(
            shl.cache_specs(cache3, _TPMesh(), paged=True),
            is_leaf=lambda x: isinstance(x, P))[0]:
        assert spec[-2] is None
    # and 4 kv-heads on the same 2-wide mesh do shard
    cache4 = api.init_cache(cfg, 2, 32, abstract=True, paged=True,
                            block_len=8)
    for _, spec in jax.tree_util.tree_flatten_with_path(
            shl.cache_specs(cache4, _TPMesh(), paged=True),
            is_leaf=lambda x: isinstance(x, P))[0]:
        assert spec[-2] == "model"


# ---------------------------------------------------------------------------
# compression
# ---------------------------------------------------------------------------

def test_psum_tree_compressed_matches_exact():
    mesh = _pod_mesh()
    rng = np.random.default_rng(0)
    tree = {
        "big": jnp.asarray(rng.standard_normal(4096), jnp.float32),
        "small": jnp.asarray(rng.standard_normal(16), jnp.float32),
        "ints": jnp.arange(2048, dtype=jnp.int32),
    }
    run = lambda compress: jax.shard_map(
        lambda t: compression.psum_tree(t, "pod", compress=compress),
        mesh=mesh, in_specs=(jax.tree.map(lambda _: P(), tree),),
        out_specs=jax.tree.map(lambda _: P(), tree), check_vma=False)(tree)
    exact = run(False)
    comp = run(True)
    # small float + int leaves bypass quantization entirely
    np.testing.assert_array_equal(np.asarray(comp["small"]),
                                  np.asarray(exact["small"]))
    np.testing.assert_array_equal(np.asarray(comp["ints"]),
                                  np.asarray(exact["ints"]))
    # big float leaf: within one block-quantization step
    err = np.abs(np.asarray(comp["big"]) - np.asarray(exact["big"]))
    step = np.abs(np.asarray(tree["big"])).reshape(-1, 256).max(axis=1) / 127
    assert (err.reshape(-1, 256) <= step[:, None] + 1e-6).all()


def test_wire_bytes_int8_beats_f32_ring():
    n = 1 << 20
    for p in (2, 4):
        c = compression.wire_bytes(n, compressed=True, n_participants=p)
        f = compression.wire_bytes(n, compressed=False, n_participants=p)
        assert c > 0 and f > 0
    # the acceptance bar: ≥3× reduction at 2 pods
    c2 = compression.wire_bytes(n, compressed=True, n_participants=2)
    f2 = compression.wire_bytes(n, compressed=False, n_participants=2)
    assert f2 / c2 >= 3.0


# ---------------------------------------------------------------------------
# compressed-DP step: CPU-mesh round trip on llama_60m
# ---------------------------------------------------------------------------

def test_compressed_dp_step_cpu_mesh_roundtrip():
    from repro.configs.base import OptimizerConfig
    from repro.data.pipeline import SyntheticC4
    from repro.optim import optimizers as opt_lib
    from repro.train import step as step_lib

    cfg = registry.get_smoke_config("llama_60m")
    api = registry.get_api(cfg)
    params, consts = api.init(cfg, jax.random.PRNGKey(0), seed=0)
    opt = opt_lib.make(OptimizerConfig(lr=1e-3, warmup_steps=1,
                                       total_steps=4))
    opt_state = opt.init(params)
    mesh = jax.make_mesh((1,), ("pod",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    step = jax.jit(step_lib.make_compressed_dp_step(cfg, api, opt, mesh))
    data = SyntheticC4(cfg.vocab_size, 32, 4, seed=0)
    p0 = jax.tree.leaves(params)[0]
    losses = []
    for _ in range(3):
        b = {k: jnp.asarray(v) for k, v in data.next_batch().items()}
        params, opt_state, m = step(params, opt_state, consts, b)
        losses.append(float(m["loss"]))
    assert all(np.isfinite(l) for l in losses), losses
    # the step actually applied updates
    assert not np.array_equal(np.asarray(p0, np.float32),
                              np.asarray(jax.tree.leaves(params)[0],
                                         np.float32))


# ---------------------------------------------------------------------------
# ISSUE 8: fsdp × TP spec composition
# ---------------------------------------------------------------------------

class _FsdpMesh:
    """Fake multi-device mesh (spec logic only reads axis_names/shape) so
    divisibility is exercised without 128 real devices."""
    axis_names = ("pod", "data", "model")
    shape = {"pod": 2, "data": 8, "model": 8}


def _axis_uses(spec):
    out = []
    for s in spec:
        out.extend(s if isinstance(s, tuple) else ((s,) if s else ()))
    return out


_ALL_ARCHS = registry.PAPER_ARCHS + registry.ARCHS


@pytest.mark.parametrize("arch", _ALL_ARCHS)
def test_fsdp_specs_never_reuse_a_mesh_axis(arch):
    """Property (ISSUE 8 satellite): for every registry config, fsdp × TP
    param/opt specs use each mesh axis AT MOST once per leaf, and every
    sharded dim divides by its axis product (the _guard contract)."""
    cfg = registry.get_smoke_config(arch)
    api = registry.get_api(cfg)
    params, consts = api.init(cfg, key=None)
    mesh = _FsdpMesh()
    for tree in (params, consts):
        specs = shl.param_specs(tree, mesh, fsdp_axes=("data",))
        flat_p = jax.tree_util.tree_flatten_with_path(tree)[0]
        flat_s = jax.tree_util.tree_flatten_with_path(
            specs, is_leaf=lambda x: isinstance(x, P))[0]
        assert len(flat_p) == len(flat_s)
        for (path, leaf), (_, spec) in zip(flat_p, flat_s):
            uses = _axis_uses(spec)
            assert len(uses) == len(set(uses)), (path, spec)
            for dim, s in zip(leaf.shape, spec):
                n = shl.axis_size(mesh, s)
                assert dim % n == 0, (path, spec, leaf.shape)


@pytest.mark.parametrize("arch", _ALL_ARCHS)
def test_named_shardings_accept_fsdp_param_trees(arch):
    """Property: named_shardings materializes a NamedSharding for every
    leaf of every registry config's param tree under fsdp=True on a real
    mesh (specs must be structurally valid for jax, not just our rules)."""
    from jax.sharding import NamedSharding

    cfg = registry.get_smoke_config(arch)
    api = registry.get_api(cfg)
    params, _ = api.init(cfg, key=None)
    mesh = shl.make_local_mesh()
    specs = shl.param_specs(params, mesh, fsdp_axes=("data",))
    nss = shl.named_shardings(mesh, specs)
    for (path, leaf), (_, ns) in zip(
            jax.tree_util.tree_flatten_with_path(params)[0],
            jax.tree_util.tree_flatten_with_path(
                nss, is_leaf=lambda x: isinstance(x, NamedSharding))[0]):
        assert isinstance(ns, NamedSharding), path
        # the sharding is consistent with the leaf's rank/shape
        ns.shard_shape(leaf.shape)


def test_fsdp_opt_state_specs_follow_params():
    """AdamW moments inherit the fsdp param spec; adam8bit codes/scales
    (non-mirroring leaves) shard dim 0 over the fsdp axes when divisible."""
    from repro.configs.base import OptimizerConfig
    from repro.optim import optimizers as opt_lib

    cfg = registry.get_smoke_config("llama_60m")
    api = registry.get_api(cfg)
    params, _ = api.init(cfg, jax.random.PRNGKey(0), seed=0)
    mesh = _FsdpMesh()
    p_specs = shl.param_specs(params, mesh, fsdp_axes=("data",))

    opt = opt_lib.make(OptimizerConfig(name="adamw"))
    st = opt.init(params)
    s_specs = shl.opt_state_specs(st, p_specs, mesh, fsdp_axes=("data",))
    # the embed moment mirrors the embed param spec exactly
    assert s_specs["mu"]["embed"] == p_specs["embed"]
    # moments never reuse an axis either
    for _, spec in jax.tree_util.tree_flatten_with_path(
            s_specs, is_leaf=lambda x: isinstance(x, P))[0]:
        uses = _axis_uses(spec)
        assert len(uses) == len(set(uses)), spec


# ---------------------------------------------------------------------------
# wire model vs measured HLO (ISSUE 8 acceptance) — needs 2 host devices,
# so it runs scripts/hostmesh_smoke.py in a subprocess with its own
# xla_force_host_platform_device_count
# ---------------------------------------------------------------------------

def test_wire_model_matches_hlo_measured_collectives():
    """dist/compression.wire_bytes (the int8 exchange model) must agree
    with the collective bytes parsed from the compiled compressed-DP
    step's post-SPMD HLO, within ring-algorithm tolerance."""
    import os
    import re
    import subprocess
    import sys

    root = os.path.join(os.path.dirname(__file__), "..")
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    out = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "hostmesh_smoke.py"),
         "--part", "wire"],
        capture_output=True, text=True, timeout=560, env=env)
    assert out.returncode == 0, out.stdout + out.stderr
    m = re.search(r"wire_model_ratio=([\d.]+)", out.stdout)
    assert m, out.stdout
    ratio = float(m.group(1))
    assert 0.7 <= ratio <= 1.3, (ratio, out.stdout)


# ---------------------------------------------------------------------------
# Trainer on the local mesh: sharded init + placement + donated steps
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("optimizer", ["adamw", "adam8bit"])
def test_trainer_fsdp_on_local_mesh_matches_no_mesh(optimizer, tmp_path):
    """FSDP on the local mesh (every visible device) builds its optimizer
    state already sharded and must train exactly like the unplaced run."""
    from repro.configs.base import (OptimizerConfig, ShardingConfig,
                                    TrainConfig)
    from repro.train.trainer import Trainer

    losses = {}
    for name, mesh in (("plain", None), ("mesh", shl.make_local_mesh())):
        tc = TrainConfig(
            model=registry.get_smoke_config("llama_60m"),
            optim=OptimizerConfig(name=optimizer, lr=1e-3, warmup_steps=1,
                                  total_steps=2),
            sharding=ShardingConfig(fsdp=mesh is not None),
            global_batch=4, seq_len=32, steps=2, log_every=100,
            ckpt_dir=str(tmp_path / name))
        tr = Trainer(tc, mesh=mesh, log_fn=lambda *_: None)
        state = tr.run()
        losses[name] = [h["loss"] for h in tr.metrics_history]
        if mesh is not None:
            leaf = jax.tree.leaves(state.opt_state)[0]
            assert isinstance(leaf.sharding, jax.sharding.NamedSharding)
            assert leaf.sharding.mesh.shape == mesh.shape
    np.testing.assert_allclose(losses["mesh"], losses["plain"], rtol=1e-5)
