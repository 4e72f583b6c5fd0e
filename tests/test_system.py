"""System-level tests: data pipeline, checkpoint/restart, trainer fault
tolerance, serve engine, gradient compression, memory estimator."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt.checkpoint import CheckpointManager
from repro.configs.base import OptimizerConfig, TrainConfig
from repro.core import memory as memory_lib
from repro.data.pipeline import SyntheticC4
from repro.models import registry
from repro.train.trainer import Trainer, StepTimeWatchdog


def _tc(tmp, steps=6, ckpt_every=0, **kw):
    cfg = registry.get_smoke_config("llama_60m")
    return TrainConfig(model=cfg,
                       optim=OptimizerConfig(lr=1e-3, warmup_steps=2,
                                             total_steps=steps),
                       global_batch=4, seq_len=32, steps=steps,
                       log_every=100, ckpt_every=ckpt_every, ckpt_dir=tmp,
                       async_ckpt=False, **kw)


# ---------------------------------------------------------------------------
# Data pipeline
# ---------------------------------------------------------------------------

def test_data_deterministic_and_host_sharded():
    full = SyntheticC4(512, 64, 8, seed=1).next_batch()["tokens"]
    h0 = SyntheticC4(512, 64, 8, seed=1, host_id=0, num_hosts=2)
    h1 = SyntheticC4(512, 64, 8, seed=1, host_id=1, num_hosts=2)
    assert (np.concatenate([h0.next_batch()["tokens"],
                            h1.next_batch()["tokens"]]) == full).all()


def test_data_checkpoint_roundtrip():
    ds = SyntheticC4(512, 64, 4, seed=3)
    ds.next_batch(); ds.next_batch()
    st = ds.state_dict()
    b3 = ds.next_batch()["tokens"]
    ds2 = SyntheticC4(512, 64, 4, seed=3)
    ds2.restore(st)
    assert (ds2.next_batch()["tokens"] == b3).all()


def test_data_tokens_in_range():
    b = SyntheticC4(512, 128, 4, seed=0).next_batch()["tokens"]
    assert b.min() >= 0 and b.max() < 512


# ---------------------------------------------------------------------------
# Checkpointing
# ---------------------------------------------------------------------------

def test_ckpt_atomic_and_gc():
    with tempfile.TemporaryDirectory() as d:
        cm = CheckpointManager(d, keep=2)
        tree = {"w": jnp.arange(8, dtype=jnp.float32),
                "b": jnp.ones(3, jnp.bfloat16) * 1.5}
        for s in (1, 2, 3):
            cm.save(s, tree, config_hash="h")
        assert cm.all_steps() == [2, 3]
        out, man = cm.restore(tree, config_hash="h")
        assert out["b"].dtype == jnp.bfloat16
        assert float(out["b"][0]) == 1.5


def test_ckpt_rejects_config_drift():
    with tempfile.TemporaryDirectory() as d:
        cm = CheckpointManager(d)
        cm.save(1, {"w": jnp.zeros(4)}, config_hash="aaa")
        with pytest.raises(ValueError, match="config hash"):
            cm.restore({"w": jnp.zeros(4)}, config_hash="bbb")


def test_ckpt_rejects_shape_drift():
    with tempfile.TemporaryDirectory() as d:
        cm = CheckpointManager(d)
        cm.save(1, {"w": jnp.zeros(4)})
        with pytest.raises(ValueError, match="shape"):
            cm.restore({"w": jnp.zeros(5)})


def test_ckpt_elastic_restore_onto_sharding():
    """Checkpoint written unsharded restores onto a mesh sharding."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    with tempfile.TemporaryDirectory() as d:
        cm = CheckpointManager(d)
        tree = {"w": jnp.arange(16, dtype=jnp.float32).reshape(4, 4)}
        cm.save(1, tree)
        mesh = jax.make_mesh((1,), ("model",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        sh = {"w": NamedSharding(mesh, P("model", None))}
        out, _ = cm.restore(tree, shardings=sh)
        assert out["w"].sharding == sh["w"]
        assert (np.asarray(out["w"]) == np.arange(16).reshape(4, 4)).all()


# ---------------------------------------------------------------------------
# Trainer: resume bit-exactness, fault hooks, straggler watchdog
# ---------------------------------------------------------------------------

def test_trainer_loss_decreases():
    with tempfile.TemporaryDirectory() as d:
        tr = Trainer(_tc(d, steps=30))
        tr.run()
        first = np.mean([m["loss"] for m in tr.metrics_history[:5]])
        last = np.mean([m["loss"] for m in tr.metrics_history[-5:]])
        assert last < first, (first, last)


def test_trainer_kill_resume_bit_exact():
    """Crash at step 5, relaunch, final params must equal an uninterrupted
    run (checkpoint/restart correctness, DESIGN §7)."""
    class Boom(Exception):
        pass

    with tempfile.TemporaryDirectory() as d1, \
            tempfile.TemporaryDirectory() as d2:
        # uninterrupted reference
        ref = Trainer(_tc(d1, steps=8, ckpt_every=4))
        ref_state = ref.run()

        def bomb(step):
            if step == 5 and not os.environ.get("_RESUMED"):
                raise Boom()

        tr = Trainer(_tc(d2, steps=8, ckpt_every=4), fault_hook=bomb)
        with pytest.raises(Boom):
            tr.run()
        os.environ["_RESUMED"] = "1"
        try:
            tr2 = Trainer(_tc(d2, steps=8, ckpt_every=4))
            state2 = tr2.run()
        finally:
            del os.environ["_RESUMED"]
        for a, b in zip(jax.tree.leaves(ref_state.params),
                        jax.tree.leaves(state2.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_straggler_watchdog_flags_slow_steps():
    events = []
    wd = StepTimeWatchdog(factor=3.0,
                          on_straggler=lambda s, dt, med: events.append(s))
    for i in range(10):
        wd.observe(i, 0.1)
    assert not wd.flagged
    wd.observe(10, 0.5)
    assert wd.flagged == [10] and events == [10]


# ---------------------------------------------------------------------------
# Serve engine
# ---------------------------------------------------------------------------

def test_serve_engine_continuous_batching():
    cfg = registry.get_smoke_config("llama_60m")
    api = registry.get_api(cfg)
    params, consts = api.init(cfg, jax.random.PRNGKey(0), seed=0)
    from repro.serve.engine import ServeEngine
    eng = ServeEngine(cfg, params, consts, n_slots=2, max_len=32)
    reqs = [eng.submit([3 + i, 7], max_new_tokens=3) for i in range(5)]
    eng.run_until_drained()
    assert all(r.done for r in reqs)
    assert all(len(r.out) == 3 for r in reqs)
    assert all(0 <= t < cfg.vocab_size for r in reqs for t in r.out)


def test_sparse_decode_matches_dense_decode():
    """exec_mode=sparse must produce the same tokens as dense decode."""
    cfg = registry.get_smoke_config("llama_60m")
    api = registry.get_api(cfg)
    params, consts = api.init(cfg, jax.random.PRNGKey(0), seed=0)
    from repro.serve.engine import ServeEngine
    outs = []
    for sparse in (False, True):
        eng = ServeEngine(cfg, params, consts, n_slots=1, max_len=32,
                          sparse_decode=sparse)
        r = eng.submit([5, 9, 11], max_new_tokens=6)
        eng.run_until_drained()
        outs.append(r.out)
    assert outs[0] == outs[1], outs


# ---------------------------------------------------------------------------
# Gradient compression
# ---------------------------------------------------------------------------

def test_int8_psum_error_bound():
    from jax.sharding import PartitionSpec as P
    from repro.dist.compression import int8_psum
    mesh = jax.make_mesh((1,), ("pod",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    f = jax.shard_map(lambda x: int8_psum(x, "pod"), mesh=mesh,
                      in_specs=P(), out_specs=P(), check_vma=False)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(4096),
                    jnp.float32)
    y = f(x)
    # error ≤ one quant step = blockmax/127 per element
    step = float(jnp.abs(x).max()) / 127.0
    assert float(jnp.abs(y - x).max()) <= step + 1e-6


def test_compression_wire_bytes_model():
    from repro.dist.compression import wire_bytes
    n = 1 << 20
    # 2-pod DCI: int8 gather ≈ 1 B/elem vs f32 ring all-reduce 4 B/elem
    assert wire_bytes(n, compressed=True, n_participants=2) <         0.3 * wire_bytes(n, compressed=False, n_participants=2)


# ---------------------------------------------------------------------------
# Memory estimator reproduces the paper
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("size,method,paper_params_M,paper_total_G", [
    ("60m", "full", 58, 0.35), ("60m", "sltrain", 44, 0.26),
    ("130m", "sltrain", 97, 0.60), ("350m", "sltrain", 194, 1.24),
    ("1b", "sltrain", 646, 4.16), ("1b", "full", 1339, 8.04),
    ("1b", "lowrank", 609, 3.66),
])
def test_memory_matches_paper_table2(size, method, paper_params_M,
                                     paper_total_G):
    est = memory_lib.paper_table8(size)[method]
    assert abs(est["params_M"] - paper_params_M) / paper_params_M < 0.02
    assert abs(est["total_G"] - paper_total_G) < 0.06 * paper_total_G + 0.02


def test_relora_periodic_merge_in_trainer():
    """ReLoRA mode: the trainer merges BA into W0 every relora_period steps
    and restarts factors + their Adam moments (paper baseline [32])."""
    import dataclasses
    cfg = registry.get_smoke_config("llama_60m")
    cfg = dataclasses.replace(
        cfg, param=dataclasses.replace(cfg.param, mode="relora",
                                       relora_period=3))
    with tempfile.TemporaryDirectory() as d:
        tc = TrainConfig(model=cfg,
                         optim=OptimizerConfig(lr=1e-3, warmup_steps=2,
                                               total_steps=7),
                         global_batch=4, seq_len=32, steps=7, log_every=100,
                         ckpt_every=0, ckpt_dir=d, async_ckpt=False)
        tr = Trainer(tc)
        state = tr.run()
        # after the merge at step 6 + one step of training, B is one Adam
        # step away from zero — tiny compared to a never-merged B
        b_leaves = [np.asarray(l) for p, l in
                    jax.tree_util.tree_flatten_with_path(state.params)[0]
                    if any(getattr(k, "key", "") == "B" for k in p)]
        assert b_leaves, "no relora factors found"
        assert max(np.abs(b).max() for b in b_leaves) < 1e-2
        # loss still finite and decreasing-ish across merges
        assert np.isfinite(tr.metrics_history[-1]["loss"])


def test_galore_composes_with_sltrain_factors():
    """Paper §3.3: GaLore's low-rank gradient projection can be applied ON
    TOP of the SLTrain factors — the B/A moments then live in an even
    lower-dimensional space."""
    from repro.optim import optimizers as opt_lib
    cfg = registry.get_smoke_config("llama_60m")  # sltrain mode, rank 8
    api = registry.get_api(cfg)
    params, consts = api.init(cfg, jax.random.PRNGKey(0), seed=0)
    oc = OptimizerConfig(name="galore_adamw", lr=1e-3, galore_rank=4,
                         warmup_steps=1, total_steps=5)
    opt = opt_lib.make(oc)
    st = opt.init(params)
    # at least one factor leaf must have a projected (rank-4) moment
    projected = [l for p, l in jax.tree_util.tree_flatten_with_path(
        st["leaves"])[0] if any(getattr(k, "key", "") == "P" for k in p)]
    assert projected, "no projected moments on SLTrain factors"
    from repro.train import step as step_lib
    from repro.data.pipeline import SyntheticC4
    tstep = jax.jit(step_lib.make_train_step(cfg, api, opt))
    data = SyntheticC4(cfg.vocab_size, 32, 4, seed=0)
    import jax.numpy as jnp_
    b = {k: jnp_.asarray(v) for k, v in data.next_batch().items()}
    p2, st2, metrics = tstep(params, st, consts, b)
    assert np.isfinite(float(metrics["loss"]))


def test_compressed_dp_step_trains():
    """Hierarchical DP with int8 cross-pod gradient compression: loss must
    decrease and params stay finite (DESIGN §4 pod-axis compression)."""
    from repro.optim import optimizers as opt_lib
    from repro.train import step as step_lib
    cfg = registry.get_smoke_config("llama_60m")
    api = registry.get_api(cfg)
    params, consts = api.init(cfg, jax.random.PRNGKey(0), seed=0)
    oc = OptimizerConfig(lr=1e-3, warmup_steps=2, total_steps=10)
    opt = opt_lib.make(oc)
    opt_state = opt.init(params)
    mesh = jax.make_mesh((1,), ("pod",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    step = jax.jit(step_lib.make_compressed_dp_step(cfg, api, opt, mesh))
    data = SyntheticC4(cfg.vocab_size, 32, 4, seed=0)
    losses = []
    for _ in range(10):
        b = {k: jnp.asarray(v) for k, v in data.next_batch().items()}
        params, opt_state, m = step(params, opt_state, consts, b)
        losses.append(float(m["loss"]))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0], losses


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_dir(env_dir, monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR, when set, is the only cache directory
    (JAX reads it; nothing else is set); otherwise one fixed directory in
    the checkout."""
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / env_dir))
    try:
        got = compile_cache.enable_compile_cache()
        if env_dir is None:
            root = os.path.dirname(os.path.dirname(os.path.abspath(
                __file__)))
            assert got == os.path.join(root, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            assert got == str(tmp_path / env_dir)
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
