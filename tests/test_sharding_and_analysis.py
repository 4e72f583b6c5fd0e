"""Sharding-rule unit tests + HLO cost-walker validation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import hlo_parser, roofline
from repro.dist import sharding as shl
from repro.models import registry


@pytest.fixture(scope="module")
def mesh4():
    # 1-device "mesh" with 4 logical axes is impossible; use (1,1) named mesh
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def test_param_specs_cover_every_leaf(mesh4):
    for arch in ("yi_34b", "qwen3_moe_235b", "zamba2_7b", "xlstm_350m",
                 "whisper_large_v3"):
        cfg = registry.get_config(arch)
        api = registry.get_api(cfg)
        params, consts = api.init(cfg, key=None)   # abstract — no alloc
        specs = shl.param_specs(params, mesh4)
        for (path, leaf), (_, spec) in zip(
                jax.tree_util.tree_flatten_with_path(params)[0],
                jax.tree_util.tree_flatten_with_path(specs)[0]):
            assert isinstance(spec, P)
            assert len(spec) <= leaf.ndim, (path, spec, leaf.shape)


def test_divisibility_guard():
    """Axes that don't divide fall back to replication, never crash."""
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    leaf = jax.ShapeDtypeStruct((56, 17), jnp.float32)  # 17 indivisible

    class FakeKey:
        def __init__(self, key):
            self.key = key
    spec = shl.spec_for_param((FakeKey("attn"), FakeKey("wq"),
                               FakeKey("w")), leaf, mesh)
    assert isinstance(spec, P)


def test_batch_specs_shard_when_divisible():
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    b = {"tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32)}
    specs = shl.batch_specs(b, mesh, ("data",))
    assert specs["tokens"][0] in (("data",), "data")
    b1 = {"tokens": jax.ShapeDtypeStruct((1, 16), jnp.int32)}
    # batch=1 with |data|=1 still divides; use a padded mesh impossible on
    # 1 CPU — the divisibility logic itself is unit-tested in dryrun.


# ---------------------------------------------------------------------------
# HLO cost walker
# ---------------------------------------------------------------------------

def test_walker_counts_scan_trips():
    def body(x, w):
        return jnp.tanh(x @ w), None
    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((9, 128, 128), jnp.float32)
    c = jax.jit(lambda x, w: jax.lax.scan(body, x, w)[0]).lower(x, ws
                                                                ).compile()
    pc = hlo_parser.analyze(c.as_text())
    expect = 9 * 2 * 64 * 128 * 128
    assert abs(pc.flops - expect) / expect < 0.01
    assert pc.dot_calls == 9
    assert 9 in pc.trip_counts.values()


def test_walker_matmul_flops_and_bytes():
    a = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    b = jax.ShapeDtypeStruct((512, 1024), jnp.float32)
    c = jax.jit(lambda a, b: a @ b).lower(a, b).compile()
    pc = hlo_parser.analyze(c.as_text())
    assert abs(pc.flops - 2 * 256 * 512 * 1024) / pc.flops < 0.01
    expect_b = 4 * (256 * 512 + 512 * 1024 + 256 * 1024)
    assert abs(pc.hbm_bytes - expect_b) / expect_b < 0.05


def test_walker_detects_remat_recompute():
    """remat=full must raise dot_calls vs no-remat (recompute detector)."""
    def blk(x, w):
        return jnp.tanh(x @ w) @ w.T

    def loss(ws, x):
        def body(h, w):
            return blk(h, w), None
        h, _ = jax.lax.scan(body, x, ws)
        return jnp.sum(h)

    def loss_remat(ws, x):
        def body(h, w):
            return jax.checkpoint(blk)(h, w), None
        h, _ = jax.lax.scan(body, x, ws)
        return jnp.sum(h)

    ws = jax.ShapeDtypeStruct((4, 64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    d_plain = hlo_parser.analyze(
        jax.jit(jax.grad(loss)).lower(ws, x).compile().as_text()).dot_calls
    d_remat = hlo_parser.analyze(
        jax.jit(jax.grad(loss_remat)).lower(ws, x).compile().as_text()
    ).dot_calls
    assert d_remat > d_plain


def test_collective_wire_bytes_ring_factors():
    txt = """HloModule m, entry_computation_layout={(f32[1024]{0})->f32[1024]{0}}

ENTRY %main (p: f32[1024]) -> f32[1024] {
  %p = f32[1024]{0} parameter(0)
  ROOT %ar = f32[1024]{0} all-reduce(%p), replica_groups=[16,16]<=[256], to_apply=%add
}
"""
    pc = hlo_parser.analyze(txt)
    per = 2 * 15 / 16 * 4096
    assert abs(pc.wire_bytes - per * 16 * 16) < 1.0
    assert pc.coll_counts == {"all-reduce": 1}


def test_roofline_terms_and_bottleneck():
    rl = roofline.Roofline(flops=1e15, hbm_bytes=1e12, wire_bytes=1e12,
                           chips=256, model_flops=5e14)
    assert rl.t_compute == pytest.approx(
        1e15 / (256 * roofline.PEAKS[roofline.V5E].flops))
    assert rl.bottleneck in ("compute", "memory", "collective")
    assert 0 < rl.roofline_fraction <= 1.0
    assert rl.useful_flops_ratio == pytest.approx(0.5)


class _Dev:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


@pytest.mark.parametrize("platform,kind,flops", [
    ("tpu", "TPU v5 lite", 197e12),     # v5e, the table's own row
    ("cpu", "cpu", None),               # no device metric on the CPU
])
def test_peaks_by_device_kind(platform, kind, flops):
    peaks = roofline.peaks_for(_Dev(platform, kind))
    assert (peaks and peaks.flops) == flops
    if peaks is not None:
        assert peaks.hbm_bw == 819e9 and peaks.source


def test_peaks_unknown_accelerator_raises():
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.peaks_for(_Dev("tpu", "TPU v99"))


def test_model_flops_moe_uses_active_params():
    dense = registry.get_config("yi_34b")
    moe = registry.get_config("qwen3_moe_235b")
    tot_d, act_d = roofline.param_count_active(dense)
    tot_m, act_m = roofline.param_count_active(moe)
    assert tot_d == act_d                       # dense: all params active
    assert act_m < 0.25 * tot_m                 # 235B total / 22B active
