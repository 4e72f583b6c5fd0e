"""Trace reduction: busy/idle union, gap labels and per-op time on a
synthesized trace, and the reader on a trace recorded here on the CPU."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench import tracing  # noqa: E402
from chipbench.tracing import Event, Line, Plane  # noqa: E402

MS = 1e6


def ev(name, start_ms, dur_ms, **stats):
    return Event(name, start_ms * MS, dur_ms * MS, stats)


def synthetic():
    host = Plane("/host:CPU", [
        Line("python", [ev(tracing.WINDOW, 0, 100),
                        ev("train_step", 0, 60), ev("next_batch", 60, 30),
                        ev("inner", 65, 5)]),
    ])
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [ev("jit_train_step", 5, 50)]),
        Line(tracing.OPS_LINE, [
            ev("%while.3 = (s32[]) while(...)", 5, 30),      # a loop ...
            ev("%fusion.1 = f32[8] fusion(...)", 5, 15),     # ... its body
            ev("%sl_matmul.7 = f32[8] custom-call(...)", 20, 15),
            ev("%fusion.1 = f32[8] fusion(...)", 40, 10),
            ev("%late.2 = f32[8] fusion(...)", 95, 10),      # crosses the end
        ]),
    ])
    custom = Plane("/device:CUSTOM:0", [Line(tracing.OPS_LINE,
                                             [ev("x", 0, 100)])])
    return [host, dev, custom]


def test_union_and_gaps():
    merged = tracing.union([(5, 25), (20, 35), (40, 50), (95, 105)], 0, 100)
    assert merged == [(5, 35), (40, 50), (95, 100)]
    assert tracing.gaps(merged, 0, 100) == [(0, 5), (35, 40), (50, 95)]


def test_op_names():
    e = ev("%sl_matmul.167 = f32[2048,5120]{1,0} custom-call(bf16[2048])", 0, 1)
    assert tracing.op_name(e) == "sl_matmul.167"
    assert tracing.base_name("sl_matmul.167") == "sl_matmul"
    assert tracing.base_name("copy") == "copy"


def test_device_planes_by_name():
    assert tracing.is_device_plane("/device:TPU:0")
    assert tracing.is_device_plane("/device:TPU:3")
    assert not tracing.is_device_plane("/device:CUSTOM:0")
    assert not tracing.is_device_plane("/host:CPU")


def test_reduce_synthetic_trace():
    red = tracing.reduce(synthetic())
    assert red["window_s"] == pytest.approx(0.1)
    # device busy inside [0, 100] ms: 5-35, 40-50, 95-100 = 45 ms
    assert red["busy_s"] == pytest.approx(0.045)
    assert red["devices"] == 1
    # innermost ops wholly inside the window, by short name
    assert red["op_seconds"] == pytest.approx(
        {"fusion.1": 0.025, "sl_matmul.7": 0.015})
    ops = dict(red["breakdown"]["device_ops"])
    assert list(ops) == ["fusion.1", "sl_matmul.7"]
    gaps = dict(red["breakdown"]["idle_gaps"])
    # 50-95 ms: midpoint 72.5 lies in next_batch (60-90), not in inner
    assert gaps == pytest.approx({"next_batch": 0.045, "train_step": 0.010})


def test_window_is_required():
    planes = synthetic()
    planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError, match="no host span"):
        tracing.reduce(planes)


def test_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(tracing.WINDOW):
            for _ in range(3):
                f(x).block_until_ready()
    planes = tracing.load(tracing.find_xplane(str(tmp_path)))
    lo, hi = tracing.window_of(planes)
    assert hi > lo
    red = tracing.reduce(planes)
    assert red["window_s"] == pytest.approx((hi - lo) * 1e-9)
    # the CPU has no device plane: nothing is read as device time
    assert red["devices"] == 0 and red["busy_s"] == 0.0
