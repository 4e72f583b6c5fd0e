"""Tests for the span recorder's process-level use: the ring bound, parent
ids, the profiler annotation each span opens, JAX's compile events, the
trainer's step spans in the process recorder, and the tile-table span."""
import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import get_registry, get_trace
from repro.obs import trace as obs_trace


def test_ring_keeps_newest_events(monkeypatch):
    monkeypatch.setattr(obs_trace, "CAPACITY", 8)
    tr = obs_trace.Trace()
    for i in range(20):
        tr.instant("tick", i=i)
    assert [e["args"]["i"] for e in tr.events] == list(range(12, 20))
    with tr.span("last"):
        pass
    assert len(tr.events) == 8 and tr.events[-1]["name"] == "last"
    assert obs_trace.validate(tr.to_dict()) == 8


def test_spans_record_parent_ids_per_thread():
    tr = obs_trace.Trace()
    with tr.span("outer"):
        with tr.span("inner", k=1):
            tr.complete("measured", 0, 10)
        with tr.span("sibling"):
            pass
        # another thread's span has its own stack: no parent here
        t = threading.Thread(target=lambda: tr.span("other").__enter__()
                             .__exit__(None, None, None))
        t.start()
        t.join(10)
        assert not t.is_alive()
    ev = {e["name"]: e for e in tr.events}
    outer = ev["outer"]
    assert "parent" not in outer
    assert ev["inner"]["parent"] == outer["id"]
    assert ev["sibling"]["parent"] == outer["id"]
    assert ev["measured"]["parent"] == ev["inner"]["id"]
    assert ev["inner"]["args"] == {"k": 1}
    assert "parent" not in ev["other"]
    assert len({e["id"] for e in tr.events}) == len(tr.events)


def test_span_opens_profiler_annotation(tmp_path):
    from jax.profiler import ProfileData

    tr = obs_trace.Trace()
    with jax.profiler.trace(str(tmp_path)):
        with tr.span("rec.outer", step_num=7):
            with tr.span("rec.inner"):
                jnp.ones(4).block_until_ready()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    host = [e for p in ProfileData.from_file(str(path)).planes
            if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events]
    got = {e.name: e for e in host if e.name.startswith("rec.")}
    assert set(got) == {"rec.outer", "rec.inner"}
    outer, inner = got["rec.outer"], got["rec.inner"]
    assert outer.start_ns <= inner.start_ns
    assert inner.start_ns + inner.duration_ns <= \
        outer.start_ns + outer.duration_ns
    assert dict(outer.stats).get("step_num") in (7, "7")
    # the recorder's own events are there too
    assert [e["name"] for e in tr.events] == ["rec.inner", "rec.outer"]


def test_compile_listener_records_new_jits_once():
    tr = get_trace()
    count = get_registry().counter("jax.compiles").labels(kind="compile")

    def compiles():
        return [e for e in tr.events if e["name"] == "jax.compile"
                and "listener_probe" in e["args"]["fun_name"]]

    def listener_probe(x):
        return x * 3 + 1

    f = jax.jit(listener_probe)
    x = jnp.arange(5.0).block_until_ready()
    before, n0 = len(compiles()), count.value
    f(x).block_until_ready()
    assert len(compiles()) == before + 1
    assert count.value == n0 + 1
    ev = compiles()[-1]
    assert ev["cat"] == "jax" and ev["dur"] > 0
    traced = [e for e in tr.events if e["name"] == "jax.trace"
              and "listener_probe" in e["args"]["fun_name"]]
    assert traced and traced[-1]["ts"] <= ev["ts"]
    f(x).block_until_ready()
    assert len(compiles()) == before + 1 and count.value == n0 + 1


def test_trainer_records_step_children_into_process_recorder(tmp_path):
    from repro.configs.base import OptimizerConfig, TrainConfig
    from repro.models import registry as model_registry
    from repro.train.trainer import Trainer

    cfg = dataclasses.replace(model_registry.get_smoke_config("llama_60m"),
                              dtype="float32")
    tc = TrainConfig(model=cfg, steps=3, seq_len=16, global_batch=2,
                     log_every=1, ckpt_every=0,
                     ckpt_dir=str(tmp_path / "ckpt"),
                     optim=OptimizerConfig(name="adamw", lr=1e-3,
                                           warmup_steps=2, total_steps=3))
    t = Trainer(tc, log_fn=lambda *_: None)
    assert t.trace is get_trace()
    t.run()
    events = get_trace().events
    steps = [e for e in events if e["name"] == "train.step"][-3:]
    assert [e["args"]["step"] for e in steps] == [1, 2, 3]
    phases = ("train.data", "train.dispatch", "train.sync", "train.readback")
    for step in steps:
        kids = sorted((e for e in events if e.get("parent") == step["id"]
                       and e["name"] in phases), key=lambda e: e["ts"])
        assert [e["name"] for e in kids] == list(phases)
        end = step["ts"] + step["dur"]
        for a, b in zip(kids, kids[1:]):
            assert a["ts"] + a["dur"] <= b["ts"]
        assert step["ts"] <= kids[0]["ts"] and \
            kids[-1]["ts"] + kids[-1]["dur"] <= end
    # the step's first dispatch compiled the step program inside it
    first_dispatch = next(e for e in events if e["name"] == "train.dispatch"
                          and e.get("parent") == steps[0]["id"])
    assert any(e["name"] == "jax.compile"
               and e.get("parent") == first_dispatch["id"] for e in events)
    # tokens/s covers data + dispatch + sync, never more than the step
    tps = t.obs.gauge("train.tokens_per_sec").value
    assert tps >= 2 * 16 / (steps[-1]["dur"] * 1e-6)


def test_tile_tables_span():
    from repro.core import support
    from repro.kernels import ops

    d_in, d_out, k = 256, 384, 4
    rng = np.random.default_rng(0)
    rows = np.repeat(np.arange(d_in, dtype=np.int32), k)
    cols = np.concatenate([rng.choice(d_out, k, replace=False)
                           for _ in range(d_in)]).astype(np.int32)
    cap = support.tile_cap(d_in, d_out, k / d_out)
    n0 = sum(e["name"] == "sl.tile_tables" for e in get_trace().events)
    out = ops.prepare_tile_consts(rows, cols, d_in, d_out, pad=cap)
    spans = [e for e in get_trace().events if e["name"] == "sl.tile_tables"]
    assert len(spans) == n0 + 1
    assert spans[-1]["args"] == {"d_in": d_in, "d_out": d_out}
    assert spans[-1]["dur"] > 0
    assert out["perm"].shape == (2, 3, cap)


def test_disabled_process_recorder_records_nothing(monkeypatch):
    tr = get_trace()
    monkeypatch.setattr(tr, "enabled", False)
    n = len(tr.events)
    with tr.span("off"):
        jax.jit(lambda x: x - 7)(jnp.ones(3)).block_until_ready()
    assert len(tr.events) == n


def test_complete_clamps_to_the_epoch():
    """A compile that began before the recorder did starts at its epoch,
    so the exported trace keeps non-negative stamps."""
    tr = obs_trace.Trace()
    tr.complete("early", 0, tr._epoch_ns + 5_000)
    (ev,) = tr.events
    assert ev["ts"] == 0.0 and ev["dur"] == pytest.approx(5.0)
    obs_trace.validate(tr.to_dict())
