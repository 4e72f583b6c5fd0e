"""Tests for the unified observability layer (ISSUE 7): the
repro.obs.metrics registry (counters/gauges/bucket histograms, labels,
snapshot, JSONL sink, jit-safety), repro.obs.trace (span recorder +
Chrome-trace validation), and the serve/train rewiring on top of them —
engine counter-view backward compatibility, per-request tick-span
geometry reproducing tick TTFT exactly, and trainer gauges."""
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import registry as model_registry
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.serve.engine import ServeEngine


# ---------------------------------------------------------------------------
# metrics: instruments
# ---------------------------------------------------------------------------

def test_counter_gauge_basics_and_labels():
    reg = obs_metrics.Registry()
    c = reg.counter("t.count")
    c.inc()
    c.inc(3)
    assert c.value == 4 and isinstance(c.value, int)
    with pytest.raises(ValueError):
        c.inc(-1)

    d = reg.counter("t.disp")
    d.labels(phase="prefill").inc(2)
    d.labels(phase="decode").inc()
    # family value aggregates the children; same labels → same child
    assert d.value == 3
    assert d.labels(phase="prefill").value == 2
    assert d.labels(phase="prefill") is d.labels(phase="prefill")

    g = reg.gauge("t.gauge")
    assert g.value is None
    g.set(2.5)
    assert g.value == 2.5

    snap = reg.snapshot()
    assert snap["t.count"] == {"type": "counter", "value": 4}
    assert snap["t.disp{phase=decode}"]["value"] == 1
    assert snap["t.disp{phase=prefill}"]["value"] == 2
    assert snap["t.gauge"]["value"] == 2.5
    json.dumps(snap)  # plain-JSON contract

    # get-or-create is idempotent; type conflicts are loud
    assert reg.counter("t.count") is c
    with pytest.raises(TypeError):
        reg.gauge("t.count")


def test_registry_reset_keeps_handles_live():
    reg = obs_metrics.Registry()
    c = reg.counter("t.c")
    h = reg.histogram("t.h", buckets=obs_metrics.tick_buckets(8))
    c.inc(5)
    h.observe(3)
    reg.reset()
    assert c.value == 0 and h.count == 0
    c.inc()  # the cached handle still feeds the registered instrument
    assert reg.snapshot()["t.c"]["value"] == 1


def test_histogram_percentiles_exact_for_integer_ticks():
    """On unit-width integer buckets the bucket-count reconstruction is
    numpy-equivalent: every sample sits exactly at its bucket bound."""
    rng = np.random.default_rng(0)
    data = rng.integers(0, 500, size=317)
    h = obs_metrics.Histogram("t.ticks", buckets=obs_metrics.tick_buckets())
    for v in data:
        h.observe(int(v))
    assert h.count == len(data)
    for q in (0, 25, 50, 90, 99, 100):
        assert h.percentile(q) == float(np.percentile(data, q)), q


def test_histogram_percentiles_within_bucket_width_for_floats():
    rng = np.random.default_rng(1)
    data = rng.lognormal(1.0, 1.5, size=400)  # ms-ish latencies
    bounds = obs_metrics.ms_buckets()
    h = obs_metrics.Histogram("t.ms", buckets=bounds)
    for v in data:
        h.observe(float(v))
    for q in (50, 90, 99):
        est, ref = h.percentile(q), float(np.percentile(data, q))
        # the estimate sits at/under its bucket's upper bound and the true
        # value lies in the same (or an interpolated-adjacent) bucket
        i = np.searchsorted(bounds, ref)
        lo = 0.0 if i == 0 else bounds[i - 1]
        hi = bounds[min(i, len(bounds) - 1)]
        assert lo <= est <= hi * (1 + 1e-12), (q, est, ref, lo, hi)


def test_histogram_edge_cases_and_bucket_conflicts():
    reg = obs_metrics.Registry()
    h = reg.histogram("t.h", buckets=(1.0, 2.0))
    assert math.isnan(h.percentile(50))
    h.observe(99.0)  # overflow bucket, represented at the last bound
    assert h.percentile(50) == 2.0
    row = reg.snapshot()["t.h"]
    assert row["buckets"] == [["+Inf", 1]] and row["count"] == 1
    with pytest.raises(ValueError):
        reg.histogram("t.h", buckets=(1.0, 3.0))


def test_instruments_reject_tracers_accept_concrete_jax():
    """The jit-safety contract: concrete jax arrays coerce (host transfer
    at the call site), tracers raise instead of leaking into host state."""
    reg = obs_metrics.Registry()
    c = reg.counter("t.c")
    c.inc(jnp.asarray(2.0))
    reg.gauge("t.g").set(jax.jit(lambda x: x * 2)(jnp.float32(1.5)))
    assert c.value == 2 and reg.gauge("t.g").value == 3.0

    def traced(x):
        c.inc(x)  # x is a tracer here
        return x

    with pytest.raises(TypeError, match="tracer|coerced"):
        jax.jit(traced)(jnp.float32(1.0))
    assert c.value == 2  # nothing leaked


def test_write_jsonl_appends_parseable_lines(tmp_path):
    reg = obs_metrics.Registry()
    reg.counter("t.c").inc(7)
    path = tmp_path / "m.jsonl"
    reg.write_jsonl(str(path), extra={"step": 1})
    reg.counter("t.c").inc()
    reg.write_jsonl(str(path), extra={"step": 2})
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert [l["step"] for l in lines] == [1, 2]
    assert lines[0]["metrics"]["t.c"]["value"] == 7
    assert lines[1]["metrics"]["t.c"]["value"] == 8
    assert all("ts" in l for l in lines)


def test_prometheus_text_counters_gauges_and_sanitization():
    reg = obs_metrics.Registry()
    reg.counter("serve.dispatches", help="jit dispatches").inc(3)
    g = reg.gauge("train.loss")
    g.set(1.5)
    reg.gauge("train.unset")           # never set → no sample line
    reg.counter("0weird-name").inc()
    text = reg.to_prometheus_text()
    lines = text.splitlines()
    assert text.endswith("\n")
    # names sanitized: dots/dashes → _, leading digit prefixed
    assert "# HELP serve_dispatches jit dispatches" in lines
    assert "# TYPE serve_dispatches counter" in lines
    assert "serve_dispatches 3" in lines
    assert "train_loss 1.5" in lines
    assert "_0weird_name 1" in lines
    # unset gauge: TYPE header only, no sample
    assert "# TYPE train_unset gauge" in lines
    assert not any(l.startswith("train_unset ") for l in lines)
    # families render in sorted name order
    assert lines.index("# TYPE _0weird_name counter") < \
        lines.index("# TYPE serve_dispatches counter")


def test_prometheus_text_label_escaping_and_ordering():
    reg = obs_metrics.Registry()
    c = reg.counter("t.labeled")
    # labels are stored sorted by key regardless of kwargs order, and
    # values escape backslash, quote, and newline per the text format
    c.labels(zeta="z", alpha='say "hi"\n\\end').inc(2)
    c.labels(zeta="other", alpha="a").inc()
    text = reg.to_prometheus_text()
    assert ('t_labeled{alpha="say \\"hi\\"\\n\\\\end",zeta="z"} 2'
            in text.splitlines())
    assert 't_labeled{alpha="a",zeta="other"} 1' in text.splitlines()
    # the two children each get exactly one sample line; no parent sample
    assert sum(l.startswith("t_labeled{") for l in text.splitlines()) == 2
    assert not any(l.startswith("t_labeled ") for l in text.splitlines())


def test_prometheus_text_histogram_cumulative_buckets():
    reg = obs_metrics.Registry()
    h = reg.histogram("t.lat", buckets=(1.0, 2.0, 5.0))
    for v in (0.5, 1.5, 1.7, 4.0, 99.0):   # one overflow sample
        h.observe(v)
    lines = reg.to_prometheus_text().splitlines()
    assert "# TYPE t_lat histogram" in lines
    # le buckets are CUMULATIVE and end at +Inf == _count
    assert 't_lat_bucket{le="1"} 1' in lines
    assert 't_lat_bucket{le="2"} 3' in lines
    assert 't_lat_bucket{le="5"} 4' in lines
    assert 't_lat_bucket{le="+Inf"} 5' in lines
    assert "t_lat_count 5" in lines
    assert any(l.startswith("t_lat_sum 106.7") for l in lines)
    # a labeled histogram emits per-child series with the le label LAST
    h2 = reg.histogram("t.lab", buckets=(1.0,))
    h2.labels(phase="x").observe(0.5)
    lines = reg.to_prometheus_text().splitlines()
    assert 't_lab_bucket{phase="x",le="1"} 1' in lines
    assert 't_lab_bucket{phase="x",le="+Inf"} 1' in lines
    assert 't_lab_count{phase="x"} 1' in lines


# ---------------------------------------------------------------------------
# trace: spans + schema validation
# ---------------------------------------------------------------------------

def test_trace_spans_export_and_validate(tmp_path):
    tr = obs_trace.Trace(enabled=True)
    with tr.span("phase.a", cat="test", detail=1):
        with tr.span("phase.b", cat="test"):
            pass
    tr.instant("marker", note="x")
    tr.thread_name(7, "request 7")
    tr.event("tick.span", ts_us=1000, dur_us=2000, tid=7, cat="request")
    doc = tr.to_dict()
    assert obs_trace.validate(doc) == 5
    names = [e["name"] for e in doc["traceEvents"]]
    assert {"phase.a", "phase.b", "marker", "thread_name",
            "tick.span"} <= set(names)
    a = next(e for e in doc["traceEvents"] if e["name"] == "phase.a")
    b = next(e for e in doc["traceEvents"] if e["name"] == "phase.b")
    assert a["ph"] == "X" and a["args"] == {"detail": 1}
    # nesting: b opens after a and closes before it
    assert a["ts"] <= b["ts"] and b["ts"] + b["dur"] <= a["ts"] + a["dur"]

    path = tmp_path / "trace.json"
    assert tr.export(str(path)) == 5
    assert obs_trace.validate_file(str(path)) == 5


def test_trace_disabled_is_noop_and_validation_catches_garbage():
    tr = obs_trace.Trace(enabled=False)
    with tr.span("x"):
        pass
    tr.instant("y")
    tr.event("z", ts_us=0, dur_us=1, tid=1)
    assert tr.events == []
    with pytest.raises(ValueError):
        obs_trace.validate({"traceEvents": []})
    with pytest.raises(ValueError):  # missing tid
        obs_trace.validate([{"name": "a", "ph": "X", "ts": 0, "pid": 1,
                             "dur": 1}])
    with pytest.raises(ValueError):  # complete event without dur
        obs_trace.validate([{"name": "a", "ph": "X", "ts": 0, "pid": 1,
                             "tid": 1}])
    with pytest.raises(ValueError):  # negative timestamp
        obs_trace.validate([{"name": "a", "ph": "i", "ts": -1, "pid": 1,
                             "tid": 1}])


# ---------------------------------------------------------------------------
# engine rewiring: counter views, reset, request trace geometry
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def model():
    cfg = model_registry.get_smoke_config("llama_60m")
    api = model_registry.get_api(cfg)
    params, consts = api.init(cfg, jax.random.PRNGKey(0), seed=0)
    return cfg, api, params, consts


def test_engine_counter_views_backward_compatible(model):
    cfg, api, params, consts = model
    eng = ServeEngine(cfg, params, consts, n_slots=2, max_len=32, paged=True)
    eng.submit([5, 9, 11], max_new_tokens=3)
    eng.run_until_drained()

    # the three legacy dicts read exactly as before through MetricView
    assert eng.dispatches["prefill"] == 1
    assert eng.dispatches["decode"] > 0
    assert dict(eng.prefill_traffic) == {"tokens_total": 3,
                                         "tokens_prefilled": 3,
                                         "tokens_shared": 0}
    assert set(eng.kv_traffic) == {"steps", "gather_tokens", "live_tokens",
                                   "resident_tokens", "active_slots"}
    assert all(isinstance(v, int) for v in dict(eng.kv_traffic).values())

    # ... but they are views now: no assignment, no item mutation
    with pytest.raises(AttributeError):
        eng.dispatches = {"prefill": 0, "decode": 0}
    with pytest.raises(TypeError):
        eng.dispatches["prefill"] = 0

    eng.reset_metrics()
    assert dict(eng.dispatches) == {"prefill": 0, "decode": 0}
    assert eng.obs.histogram("serve.ttft_ticks").count == 0
    assert eng.clock == 0 and eng.completed == []

    # the engine still serves correctly after a reset
    r = eng.submit([5, 9, 11], max_new_tokens=3)
    eng.run_until_drained()
    assert len(r.out) == 3 and eng.dispatches["prefill"] == 1


def test_engine_histograms_and_wall_stamps(model):
    cfg, api, params, consts = model
    eng = ServeEngine(cfg, params, consts, n_slots=2, max_len=32, paged=True)
    arrivals = [0, 1, 3]
    reqs = [eng.submit([7, 3, 2, 8][: 2 + i], max_new_tokens=3, arrival=a)
            for i, a in enumerate(arrivals)]
    eng.run_stream()

    ht = eng.obs.histogram("serve.ttft_ticks")
    assert ht.count == len(reqs)
    ticks = np.array([r.t_first - r.arrival for r in reqs], np.float64)
    assert ht.percentile(50) == float(np.percentile(ticks, 50))
    assert ht.percentile(99) == float(np.percentile(ticks, 99))

    hw = eng.obs.histogram("serve.ttft_wall_ms")
    assert hw.count == len(reqs) and hw.sum > 0
    for r in reqs:
        assert r.wall_arrival is not None
        assert r.wall_first is not None and r.wall_done is not None
        assert r.wall_arrival <= r.wall_first <= r.wall_done
    # scheduler instruments share the engine registry
    snap = eng.obs.snapshot()
    assert snap["serve.sched.admitted_batch"]["count"] > 0
    assert snap["serve.requests.completed"]["value"] == len(reqs)


def test_engine_request_trace_reproduces_tick_ttft(model):
    """Acceptance: exported per-request spans, laid out at TICK_US per
    engine tick, reproduce each request's tick TTFT exactly."""
    cfg, api, params, consts = model
    tr = obs_trace.Trace(enabled=True)
    eng = ServeEngine(cfg, params, consts, n_slots=2, max_len=32, paged=True,
                      trace=tr)
    reqs = [eng.submit([5, 9, 11, 4][: 2 + i % 2], max_new_tokens=3,
                       arrival=i) for i in range(4)]
    eng.run_stream()

    doc = tr.to_dict()
    obs_trace.validate(doc)
    for req in reqs:
        lane = {e["name"]: e for e in doc["traceEvents"]
                if e.get("tid") == req.uid and e.get("cat") == "request"}
        assert {"queued", "prefill", "decode"} <= set(lane)
        q, pf, dec = lane["queued"], lane["prefill"], lane["decode"]
        # ttft = end of prefill minus start of queued, in ticks
        ttft_trace = (pf["ts"] + pf["dur"] - q["ts"]) / obs_trace.TICK_US
        assert ttft_trace == req.t_first - req.arrival, req.uid
        # lifecycle spans tile the request's lifetime contiguously
        assert q["ts"] == req.arrival * obs_trace.TICK_US
        assert q["ts"] + q["dur"] == pf["ts"]
        assert pf["ts"] + pf["dur"] == dec["ts"]
        assert dec["ts"] + dec["dur"] == req.t_done * obs_trace.TICK_US
        assert pf["args"]["ttft_ticks"] == req.t_first - req.arrival
    # engine phase spans rode along on the wall clock
    phases = {e["name"] for e in doc["traceEvents"]
              if e.get("cat") == "engine"}
    assert {"serve.admission", "serve.prefill_dispatch",
            "serve.decode_dispatch", "serve.block_until_ready"} <= phases


# ---------------------------------------------------------------------------
# trainer rewiring
# ---------------------------------------------------------------------------

def test_trainer_gauges_spans_and_jsonl(tmp_path):
    import dataclasses

    from repro.configs.base import OptimizerConfig, TrainConfig
    from repro.train.trainer import Trainer

    cfg = dataclasses.replace(model_registry.get_smoke_config("llama_60m"),
                              dtype="float32")
    tc = TrainConfig(model=cfg, steps=3, seq_len=16, global_batch=2,
                     log_every=1, ckpt_every=0,
                     ckpt_dir=str(tmp_path / "ckpt"),
                     optim=OptimizerConfig(name="adamw", lr=1e-3,
                                           warmup_steps=2, total_steps=3))
    tr = obs_trace.Trace(enabled=True)
    mpath = tmp_path / "metrics.jsonl"
    t = Trainer(tc, log_fn=lambda *_: None, trace=tr,
                metrics_out=str(mpath))
    t.run()

    snap = t.obs.snapshot()
    assert snap["train.steps"]["value"] == 3
    assert snap["train.tokens"]["value"] == 3 * 2 * 16
    assert snap["train.loss"]["value"] == pytest.approx(
        t.metrics_history[-1]["loss"])
    assert snap["train.lr"]["value"] == pytest.approx(
        t.metrics_history[-1]["lr"])
    # the dense 6ND MFU gauge is gone: the benchmark's train_mfu counts
    # the factored model's own FLOPs from the device trace
    assert "train.mfu" not in snap
    assert snap["train.tokens_per_sec"]["value"] > 0
    assert snap["train.step_ms"]["count"] == 3
    for phase in ("data", "dispatch", "sync"):
        assert snap[f"train.phase_ms{{phase={phase}}}"]["count"] == 3

    lines = [json.loads(l) for l in mpath.read_text().splitlines()]
    assert [l["step"] for l in lines] == [1, 2, 3]

    obs_trace.validate(tr.to_dict())
    steps = [e for e in tr.events if e["name"] == "train.step"]
    assert [e["args"]["step"] for e in steps] == [1, 2, 3]
    for sub in ("train.data", "train.dispatch", "train.sync"):
        assert sum(e["name"] == sub for e in tr.events) == 3
