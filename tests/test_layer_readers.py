"""The per-layer readers of the entry, loop and kernels layers
(``benchmarks/chip/metrics/*.py``) on a synthesized span list: compiles
before, inside and after the window, tile tables before it, the host
gap between one step's sync and the next step's dispatch, the SL
kernels' row blocks per call, and the share of the SL linears' layout
permutations that run as sorts."""
import importlib.util
import os

import pytest

import repro.obs

METRICS = os.path.join(os.path.dirname(__file__), os.pardir, "benchmarks",
                       "chip", "metrics")
READERS = ("setup_compile_s.train", "setup_tile_tables_s.train",
           "window_compiles.train", "trainer_host_gap_ms.train",
           "sl_row_blocks.train", "sl_permute_sort_share.train")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _Recorder:
    def __init__(self, events):
        self.events = events


def X(name, ts, dur, sid=None, parent=None, **args):
    ev = {"name": name, "ph": "X", "ts": float(ts), "dur": float(dur)}
    if sid is not None:
        ev["id"] = sid
    if parent is not None:
        ev["parent"] = parent
    if args:
        ev["args"] = args
    return ev


def row_blocks(ts, kernel, m, rows, n_blocks, tiles=40 * 216):
    """The ``sl.row_blocks`` instant ``kernels/ops`` records per traced
    SL kernel call."""
    return {"name": "sl.row_blocks", "ph": "i", "ts": float(ts),
            "args": {"kernel": kernel, "m": m, "rows": rows,
                     "row_blocks": n_blocks, "tiles": tiles}}


def permute(ts, pass_, how, slots=40 * 216 * 688, nnz=5120 * 829):
    """The ``sl.permute`` instant ``kernels/ops.sl_linear`` records per
    traced pass."""
    return {"name": "sl.permute", "ph": "i", "ts": float(ts),
            "args": {"pass": pass_, "how": how, "slots": slots, "nnz": nnz}}


def steps(first_id, t0, n, period=1000.0):
    """n train.step spans from t0 (us), with the trainer's four children:
    data 10, dispatch 20, sync 900, readback 30 us."""
    out = []
    for i in range(n):
        sid, t = first_id + 10 * i, t0 + i * period
        out += [X("train.data", t, 10, sid + 1, sid),
                X("train.dispatch", t + 10, 20, sid + 2, sid),
                X("train.sync", t + 30, 900, sid + 3, sid),
                X("train.readback", t + 930, 30, sid + 4, sid),
                X("train.step", t, 960, sid, step=i + 1)]
    return out


@pytest.fixture
def events(monkeypatch):
    evs = [
        X("jax.trace", 0, 400_000, 1),
        X("jax.trace", 100_000, 100_000, 2, 1),     # nested: inside id 1
        X("sl.tile_tables", 500_000, 250_000, 3),
        X("sl.tile_tables", 800_000, 50_000, 4),
        X("jax.lower", 900_000, 100_000, 5),
        X("jax.compile", 1_000_000, 2_000_000, 6, fun_name="step"),
    ]
    # three checked set-up steps, then a window of four
    evs += steps(100, 3_000_000, 3)
    evs += steps(200, 4_000_000, 4)
    evs.append(X("jax.compile", 4_001_500, 10, 300))     # inside the window
    evs.append(X("jax.compile", 3_999_995, 10, 301))     # overlaps its start
    evs.append(X("jax.compile", 9_000_000, 5_000, 302))  # after: harness's
    monkeypatch.setattr(repro.obs, "get_trace", lambda: _Recorder(evs))
    return evs


CTX = {"job": "train", "steps": 4, "trace": {"window_s": 0.004}}


def test_setup_compile_s_is_the_union_before_the_window(events):
    # trace 0-0.4 s (the nested trace inside it), lower 0.9-1.0 s,
    # compile 1.0-3.0 s; the set-up steps' spans are not compiles
    assert reader("setup_compile_s.train").read(CTX) == pytest.approx(2.5)


def test_setup_tile_tables_s_sums_spans_before_the_window(events):
    assert reader("setup_tile_tables_s.train").read(CTX) == \
        pytest.approx(0.3)


def test_window_compiles_counts_overlaps_only(events):
    assert reader("window_compiles.train").read(CTX) == 2.0


def test_window_compiles_reads_zero_on_a_clean_window(events):
    del events[-3:-1]
    assert reader("window_compiles.train").read(CTX) == 0.0


def test_trainer_host_gap_ms_mean_over_window_pairs(events):
    # sync of step i ends at t + 930 us; dispatch of step i+1 ends at
    # t + 1000 + 30 us: 100 us a step, over the window's three pairs
    assert reader("trainer_host_gap_ms.train").read(CTX) == \
        pytest.approx(0.1)


def test_trainer_host_gap_ms_bounds_by_the_window(events):
    # a slower last step: only pairs inside the window count
    last_dispatch = [e for e in events if e["name"] == "train.dispatch"][-1]
    last_dispatch["dur"] += 300
    assert reader("trainer_host_gap_ms.train").read(CTX) == \
        pytest.approx(0.2)
    assert reader("trainer_host_gap_ms.train").read(
        dict(CTX, steps=1)) is None


def test_sl_row_blocks_reads_one_when_each_call_has_one_block(events):
    # the step traced in set-up: forward, dx and dv of one linear
    events += [row_blocks(1_100_000, "sl_matmul", 2048, 2048, 1),
               row_blocks(1_100_010, "sddmm", 2048, 2048, 1),
               row_blocks(1_100_020, "sl_matmul", 2048, 2048, 1)]
    assert reader("sl_row_blocks.train").read(CTX) == 1.0


def test_sl_row_blocks_reads_the_mean_over_calls_above_the_cap(events):
    # 16 blocks of 128 rows (the old fixed block) and 3 above a cap;
    # an instant after the window (the harness's later compile) is not read
    events += [row_blocks(1_100_000, "sl_matmul", 2048, 128, 16),
               row_blocks(1_100_010, "sddmm", 1000, 384, 3),
               row_blocks(1_100_020, "sl_matmul", 64, 128, 1),
               row_blocks(9_000_000, "sl_matmul", 2048, 128, 16)]
    assert reader("sl_row_blocks.train").read(CTX) == pytest.approx(20 / 3)


def test_sl_row_blocks_reads_nothing_without_the_instants(events):
    # the window's spans are there; the parent's kernels record no instant
    assert reader("sl_row_blocks.train").read(CTX) is None


def test_sl_permute_sort_share_reads_the_share_of_sorts(events):
    # a sort forward twice, a gather forward once, a sort dv once; an
    # instant after the window (the harness's later compile) is not read
    events += [permute(1_100_000, "fwd", "sort"),
               permute(1_100_010, "fwd", "sort"),
               permute(1_100_020, "fwd", "gather"),
               permute(1_100_030, "dv", "sort"),
               permute(9_000_000, "dv", "scatter")]
    assert reader("sl_permute_sort_share.train").read(CTX) == \
        pytest.approx(75.0)


def test_sl_permute_sort_share_reads_nothing_without_the_instants(events):
    # the window's spans are there; the parent's linear records no instant
    events.append(row_blocks(1_100_000, "sl_matmul", 2048, 2048, 1))
    assert reader("sl_permute_sort_share.train").read(CTX) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_read_nothing_without_the_programs_spans(name, monkeypatch):
    monkeypatch.setattr(repro.obs, "get_trace", lambda: _Recorder([]))
    assert reader(name).read(CTX) is None
    monkeypatch.delattr(repro.obs, "get_trace")
    assert reader(name).read(CTX) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_read_only_a_traced_training_run(name, events):
    assert reader(name).read(dict(CTX, job="serve")) is None
    # an untraced run's context: the spans of earlier runs are not read
    assert reader(name).read({"job": "train", "steps": 4}) is None


def test_traced_tiny_cell_reports_the_new_metrics(tmp_path):
    """A whole traced run of the benchmark's tiny CPU cell: the readers
    find the trainer's, the tile tables' and JAX's events, and the window
    compiled nothing though the harness compiled after it."""
    spec = importlib.util.spec_from_file_location(
        "chip_bench_tiny", os.path.join(METRICS, os.pardir, "tests",
                                        "chip_bench_tiny.py"))
    tiny = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tiny)
    res = tiny.run_cell(tiny.make_root(tmp_path), trace=1)
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(READERS) <= set(got), got
    assert got["window_compiles.train"] == 0.0
    assert got["setup_compile_s.train"] > 0
    assert got["setup_tile_tables_s.train"] > 0
    assert got["trainer_host_gap_ms.train"] > 0
    assert got["sl_row_blocks.train"] == 1.0
    assert got["sl_permute_sort_share.train"] == 100.0
    assert res["correct"]
