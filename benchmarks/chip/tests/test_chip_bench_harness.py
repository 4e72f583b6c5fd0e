"""The harness end to end on the CPU at a tiny size: a cell, a config and a
per-layer metric added by new files only; a sound run reads correct; the
timed path broken underneath reads not correct; no chip, or no program,
means a non-zero exit and no result line."""
import json
import os
import shutil
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import chip_bench_tiny as tiny  # noqa: E402
from chipbench import harness  # noqa: E402


def test_new_cell_config_and_metric_are_found_by_name(tmp_path):
    root = tiny.make_root(tmp_path)
    chip = os.path.join(root, "benchmarks", "chip")
    with open(os.path.join(chip, "metrics", "tiny_steps.train.py"), "w") as f:
        f.write("def read(ctx):\n    return ctx.get('steps')\n")
    bench = tiny.read(os.path.join(root, "BENCHMARK.json"))
    bench["per_layer"].append({
        "name": "tiny_steps.train", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "loop",
        "moves": "train_tokens_per_s", "workloads": [tiny.CELL]})
    tiny.write(os.path.join(root, "BENCHMARK.json"), bench)

    cell = harness.Cell(tiny.CELL, root=root)
    assert cell.config["hidden_size"] == 64
    assert cell.traffic["seq_len"] == 32
    assert hasattr(cell.job_module(), "Job")
    assert {m["name"] for m in cell.end_to_end()} >= {
        "train_tokens_per_s", "setup_s"}
    got = harness.read_layers(cell, {"job": "train", "steps": 7})
    # the new reader reads; readers that find nothing leave their metric out
    assert got == {"tiny_steps.train": {"value": 7.0, "unit": "steps"}}


def test_sound_fused_run_is_correct(tmp_path):
    """The fused Trainer step agrees with the plain f32 reference."""
    res = tiny.run_cell(tiny.make_root(tmp_path, exec_mode="fused"))
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"train_tokens_per_s",
                                   "train_peak_hbm_gib", "setup_s"}
    assert res["device"]["platform"] == "cpu"


def _broken_state_unchanged(monkeypatch):
    from repro.train import step as step_lib
    real = step_lib.make_train_step

    def make(*a, **k):
        f = real(*a, **k)

        def step(params, opt_state, consts, batch):
            return (params, opt_state) + (f(params, opt_state, consts,
                                            batch)[2],)
        return step
    monkeypatch.setattr(step_lib, "make_train_step", make)


def _broken_half_batch(monkeypatch):
    from repro.train import step as step_lib
    real = step_lib.cross_entropy

    def ce(logits, labels, vocab_size):
        n = logits.shape[0] // 2
        return real(logits[:n], labels[:n], vocab_size)
    monkeypatch.setattr(step_lib, "cross_entropy", ce)


@pytest.mark.parametrize("fault", [_broken_state_unchanged,
                                   _broken_half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_broken_timed_path_is_not_correct(tmp_path, monkeypatch, fault):
    fault(monkeypatch)
    res = tiny.run_cell(tiny.make_root(tmp_path, exec_mode="dense"))
    assert not res["correct"], res["checks"]


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "qwen2.5-32b.pretrain_fused_adamw", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_chip_exits_nonzero_without_a_result():
    p = _run_py(tiny.REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs 1 TPU chip" in p.stderr


def test_benchmark_alone_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(tiny.REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(tiny.CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(str(tmp_path), {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_benchmark_json_names_existing_files():
    bench = tiny.read(os.path.join(tiny.REPO, "BENCHMARK.json"))
    for w in bench["workloads"]:
        cell = harness.Cell(w["name"])
        assert cell.job_module()
        assert set(cell.limits) == {"loss", "grad1", "change"}
        for m in cell.per_layer():
            assert hasattr(cell.reader(m["name"]), "read")
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    json.dumps(bench)
