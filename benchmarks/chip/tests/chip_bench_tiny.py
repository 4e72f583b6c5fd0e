"""A copy of the benchmark in a temporary checkout with one tiny training
cell added by new files only, for the CPU tests of the harness."""
from __future__ import annotations

import json
import os
import shutil
import sys

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(CHIP))
sys.path[:0] = [p for p in (CHIP, os.path.join(REPO, "src"))
                if p not in sys.path]

TINY = {"hidden_size": 64, "intermediate_size": 160,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "num_hidden_layers": 2, "vocab_size": 500}
CELL = "tiny.train"
#: the tiny cell's own limits, from CPU readings over seeds 2**31+1,
#: 2**31+2, 12345, 777, 2**32+3 and 99991: the fused program read at most
#: 1.0e-4 / 2.8e-3 / 0.034 (loss / grad1 / change, the last on the k bias,
#: whose gradient is small at this size), the float8 control at least
#: 7.6e-3 on grad1, and a loss over half the tokens at least 5.1e-3 /
#: 0.19 / 0.093; a state left unchanged reads 1 on change
TINY_LIMITS = {"loss": 4e-4, "grad1": 5e-3, "change": 0.1}
SEED = 2**31 + 1


def read(path):
    with open(path) as f:
        return json.load(f)


def write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


def make_root(tmp, exec_mode="fused", batch=2, limits=None) -> str:
    """A checkout at ``tmp`` holding BENCHMARK.json, the benchmark's files
    and a link to the program, plus the tiny cell's own new files."""
    root = str(tmp)
    shutil.copytree(CHIP, os.path.join(root, "benchmarks", "chip"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    chip = os.path.join(root, "benchmarks", "chip")
    cfg = read(os.path.join(CHIP, "configs", "qwen2.5-32b.json"))
    cfg.update(name="tiny", **TINY)
    cfg["sltrain"].update(rank=8, delta=0.05)
    write(os.path.join(chip, "configs", "tiny.json"), cfg)
    traffic = read(os.path.join(CHIP, "traffic", "pretrain_fused_adamw.json"))
    traffic.update(seq_len=32, batch=batch, exec_mode=exec_mode)
    write(os.path.join(chip, "traffic", "tiny_train.json"), traffic)
    write(os.path.join(chip, "limits", CELL + ".json"), limits or TINY_LIMITS)
    bench = read(os.path.join(REPO, "BENCHMARK.json"))
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "benchmarks/chip/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny",
                               "traffic": "tiny_train", "chips": 1,
                               "why": "test"})
    for m in bench["per_layer"] + bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append(CELL)
    write(os.path.join(root, "BENCHMARK.json"), bench)
    return root


def run_cell(root, seed=SEED, seconds=0.5, trace=0):
    """Drive a whole run of the tiny cell on the CPU, skipping only the
    harness's look for a chip; returns the result line as a dict."""
    import io
    from contextlib import redirect_stdout

    import jax

    import run as runmod
    from chipbench import harness, peaks

    peaks.PEAKS.setdefault("cpu", dict(peaks.PEAKS["TPU v5 lite"]))
    cell = harness.Cell(CELL, root=root)

    class Args:
        pass
    args = Args()
    args.seed, args.seconds, args.trace = seed, seconds, trace
    out = io.StringIO()
    with redirect_stdout(out):
        assert runmod.run(args, cell, jax, jax.devices()) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])
