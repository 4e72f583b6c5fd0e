"""The plain f32 reference against the program at a smoke size on the CPU:
the model's logits, the paged serving engine's greedy tokens, and the
control (the reference in float8) failing the cell's limits."""
import os
import sys

import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import chip_bench_tiny as tiny  # noqa: E402
from chipbench import harness, weights  # noqa: E402

REF = harness.load_module(os.path.join(tiny.CHIP, "reference",
                                       "sltrain_lm.py"),
                          "test_chip_bench_reference_sltrain_lm")
TRAIN = harness.load_module(os.path.join(tiny.CHIP, "jobs", "train.py"),
                            "test_chip_bench_job_train")


def setup_tiny(tmp_path, exec_mode):
    cell = harness.Cell(tiny.CELL, root=tiny.make_root(tmp_path,
                                                       exec_mode=exec_mode))
    cfg = cell.config
    canon = weights.generate(cfg, 2**31 + 5)
    floats = {k: v for k, v in canon.items() if not k.endswith(".cols")}
    cols = {k: v for k, v in canon.items() if k.endswith(".cols")}
    pcfg = TRAIN.model_config(cfg, cell.traffic)
    return cell, cfg, pcfg, floats, cols


def test_program_logits_match_reference(tmp_path):
    from repro.models import lm
    cell, cfg, pcfg, floats, cols = setup_tiny(tmp_path, "dense")
    params = TRAIN.program_params(pcfg, floats)
    consts = TRAIN.program_consts(cfg, cols, tile_tables=False)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (2, 24)), jnp.int32)
    got, _ = lm.apply_lm(pcfg, params, consts, tokens)
    want = REF.logits(cfg, floats, cols, tokens)
    got = np.asarray(got[..., :cfg["vocab_size"]], np.float32)
    err = np.abs(got - np.asarray(want)).max() / np.abs(want).max()
    assert err < 2e-2, err           # bf16 activations against f32


def test_paged_engine_greedy_tokens_are_reference_argmax(tmp_path):
    """Prefill and decode through the paged cache, the paged-attention
    kernel and the factored sparse decode: every served token lies within
    bf16 noise of the reference's best logit at its position."""
    from repro.serve.engine import ServeEngine
    cell, cfg, pcfg, floats, cols = setup_tiny(tmp_path, "sparse")
    params = TRAIN.program_params(pcfg, floats)
    consts = TRAIN.program_consts(cfg, cols, tile_tables=False)
    eng = ServeEngine(pcfg, params, consts, n_slots=2, max_len=64,
                      paged=True, block_len=16, attn_kernel="paged",
                      exec_mode="sparse")
    rng = np.random.default_rng(1)
    reqs = [eng.submit(list(rng.integers(3, cfg["vocab_size"], n)), 6)
            for n in (5, 19, 11)]
    eng.run_until_drained()
    worst = 0.0
    for r in reqs:
        assert r.status == "done" and len(r.out) == 6
        seq = jnp.asarray([r.prompt + r.out[:-1]], jnp.int32)
        lg = np.asarray(REF.logits(cfg, floats, cols, seq))[0]
        for i, tok in enumerate(r.out):
            row = lg[len(r.prompt) - 1 + i]
            worst = max(worst, (row.max() - row[tok]) / row.std())
    assert worst < 0.05, worst


def test_control_in_float8_fails_the_limits(tmp_path):
    cell, cfg, *_ = setup_tiny(tmp_path, "fused")
    job = TRAIN.Job(cell, tiny.SEED + 1)
    ref = job.reference("f32")
    control = job.reference("fp8")
    got = TRAIN.compare(control, ref)
    assert any(got[k] > cell.limits[k] for k in cell.limits), got
    job.free()
