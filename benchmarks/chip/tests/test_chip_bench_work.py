"""Work functions, peaks, shapes, seeded weights and the readers that turn
them into shares, on hand-counted shapes."""
import json
import os
import sys

import numpy as np
import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHIP)

from chipbench import harness, peaks, spec, work  # noqa: E402

V5E = peaks.PEAKS["TPU v5 lite"]


def qwen():
    with open(os.path.join(CHIP, "configs", "qwen2.5-32b.json")) as f:
        return json.load(f)


def reader(name):
    return harness.load_module(os.path.join(CHIP, "metrics", name + ".py"),
                               "test_reader_" + name.replace(".", "_"))


def test_sl_matmul_and_sddmm_hand_counts():
    # m=4, d_in=8, d_out=16, r=2, nnz=8
    assert work.sl_matmul(4, 8, 16, 2, 8) == {
        "flops": 2 * 4 * (2 * 24 + 8),
        "bytes": 2 * (32 + 64 + 2 * 24 + 8) + 4 * 8}
    assert work.sddmm(4, 8, 16, 8) == {
        "flops": 2 * 4 * 8, "bytes": 2 * (32 + 64) + 4 * 8 + 4 * 8}


def test_least_seconds_is_the_larger_bound():
    c = {"flops": 197e12, "bytes": 819e9 / 2}
    assert work.least_seconds(c, V5E) == pytest.approx(1.0)
    c = {"flops": 197e12 / 2, "bytes": 819e9}
    assert work.least_seconds(c, V5E) == pytest.approx(1.0)


def test_peaks_unknown_device_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")


def test_qwen_shapes():
    cfg = qwen()
    lins = {l["name"]: l for l in spec.linears(cfg)}
    assert lins["wq"]["rank"] == 1280 and lins["wk"]["rank"] == 512
    assert lins["gate"]["k"] == round(0.03 * 27648) == 829
    assert lins["down"]["nnz"] == 27648 * 154


def test_sl_work_below_dense_work():
    """The SLTrain count is below what a densified matmul needs, so a share
    of its roofline cannot pass 100% because of the implementation."""
    cfg = qwen()
    for lin in spec.linears(cfg):
        sl = work.sl_matmul(2048, lin["d_in"], lin["d_out"], lin["rank"],
                            lin["nnz"])
        assert sl["flops"] < 2 * 2048 * lin["d_in"] * lin["d_out"]


def test_train_flops_per_token_hand_count():
    cfg = dict(qwen(), hidden_size=8, intermediate_size=16,
               num_attention_heads=2, num_key_value_heads=1, head_dim=4,
               num_hidden_layers=1, vocab_size=10)
    cfg["sltrain"] = dict(cfg["sltrain"], rank=4, delta=0.25)
    # linears (d_in, d_out; rank 4, the floor; k = round(d_out / 4) per row):
    # wq 8x8 k2, wk 8x4 k1, wv 8x4 k1, wo 8x8 k2, gate/up 8x16 k4, down 16x8 k2
    per = [4 * 16 + 16, 4 * 12 + 8, 4 * 12 + 8, 4 * 16 + 16,
           4 * 24 + 32, 4 * 24 + 32, 4 * 24 + 32]
    attn = 2 * 2 * (6 / 2) * 2 * 4
    fwd = 2 * sum(per) + attn + 2 * 8 * 10
    assert work.forward_flops_per_token(cfg, 6) == pytest.approx(fwd)
    assert work.train_flops_per_token(cfg, 6) == pytest.approx(3 * fwd)
    calls = work.train_sl_calls(cfg, 12)
    assert len(calls) == 3 * 7
    assert [c["kernel"] for c in calls[:3]] == ["sl_matmul", "sl_matmul",
                                               "sddmm"]


def test_roofline_reader():
    r = reader("sl_kernels_roofline.train")
    calls = [{"flops": 197e9, "bytes": 1.0}, {"flops": 1.0, "bytes": 819e6}]
    ctx = {"job": "train", "steps": 3, "sl_calls_per_step": calls,
           "peaks": V5E, "trace": {"op_seconds": {
               "sl_matmul.3": 0.004, "sddmm.9": 0.002, "fusion.2": 5.0}}}
    # least 2 ms per step, 3 steps, 6 ms of kernel time
    assert r.read(ctx) == pytest.approx(100.0)
    ctx["trace"]["op_seconds"]["sl_matmul.3"] = 0.058
    assert r.read(ctx) == pytest.approx(10.0)
    ctx["trace"]["op_seconds"] = {"fusion.2": 5.0}
    assert r.read(ctx) is None            # no kernel in the trace: nothing


def test_mfu_and_idle_readers():
    trace = {"window_s": 2.0, "busy_s": 1.5, "devices": 1}
    ctx = {"job": "train", "trace": trace, "tokens": 4096,
           "flops_per_token": 197e12 / 4096, "peaks": V5E}
    assert reader("train_mfu").read(ctx) == pytest.approx(50.0)
    assert reader("device_idle_share.train").read(ctx) == pytest.approx(25.0)
    trace["devices"] = 0
    assert reader("device_idle_share.train").read(ctx) is None


def test_strata_give_distinct_sorted_columns():
    for d_out, k in ((5120, 154), (1024, 31), (27648, 829), (10, 10)):
        b = np.asarray(spec.strata(d_out, k))
        assert b[0] == 0 and b[-1] == d_out and (np.diff(b) >= 1).all()


def _token_params():
    with open(os.path.join(CHIP, "traffic",
                           "pretrain_fused_adamw.json")) as f:
        return json.load(f)["tokens"]


@pytest.mark.parametrize("vocab,seq,batch", [(19008, 2048, 1), (700, 96, 3)])
def test_token_rows_repeat_alike_on_every_seed(vocab, seq, batch):
    """Every row of every seed has the same sorted token counts and
    document ends; the same seed gives the same rows, past 32 bits too."""
    from chipbench import tokens
    params = _token_params()
    want = np.sort(tokens.profile(vocab - params["first_token"],
                                  seq - seq // params["mean_doc_len"],
                                  params["zipf_s"], params["zipf_q"]))
    rows = {}
    for seed in (2**32 + 12345, 1284214816, 7):
        feed = tokens.ZipfDocs(vocab, seq, batch, seed, **params)
        rows[seed] = [feed.next_batch()["tokens"] for _ in range(2)]
        for block in rows[seed]:
            assert block.shape == (batch, seq) and block.dtype == np.int32
            for row in block:
                text = row[row != params["eos"]]
                assert len(text) == seq - seq // params["mean_doc_len"]
                assert text.min() >= params["first_token"]
                assert text.max() < vocab
                counts = np.unique(text, return_counts=True)[1]
                assert np.array_equal(
                    np.sort(counts), want[want > 0])
    again = tokens.ZipfDocs(vocab, seq, batch, 2**32 + 12345, **params)
    assert np.array_equal(again.next_batch()["tokens"], rows[2**32 + 12345][0])
    assert not np.array_equal(rows[7][0], rows[1284214816][0])
    assert not np.array_equal(rows[7][0], rows[7][1])


def test_token_profile_sums_to_the_row_and_falls_with_rank():
    from chipbench import tokens
    for n, length in ((19005, 2038), (497, 32), (10, 1000)):
        c = tokens.profile(n, length, 1.0, 2.7)
        assert c.sum() == length and (np.diff(c) <= 0).all()


def test_seeded_weights():
    from chipbench import weights
    cfg = dict(qwen(), hidden_size=32, intermediate_size=64,
               num_attention_heads=2, num_key_value_heads=1, head_dim=16,
               num_hidden_layers=2, vocab_size=50)
    cfg["sltrain"] = dict(cfg["sltrain"], rank=4, delta=0.1)
    seed = 2**31 + 99
    a, b = weights.generate(cfg, seed), weights.generate(cfg, seed)
    c = weights.generate(cfg, seed + 2**32)
    assert set(a) == set(weights.shapes(cfg))
    for n in a:
        assert a[n].shape == weights.shapes(cfg)[n][0]
        assert np.array_equal(np.asarray(a[n]), np.asarray(b[n]))
    assert not np.array_equal(np.asarray(a["embed"]), np.asarray(c["embed"]))
    for lin in spec.linears(cfg):
        cols = np.asarray(a[lin["name"] + ".cols"])
        assert cols.min() >= 0 and cols.max() < lin["d_out"]
        assert (np.diff(cols, axis=-1) > 0).all()
