#!/usr/bin/env python3
"""Drive SLTrain training and serving once on TPU chips, at the paper's
LLaMA-1B width, through the normal entry points, and check the results.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # FSDP LLaMA-7B across four chips

One chip runs these phases in one process:

  kernels  every Pallas kernel of the main path, compiled, against its
           ``kernels/ref.py`` oracle at LLaMA-1B widths;
  train    LLaMA-1B, fused SLTrain kernels, AdamW, global update, 3 steps,
           checkpointed; the step-0 loss must match the XLA densify path
           (``--exec-mode dense``) on the same batch;
  perlayer LLaMA-1B, per-layer updates with the fused 8-bit Adam kernel
           (the paper's memory path), 3 steps;
  serve    8 requests on 4 slots from the ``train`` checkpoint: paged KV,
           the paged-attention kernels, continuous batching, prefix
           sharing, factored sparse decode; every request must finish.

``--chips 4`` runs only the multi-chip path and its comparison: LLaMA-7B
at full width with FSDP over a data=4 mesh and 8-bit Adam for 2 steps
(each leaf must be spread over the four devices), and a 2-layer cut of
the same config trained 3 steps on one chip and on the mesh, whose losses
must agree.

Per-phase lines carry losses, seconds and device bytes. The last line is
one JSON object naming the device; it is printed only when every phase
passed. Without a TPU, or outside a checkout of the repository, the
script exits non-zero before any phase. Outputs go to ``chip_smoke_out/``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chip_smoke_out")
SRC = os.path.join(ROOT, "src")

# bf16 agreement: losses of two execution paths, relative
LOSS_RTOL = 1e-2
# kernel against oracle: max |got - want| over max |want|
KERNEL_TOL = 2e-2


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def report(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def peak_bytes(devices) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


def _rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def phase_kernels() -> None:
    """Compiled kernels against the jnp oracles at LLaMA-1B widths."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.llama_paper import LLAMA_1B
    from repro.core import support
    from repro.kernels import ops, ref
    from repro.optim import quant

    t0 = time.perf_counter()
    rng = np.random.default_rng(0)
    d_in, d_out, r = LLAMA_1B.d_model, LLAMA_1B.d_ff, LLAMA_1B.param.rank
    m, bf16 = 512, jnp.bfloat16
    rows, cols = support.sample_support(1, d_in, d_out, LLAMA_1B.param.delta)
    v = (rng.standard_normal(rows.shape[0]) * 0.05).astype(np.float32)
    v = np.asarray(jnp.asarray(v, bf16), np.float32)
    v_t, r_t, c_t, perm = ops.prepare_tiles(rows, cols, v, d_in, d_out)
    x = jnp.asarray(rng.standard_normal((m, d_in)), bf16)
    B = jnp.asarray(rng.standard_normal((d_in, r)) * 0.05, bf16)
    A = jnp.asarray(rng.standard_normal((r, d_out)) * 0.05, bf16)
    dy = jnp.asarray(rng.standard_normal((m, d_out)), bf16)
    err = {}
    err["sl_matmul"] = _rel_err(
        ops.sl_matmul(x, B, A, v_t, r_t, c_t, 0.25),
        ref.sl_matmul_ref(x, B, A, rows, cols, v, 0.25))
    err["sparse_matmul"] = _rel_err(
        ops.sl_decode(x[:4], B, A, v_t, r_t, c_t, 0.25),
        ref.sl_decode_ref(x[:4], B, A, rows, cols, v, 0.25))
    dv_t = np.asarray(ops.sddmm(x, dy, r_t, c_t)).reshape(-1)
    p = np.asarray(perm).reshape(-1)
    dv = np.zeros(rows.shape[0], np.float32)
    dv[p[p >= 0]] = dv_t[p >= 0]
    err["sddmm"] = _rel_err(dv, ref.sddmm_ref(x, dy, rows, cols))

    n = r * d_out // 256 * 256        # whole quantization blocks
    pf = jnp.asarray(rng.standard_normal(n), jnp.float32)
    g = jnp.asarray(rng.standard_normal(n), jnp.float32)
    mc, ms, _ = quant.quantize_blockwise(g * 0.1, 256, True)
    vc, vs, _ = quant.quantize_blockwise(g * g * 0.01, 256, False)
    kw = dict(lr=1e-3, b1=0.9, b2=0.999, bc1=0.2, bc2=0.01, eps=1e-8,
              wd=0.1)
    new_p, mc2, ms2, vc2, vs2 = ops.adam8bit_update(pf, g, mc, ms, vc, vs,
                                                    **kw)
    scalars = jnp.array([kw["lr"], kw["b1"], kw["b2"], 1 - kw["b1"],
                         1 - kw["b2"], kw["bc1"], kw["bc2"], kw["eps"],
                         kw["wd"], 0.0])
    rp, rmc, rms, rvc, rvs = ref.adam8bit_ref(
        pf.reshape(-1, 256), g.reshape(-1, 256), mc, ms, vc, vs, scalars,
        n_valid=n)
    err["adam8bit_update"] = max(
        _rel_err(new_p, rp.reshape(-1)),
        _rel_err(mc2 * ms2[:, None], rmc * rms[:, None]),
        _rel_err((vc2.astype(jnp.float32) + 128) * vs2[:, None],
                 (rvc.astype(jnp.float32) + 128) * rvs[:, None]))

    slots, bl, heads, hd, bps, sq = 4, 16, LLAMA_1B.n_heads, 64, 8, 32
    n_blocks = 1 + slots * bps
    k_pool = jnp.asarray(rng.standard_normal((n_blocks, bl, heads, hd)), bf16)
    v_pool = jnp.asarray(rng.standard_normal((n_blocks, bl, heads, hd)), bf16)
    table = rng.permutation(np.arange(1, n_blocks))[:slots * bps]
    table = table.reshape(slots, bps).astype(np.int32)
    table[3] = 0                                   # one idle slot
    pos = jnp.asarray([5, 40, 127, 0], jnp.int32)
    q = jnp.asarray(rng.standard_normal((slots, heads, hd)), bf16)
    got = ops.paged_attention(q, k_pool, v_pool, table, pos, scale=0.125)
    want = ref.paged_attention_ref(q.reshape(slots, heads, 1, hd), k_pool,
                                   v_pool, table, pos, scale=0.125)
    err["paged_attention"] = _rel_err(got, want.reshape(got.shape))
    qp = jnp.asarray(rng.standard_normal((slots, sq, heads, hd)), bf16)
    off = jnp.asarray([0, 16, 90, 0], jnp.int32)
    got = ops.paged_prefill_attention(qp, k_pool, v_pool, table, off,
                                      scale=0.125)
    want = ref.paged_prefill_ref(qp.reshape(slots, sq, heads, 1, hd),
                                 k_pool, v_pool, table, off, scale=0.125)
    err["paged_prefill"] = _rel_err(got, want.reshape(got.shape))
    jax.effects_barrier()
    report("kernels", rel_err=err, seconds=time.perf_counter() - t0)
    for name, e in err.items():
        check(e <= KERNEL_TOL, f"{name} kernel off its oracle by {e:.3g} "
              f"(limit {KERNEL_TOL})")


def _train(phase: str, argv: list) -> list:
    """Run the training launcher; return its per-step losses."""
    import gc

    import jax

    from repro.launch import train
    t0 = time.perf_counter()
    trainer, _ = train.main(argv)
    hist = trainer.metrics_history
    losses = [h["loss"] for h in hist]
    report(phase, losses=losses, step_seconds=[h["dt"] for h in hist],
           seconds=time.perf_counter() - t0,
           peak_bytes_in_use=peak_bytes(jax.devices()[:1]))
    check(len(losses) > 0 and all(math.isfinite(l) for l in losses),
          f"{phase}: non-finite or missing losses {losses}")
    del trainer
    gc.collect()
    return losses


def _close(a: float, b: float, what: str) -> None:
    rel = abs(a - b) / max(abs(b), 1e-30)
    check(rel <= LOSS_RTOL, f"{what}: {a} vs {b} (rel {rel:.3g} > "
          f"{LOSS_RTOL})")


def phase_train() -> str:
    """Fused training with its dense reference; returns the ckpt dir."""
    # batch 4: at 8 the fused global step needs 16.9 GiB of the chip's
    # 15.75 (the TPU compiler's out-of-memory report for a described v5e)
    common = ["--arch", "llama_1b", "--mode", "sltrain", "--batch", "4",
              "--seq", "256", "--log-every", "1", "--optimizer", "adamw",
              "--update-mode", "global"]
    ckpt = os.path.join(OUT, "train_fused")
    fused = _train("train", common + ["--exec-mode", "fused", "--steps",
                                      "3", "--ckpt-dir", ckpt])
    dense = _train("train_dense_reference",
                   common + ["--exec-mode", "dense", "--steps", "1",
                             "--ckpt-dir", os.path.join(OUT, "train_dense")])
    _close(fused[0], dense[0], "step-0 loss, fused vs dense")
    return ckpt


def phase_perlayer() -> None:
    _train("perlayer", [
        "--arch", "llama_1b", "--mode", "sltrain", "--exec-mode", "fused",
        "--update-mode", "per_layer", "--optimizer", "adam8bit",
        "--batch", "8", "--seq", "256", "--steps", "3", "--log-every", "1",
        "--ckpt-dir", os.path.join(OUT, "train_perlayer")])


def phase_serve(ckpt: str) -> None:
    import jax

    from repro.launch import serve
    t0 = time.perf_counter()
    new_tokens = 16
    eng, reqs = serve.main([
        "--arch", "llama_1b", "--ckpt-dir", ckpt, "--paged",
        "--attn-kernel", "paged", "--stream", "--prefix-sharing",
        "--sparse-decode", "--requests", "8", "--slots", "4",
        "--new-tokens", str(new_tokens)])
    vocab = eng.cfg.vocab_size
    report("serve", statuses=[r.status for r in reqs],
           tokens=[len(r.out) for r in reqs],
           seconds=time.perf_counter() - t0,
           peak_bytes_in_use=peak_bytes(jax.devices()[:1]))
    check(len(reqs) == 8 and all(r.status == "done" for r in reqs),
          f"serve: not every request finished: "
          f"{[(r.uid, r.status) for r in reqs]}")
    check(all(len(r.out) == new_tokens and
              all(0 <= t < vocab for t in r.out) for r in reqs),
          "serve: a request returned the wrong number of tokens or a "
          "token outside the vocabulary")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def phase_fsdp_7b() -> None:
    """LLaMA-7B, FSDP over data=4, 8-bit Adam, 2 steps via the launcher."""
    import jax
    import numpy as np

    from repro.launch import train
    t0 = time.perf_counter()
    trainer, state = train.main([
        "--arch", "llama_7b", "--mode", "sltrain", "--use-mesh", "--fsdp",
        "--optimizer", "adam8bit", "--batch", "4", "--seq", "256",
        "--steps", "2", "--log-every", "1",
        "--ckpt-dir", os.path.join(OUT, "fsdp_7b")])
    losses = [h["loss"] for h in trainer.metrics_history]
    devices = jax.devices()
    per_dev = {str(d.id): 0 for d in devices}
    for leaf in jax.tree.leaves((state.params, state.opt_state)):
        for sh in leaf.addressable_shards:
            per_dev[str(sh.device.id)] += sh.data.nbytes
    big_name, big = max(
        ((jax.tree_util.keystr(k), v) for k, v in
         jax.tree_util.tree_leaves_with_path(state.params)),
        key=lambda kv: kv[1].size)
    shards = big.addressable_shards
    report("fsdp_7b", losses=losses,
           step_seconds=[h["dt"] for h in trainer.metrics_history],
           seconds=time.perf_counter() - t0, state_bytes_per_device=per_dev,
           peak_bytes_in_use=peak_bytes(devices),
           largest_leaf={"path": big_name, "shape": list(big.shape),
                         "shard_shapes": [list(s.data.shape)
                                          for s in shards],
                         "devices": [s.device.id for s in shards]})
    check(all(math.isfinite(l) for l in losses) and len(losses) == 2,
          f"fsdp_7b: losses {losses}")
    check(len({s.device.id for s in shards}) == len(devices) == 4 and
          all(int(np.prod(s.data.shape)) * 4 == big.size for s in shards),
          f"fsdp_7b: {big_name} is not split in 4 over 4 devices")


def phase_mesh_vs_one_chip() -> None:
    """2-layer full-width LLaMA-7B: 3 steps on one chip and on data=4."""
    import gc
    import tempfile

    from repro.configs.base import (OptimizerConfig, ShardingConfig,
                                    TrainConfig)
    from repro.dist import sharding as dist_sharding
    from repro.models import registry
    from repro.train.trainer import Trainer

    cfg = dataclasses.replace(registry.get_config("llama_7b"), n_layers=2)
    runs = {}
    for name, mesh in (("one_chip", None),
                       ("mesh", dist_sharding.make_local_mesh())):
        t0 = time.perf_counter()
        tc = TrainConfig(
            model=cfg, optim=OptimizerConfig(name="adam8bit", lr=3e-3,
                                             warmup_steps=1, total_steps=3),
            sharding=ShardingConfig(fsdp=mesh is not None), seed=42,
            global_batch=8, seq_len=256, steps=3, log_every=1,
            ckpt_dir=tempfile.mkdtemp(dir=OUT))
        tr = Trainer(tc, mesh=mesh)
        tr.run()
        runs[name] = [h["loss"] for h in tr.metrics_history]
        report(f"cut_7b_{name}", losses=runs[name],
               seconds=time.perf_counter() - t0)
        del tr
        gc.collect()
    for i, (a, b) in enumerate(zip(runs["mesh"], runs["one_chip"])):
        _close(a, b, f"2-layer cut step {i} loss, mesh vs one chip")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print("chip_smoke: src/repro not found next to this script; run it "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    report("setup", device_kind=devices[0].device_kind,
           count=len(devices), compile_cache=enable_compile_cache())
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)
    if args.chips == 4:
        phase_fsdp_7b()
        phase_mesh_vs_one_chip()
    else:
        phase_kernels()
        ckpt = phase_train()
        phase_perlayer()
        phase_serve(ckpt)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
