"""llama_7b FSDP placement gate (ISSUE 8 acceptance).

Builds an 8-host-device (data=8, model=1) mesh, computes the FSDP spec
trees for the paper's llama_7b config (sltrain, r=1024, δ=0.05, bf16
params + f32 adamw moments), and asserts the MEASURED per-device
parameter + optimizer-state residency — summed over every leaf's
``NamedSharding.shard_shape`` — lands within 10% of the
``core/memory.training_estimate`` sharded prediction
((param_bytes + optim_bytes) / n_devices with ``moment_bytes=4`` and
the framework's int32 indices). Then AOT-lowers (and by default
compiles) the fsdp train step on the mesh via ``launch.dryrun.
lower_cell`` to prove the placement actually lowers end-to-end.

Usage:
  python scripts/fsdp_dryrun.py                # full gate (lower+compile)
  python scripts/fsdp_dryrun.py --skip-compile # residency check only
"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8").strip()
# ^ must precede jax import: device count locks at first backend init.
import argparse
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np
from jax.sharding import NamedSharding

from repro.configs.base import OptimizerConfig, ShapeCell
from repro.core import memory as memory_lib
from repro.dist import sharding as shl
from repro.models import registry
from repro.optim import optimizers

N_DEV = 8
ARCH = "llama_7b"
# small train cell: the gate is about PLACEMENT (params/opt residency),
# not activation scale — seq 256 × batch 8 keeps host-CPU compile cheap
CELL = ShapeCell("train_fsdp_smoke", 256, 8, "train")


def sharded_bytes(tree, specs, mesh):
    """Per-device bytes of ``tree`` placed per ``specs``: sum over leaves
    of prod(shard_shape) × itemsize."""
    total = 0
    for leaf, spec in zip(jax.tree.leaves(tree),
                          jax.tree.leaves(specs,
                                          is_leaf=lambda x: isinstance(
                                              x, jax.sharding.PartitionSpec))):
        shard = NamedSharding(mesh, spec).shard_shape(tuple(leaf.shape))
        total += int(np.prod(shard)) * jax.dtypes.canonicalize_dtype(
            leaf.dtype).itemsize
    return total


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-compile", action="store_true",
                    help="residency gate only; skip lower+compile")
    args = ap.parse_args(argv)

    assert jax.device_count() >= N_DEV, (
        f"need >= {N_DEV} host devices, got {jax.device_count()} — is "
        "another jax init clobbering xla_force_host_platform_device_count?")
    mesh = jax.make_mesh(
        (N_DEV, 1), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)

    cfg = registry.get_config(ARCH)
    api = registry.get_api(cfg)
    params_abs, consts_abs = api.init(cfg, key=None)      # abstract init
    opt = optimizers.make(OptimizerConfig())              # adamw, f32 m/v
    opt_abs = jax.eval_shape(opt.init, params_abs)

    fsdp_axes = ("data",)
    p_specs = shl.param_specs(params_abs, mesh, fsdp_axes=fsdp_axes)
    c_specs = shl.param_specs(consts_abs, mesh, fsdp_axes=fsdp_axes)
    o_specs = shl.opt_state_specs(opt_abs, p_specs, mesh,
                                  fsdp_axes=fsdp_axes)

    measured = (sharded_bytes(params_abs, p_specs, mesh)
                + sharded_bytes(consts_abs, c_specs, mesh)
                + sharded_bytes(opt_abs, o_specs, mesh))

    pl = dict(memory_lib.PAPER_LLAMA["7b"])
    rank = pl.pop("rank")
    inv = memory_lib.llama_inventory(**pl)
    est = memory_lib.training_estimate(
        inv, "sltrain", optimizer="adamw", update_mode="global",
        rank=rank, delta=cfg.param.delta, dtype_bytes=2, index_bytes=4,
        support_kind=cfg.param.support_kind, moment_bytes=4)
    expected = (est.param_bytes + est.optim_bytes) / N_DEV

    rel = abs(measured - expected) / expected
    print(f"fsdp_dryrun[{ARCH} @ data={N_DEV}]: measured param+opt "
          f"{measured / 2**30:.3f} GiB/dev vs estimate "
          f"{expected / 2**30:.3f} GiB/dev (rel err {rel:.3%})")
    assert rel <= 0.10, (
        f"per-device residency off by {rel:.1%} (> 10%): measured "
        f"{measured} vs estimated {expected} bytes — FSDP specs are not "
        "sharding what core/memory says they should")

    # unsharded reference: the same state replicated would be N_DEV× larger
    ratio = (est.param_bytes + est.optim_bytes) / measured
    print(f"fsdp_dryrun: sharding factor {ratio:.2f}x "
          f"(ideal {N_DEV}x; gap = replicated small leaves)")

    if not args.skip_compile:
        from repro.launch import dryrun
        res = dryrun.lower_cell(ARCH, CELL, mesh=mesh, fsdp=True,
                                verbose=True)
        assert res["fsdp"], res
        bpd = res["bytes_per_device"]["argument"]
        print(f"fsdp_dryrun: compiled argument bytes "
              f"{bpd / 2**30:.3f} GiB/dev")
    print("fsdp_dryrun: gate passed")


if __name__ == "__main__":
    main()
