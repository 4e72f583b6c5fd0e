"""Training launcher (deliverable (b) driver).

Pick an arch (full config, or ``--smoke`` for the reduced one), a batch,
and run the fault-tolerant Trainer on the synthetic C4 pipeline. The same
entrypoint runs on the CPU (Pallas kernels interpreted) and on TPU chips;
``--use-mesh`` places state on a mesh of every visible device, and
``--multipod`` joins a multi-process ``jax.distributed`` job.

Usage:
  python -m repro.launch.train --arch llama_60m --smoke --steps 200
  python -m repro.launch.train --arch llama_1b --exec-mode fused --steps 3
  python -m repro.launch.train --arch llama_60m --smoke --mode dense   # baseline
  python -m repro.launch.train --arch yi_34b --smoke --optimizer adam8bit
  python -m repro.launch.train --arch llama_60m --smoke --steps 20 \
      --update-mode per_layer \
      --metrics-out /tmp/train.jsonl --trace-out /tmp/train_trace.json
"""
from __future__ import annotations

import argparse
import dataclasses

import jax

from repro.configs.base import (OptimizerConfig, ShardingConfig, TrainConfig,
                                ParamConfig)
from repro.launch.compile_cache import enable_compile_cache
from repro.models import registry
from repro.obs import trace as obs_trace
from repro.train.trainer import Trainer


def build_train_config(args) -> TrainConfig:
    cfg = (registry.get_smoke_config(args.arch) if args.smoke
           else registry.get_config(args.arch))
    if args.mode:
        cfg = dataclasses.replace(
            cfg, param=dataclasses.replace(cfg.param, mode=args.mode))
    if args.exec_mode:
        cfg = dataclasses.replace(
            cfg, param=dataclasses.replace(cfg.param, exec_mode=args.exec_mode))
    if args.delta is not None:
        cfg = dataclasses.replace(
            cfg, param=dataclasses.replace(cfg.param, delta=args.delta))
    if args.rank is not None:
        cfg = dataclasses.replace(
            cfg, param=dataclasses.replace(cfg.param, rank=args.rank))
    oc = OptimizerConfig(name=args.optimizer, lr=args.lr,
                         warmup_steps=max(1, args.steps // 10),
                         total_steps=args.steps)
    sc = ShardingConfig(remat=args.remat, grad_accum=args.grad_accum,
                        update_mode=args.update_mode, fsdp=args.fsdp)
    return TrainConfig(model=cfg, optim=oc, sharding=sc, seed=args.seed,
                       global_batch=args.batch, seq_len=args.seq,
                       steps=args.steps, log_every=args.log_every,
                       ckpt_every=args.ckpt_every, ckpt_dir=args.ckpt_dir)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama_60m")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-friendly)")
    ap.add_argument("--mode", default=None,
                    choices=[None, "dense", "lowrank", "sltrain", "relora"])
    ap.add_argument("--exec-mode", default=None,
                    choices=[None, "dense", "sparse", "fused"],
                    help="sltrain execution mode: dense densify (XLA "
                         "baseline), sparse factored gather (decode), "
                         "fused Pallas tile kernels (training)")
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "adam8bit", "galore_adamw"])
    ap.add_argument("--delta", type=float, default=None)
    ap.add_argument("--rank", type=int, default=None)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--remat", default="none")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--update-mode", default="global",
                    choices=["global", "per_layer"],
                    help="per_layer = layer-wise backward sweep with "
                         "in-sweep optimizer updates (repro.train.perlayer"
                         "; O(layer) grad residency, the paper's Appendix-F"
                         " memory path)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--metrics-out", default=None,
                    help="append registry snapshot JSONL lines here (one "
                         "per log interval; repro.obs.metrics)")
    ap.add_argument("--trace-out", default=None,
                    help="write the process recorder's Chrome-trace JSON: "
                         "step spans (data/dispatch/sync/readback), tile "
                         "tables and JAX's compile events (repro.obs.trace)")
    ap.add_argument("--jax-profile-dir", default=None,
                    help="also record a jax.profiler trace into this dir "
                         "for the duration of the run")
    ap.add_argument("--chaos", default=None,
                    help="fault-injection spec 'kind@step[:arg],...' "
                         "(repro.resilience.chaos), e.g. 'kill@3' or "
                         "'nonfinite@5,straggler@4:50' — every injected "
                         "fault must end in a verified recovery")
    ap.add_argument("--max-rollbacks", type=int, default=2,
                    help="checkpoint rollbacks tolerated before the "
                         "trainer gives up on a persistent divergence")
    ap.add_argument("--multipod", action="store_true",
                    help="initialize jax.distributed from the "
                         "JAX_COORDINATOR_ADDRESS, JAX_NUM_PROCESSES and "
                         "JAX_PROCESS_ID env vars")
    ap.add_argument("--use-mesh", action="store_true",
                    help="run under the named local mesh and place state "
                         "via the repro.dist.sharding spec engine")
    ap.add_argument("--fsdp", action="store_true",
                    help="with --use-mesh: additionally shard params and "
                         "optimizer state over the data axis "
                         "(ShardingConfig.fsdp) and pin gradients to the "
                         "sharded layout (reduce-scatter update)")
    args = ap.parse_args(argv)
    if args.use_mesh and args.multipod:
        ap.error("--use-mesh builds the single-process local mesh and "
                 "cannot be combined with --multipod")
    if args.fsdp and not args.use_mesh:
        ap.error("--fsdp shards state via the spec engine and needs "
                 "--use-mesh (or a multipod mesh wired in code)")

    enable_compile_cache()
    if args.multipod:
        import os
        jax.distributed.initialize(
            coordinator_address=os.environ["JAX_COORDINATOR_ADDRESS"],
            num_processes=int(os.environ["JAX_NUM_PROCESSES"]),
            process_id=int(os.environ["JAX_PROCESS_ID"]))

    mesh = None
    if args.use_mesh:
        from repro.dist import sharding as dist_sharding
        mesh = dist_sharding.make_local_mesh()

    chaos = None
    if args.chaos:
        from repro.resilience.chaos import ChaosEngine
        chaos = ChaosEngine.parse(args.chaos, seed=args.seed)

    tc = build_train_config(args)
    trace = obs_trace.get_trace()
    if args.jax_profile_dir:
        jax.profiler.start_trace(args.jax_profile_dir)
    trainer = Trainer(tc, mesh=mesh, metrics_out=args.metrics_out,
                      chaos=chaos, max_rollbacks=args.max_rollbacks)
    state = trainer.run()
    if args.jax_profile_dir:
        jax.profiler.stop_trace()
    print(f"final step {state.step}: "
          f"loss={trainer.metrics_history[-1]['loss']:.4f}")
    if args.trace_out:
        n = trace.export(args.trace_out)
        print(f"trace: {n} events -> {args.trace_out}")
    return trainer, state


if __name__ == "__main__":
    main()
