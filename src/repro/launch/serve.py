"""Serving launcher: restore a checkpoint (or init) and serve batched
requests through the continuous-batching engine.

Usage:
  python -m repro.launch.serve --arch llama_60m --smoke --requests 8
  python -m repro.launch.serve --arch llama_60m --smoke --sparse-decode
  python -m repro.launch.serve --arch llama_60m --smoke --paged --block-len 8
  python -m repro.launch.serve --arch llama_60m --smoke --paged --stagger
  python -m repro.launch.serve --arch llama_60m --smoke --paged \
      --attn-kernel paged
  python -m repro.launch.serve --arch llama_60m --smoke --paged \
      --stream --prefix-sharing
  python -m repro.launch.serve --arch llama_60m --smoke --paged --stream \
      --metrics-out /tmp/serve.jsonl --trace-out /tmp/serve_trace.json
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.models import registry
from repro.obs import trace as obs_trace
from repro.serve.engine import ServeEngine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama_60m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--sparse-decode", action="store_true",
                    help="factored SLTrain decode (DESIGN §3 beyond-paper)")
    ap.add_argument("--exec-mode", default=None,
                    choices=("dense", "sparse", "fused", "quant"),
                    help="explicit SLTrain serve execution mode (supersedes "
                         "--sparse-decode; 'quant' requires --quant-ckpt)")
    ap.add_argument("--quant-ckpt", default=None,
                    help="load a calibrated int8 quant artifact "
                         "(python -m repro.quant.calibrate) instead of a "
                         "training checkpoint; defaults --exec-mode to "
                         "'quant'")
    ap.add_argument("--paged", action="store_true",
                    help="block-paged KV cache with batched prefill and "
                         "per-slot decode positions (serve/kv.py)")
    ap.add_argument("--block-len", type=int, default=16,
                    help="tokens per KV block (paged only)")
    ap.add_argument("--attn-kernel", default=None,
                    choices=("gather", "paged"),
                    help="paged attention read path: 'gather' materializes "
                         "the per-slot K/V view, 'paged' streams blocks "
                         "through the Pallas paged-attention kernels "
                         "(kernels/paged_attention.py; requires --paged). "
                         "Default: the config's choice ('paged' on a paged "
                         "engine, auto-fallback to 'gather' otherwise)")
    ap.add_argument("--stagger", action="store_true",
                    help="submit requests one engine step apart (exercises "
                         "diverging per-slot positions)")
    ap.add_argument("--stream", action="store_true",
                    help="continuous batching: stamp Poisson arrival ticks "
                         "on the requests and serve via run_stream — "
                         "admission happens inside the decode loop "
                         "(requires --paged)")
    ap.add_argument("--prefix-sharing", action="store_true",
                    help="copy-on-write prefix sharing: admissions whose "
                         "prompt matches a resident block-aligned prefix "
                         "attach those pages read-only and prefill only "
                         "the suffix (requires --paged)")
    ap.add_argument("--use-mesh", action="store_true",
                    help="place weights/cache via repro.dist.sharding on "
                         "the named local mesh")
    ap.add_argument("--metrics-out", default=None,
                    help="append one registry snapshot JSONL line here at "
                         "the end of the run (repro.obs.metrics)")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome-trace JSON (Perfetto-loadable) "
                         "with engine phase spans + per-request tick "
                         "lifecycle lanes (repro.obs.trace)")
    ap.add_argument("--jax-profile-dir", default=None,
                    help="also record a jax.profiler trace into this dir "
                         "for the duration of the run")
    ap.add_argument("--chaos", default=None,
                    help="fault-injection spec (repro.resilience.chaos), "
                         "e.g. 'stall@4:64' — freezes one active slot for "
                         "64 ticks at tick 4; the engine must drain with "
                         "zero wedged requests")
    ap.add_argument("--deadline-ticks", type=int, default=None,
                    help="cancel any request not completed within this "
                         "many engine ticks of its arrival "
                         "(status='timed_out', pages released)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="wall-clock completion deadline per request, ms")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="admission queue cap: submits past it are shed "
                         "with status='rejected' instead of queued")
    ap.add_argument("--quant-fallback", action="store_true",
                    help="with --exec-mode quant: degrade to the bf16 "
                         "sparse path (warn + serve) when the artifact "
                         "fails validation, instead of refusing to start")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = (registry.get_smoke_config(args.arch) if args.smoke
           else registry.get_config(args.arch))
    if args.quant_ckpt and cfg.param.mode != "sltrain":
        import dataclasses
        cfg = dataclasses.replace(
            cfg, param=dataclasses.replace(cfg.param, mode="sltrain"))
    api = registry.get_api(cfg)
    exec_mode = args.exec_mode
    if args.quant_ckpt:
        # the artifact carries BOTH trees (error-folded B/A params and the
        # int8 tile-CSR consts) — no init-then-restore template needed
        from repro.ckpt.checkpoint import load_quant_artifact
        params, consts, qman = load_quant_artifact(args.quant_ckpt)
        exec_mode = exec_mode or "quant"
        print(f"quant artifact: {args.quant_ckpt} "
              f"({qman['extra'].get('n_matrices', '?')} matrices)")
    else:
        params, consts = api.init(cfg, jax.random.PRNGKey(0), seed=0)
    if args.ckpt_dir:
        from repro.ckpt.checkpoint import CheckpointManager
        cm = CheckpointManager(args.ckpt_dir)
        tree, _ = cm.restore({"params": params}, allow_config_change=True)
        params = tree["params"]

    mesh = None
    if args.use_mesh:
        from repro.dist import sharding as dist_sharding
        mesh = dist_sharding.make_local_mesh()
    if (args.stream or args.prefix_sharing) and not args.paged:
        ap.error("--stream/--prefix-sharing require --paged")
    trace = obs_trace.Trace(
        enabled=bool(args.trace_out or args.jax_profile_dir),
        jax_profile_dir=args.jax_profile_dir)
    trace.start()
    chaos = None
    if args.chaos:
        from repro.resilience.chaos import ChaosEngine
        chaos = ChaosEngine.parse(args.chaos)
    eng = ServeEngine(cfg, params, consts, n_slots=args.slots,
                      max_len=args.max_len,
                      sparse_decode=args.sparse_decode,
                      exec_mode=exec_mode, mesh=mesh,
                      paged=args.paged, block_len=args.block_len,
                      attn_kernel=args.attn_kernel,
                      prefix_sharing=args.prefix_sharing,
                      trace=trace, max_queue=args.max_queue,
                      deadline_ticks=args.deadline_ticks,
                      deadline_ms=args.deadline_ms,
                      tick_hook=chaos.serve_hook if chaos else None,
                      quant_fallback=args.quant_fallback)
    if chaos is not None:
        chaos.bind(eng.obs)
    rng = np.random.default_rng(0)
    prompts = []
    shared = rng.integers(3, cfg.vocab_size, size=16).tolist()
    for i in range(args.requests):
        plen = int(rng.integers(2, 8))
        tail = rng.integers(3, cfg.vocab_size, size=plen).tolist()
        # with sharing on, give the workload something to share: half the
        # prompts open with one common (block-alignable) system prefix
        prompts.append(shared + tail if args.prefix_sharing and i % 2 == 0
                       else tail)
    t0 = time.perf_counter()
    reqs = []
    if args.stream:
        arrivals = np.cumsum(rng.poisson(2.0, size=len(prompts)))
        reqs = [eng.submit(p, max_new_tokens=args.new_tokens, arrival=int(a))
                for p, a in zip(prompts, arrivals)]
        stats = eng.run_stream()
    else:
        if args.stagger:
            it = iter(prompts)
            reqs.append(eng.submit(next(it), max_new_tokens=args.new_tokens))
            for p in it:
                eng.step()
                reqs.append(eng.submit(p, max_new_tokens=args.new_tokens))
        else:
            reqs = [eng.submit(p, max_new_tokens=args.new_tokens)
                    for p in prompts]
        stats = eng.run_until_drained()
    dt = time.perf_counter() - t0
    # terminal-status accounting: the engine never silently loses a
    # request — every one ends done/rejected/timed_out (failed only when
    # the step budget ran out, which these bounded runs never hit)
    assert all(r.status in ("done", "rejected", "timed_out") for r in reqs) \
        and not stats["exhausted"], \
        ([(r.uid, r.status) for r in reqs], stats["exhausted"])
    degraded = args.chaos or args.deadline_ticks is not None \
        or args.deadline_ms is not None or args.max_queue is not None
    if not degraded:
        assert len(stats["completed"]) == len(reqs), \
            (len(stats["completed"]), len(reqs))
    total_toks = sum(len(r.out) for r in reqs)
    mode = f"paged/{eng.cfg.attn_kernel}" if args.paged else "legacy"
    if args.stream:
        mode += "/stream"
    print(f"served {len(reqs)} requests, {total_toks} tokens in {dt:.2f}s "
          f"({total_toks/dt:.1f} tok/s, {stats['decode_steps']} decode steps,"
          f" {eng.dispatches['prefill']} prefill dispatches, {mode},"
          f" exec_mode={eng.cfg.param.exec_mode})")
    if args.prefix_sharing:
        pt = eng.prefill_traffic
        print(f"  prefix sharing: {pt['tokens_shared']}/{pt['tokens_total']} "
              "prompt tokens attached from resident pages (never "
              "recomputed or rewritten)")
    if args.stream:
        # both TTFT units, from the engine's registry histograms: ticks
        # (deterministic dispatch clock) and wall ms (what an SLO means);
        # shed/timed-out requests may never see a first token — skip them
        ht = eng.obs.histogram("serve.ttft_ticks")
        hw = eng.obs.histogram("serve.ttft_wall_ms")
        tt = sorted(r.t_first - r.arrival for r in reqs
                    if r.t_first is not None)
        if tt:
            print(f"  TTFT: p50={ht.percentile(50):.0f} ticks "
                  f"(max={tt[-1]}) | p50={hw.percentile(50):.1f}ms "
                  f"p99={hw.percentile(99):.1f}ms wall")
    if eng.timed_out or eng.rejected:
        print(f"  resilience: {stats['summary']} "
              f"({len(eng.timed_out)} past deadline, "
              f"{len(eng.rejected)} shed at submit)")
    for r in reqs[:4]:
        print(f"  req {r.uid}: prompt {r.prompt} -> {r.out}")
    trace.stop()
    if args.metrics_out:
        eng.obs.write_jsonl(args.metrics_out,
                            extra={"run": "serve", "arch": args.arch,
                                   "requests": len(reqs)})
        print(f"  metrics snapshot appended to {args.metrics_out}")
    if args.trace_out:
        n = trace.export(args.trace_out)
        print(f"  trace: {n} events -> {args.trace_out}")
    return eng, reqs


if __name__ == "__main__":
    main()
