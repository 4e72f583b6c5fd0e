#!/usr/bin/env python3
"""Run one cell of the chip benchmark once and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell is found by name in ``BENCHMARK.json`` at the root of the
checkout, and everything it needs by the names there (see
``chipbench/harness.py``). The run refuses to start without a TPU, or with
fewer chips than the cell asks for. It builds the program's state from the
seed and warms up the cell's own shapes (set-up), measures for
``--seconds``, reads the device's peak memory, frees the program's state,
checks what the timed path produced against the plain reference, and
prints one JSON line last on stdout: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``, each number compared beside its limit.
With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the metrics are the
cell's per-layer metrics, read from the trace and the program.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                   "src")]


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def enable_cache(jax) -> None:
    """The program's persistent compile cache, at its fixed path in the
    checkout (or ``JAX_COMPILATION_CACHE_DIR``), for every program."""
    from repro.launch.compile_cache import enable_compile_cache
    log("compile cache:", enable_compile_cache())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def run(args, cell, jax, devices) -> int:
    from chipbench import harness, peaks as peaks_lib, tracing
    peaks = peaks_lib.peaks_for(devices[0].device_kind)
    job = cell.job_module().Job(cell, args.seed)
    job.setup()
    setup_s = time.perf_counter() - T0
    log(f"set-up {setup_s:.3f} s")
    trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_") \
        if args.trace else None
    try:
        if trace_dir:
            with jax.profiler.trace(trace_dir):
                with jax.profiler.TraceAnnotation(tracing.WINDOW):
                    job.window(args.seconds)
        else:
            job.window(args.seconds)
        used = devices[:cell.chips]
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in used)
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": int(peak)}
        result = {}
        if trace_dir:
            red = tracing.reduce(tracing.load(tracing.find_xplane(trace_dir)))
            ctx = {"cell": cell.name, "config": cell.config,
                   "traffic": cell.traffic, "peaks": peaks, "trace": red,
                   **job.layer_context()}
            metrics = harness.read_layers(cell, ctx)
            device.update(busy_s=red["busy_s"], window_s=red["window_s"])
            result["breakdown"] = red["breakdown"]
        else:
            values = {"setup_s": setup_s, **job.end_to_end(peak)}
            metrics = {m["name"]: {"value": values[m["name"]],
                                   "unit": m["unit"]}
                       for m in cell.end_to_end()}
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    attempted, failed = job.counts()
    job.free()
    t_check = time.perf_counter()
    correct, checks, detail = job.check(cell.limits)
    log("check detail:", json.dumps(detail))
    log(f"check took {time.perf_counter() - t_check:.3f} s")
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device,
              **result}
    harness.emit(result, checks)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from chipbench import harness
    cell = harness.Cell(args.workload)
    import jax
    enable_cache(jax)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        log(f"chip benchmark: {cell.name} needs {cell.chips} TPU chip(s); "
            f"JAX found {len(devices)} {devices[0].platform} device(s)")
        return 3
    return run(args, cell, jax, devices)


if __name__ == "__main__":
    sys.exit(main())
