#!/usr/bin/env python3
"""Readings that the limits of a training cell's check are set from.

    python3 benchmarks/chip/calibrate.py --workload <cell> \\
        --seeds 11,12,13 --control-seeds 11,12,13 --out <file.jsonl>

For each seed, in one process: the program's sound run (set-up only: the
checked steps through the trainer's own loop, no window) against the plain
f32 reference; and for the control seeds also the control (the reference
in float8, put in the program's place) and the planted fault of a loss
mean taken over half of the rows' tokens (the reference with
``loss_tokens="first_half"`` in the program's place), each against the f32
reference. One JSON line per seed goes to stdout and to ``--out``. A state
left unchanged reads 1 on ``change`` by construction and needs no run.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                   "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import jax

    import run as runmod
    from chipbench import harness
    runmod.enable_cache(jax)
    cell = harness.Cell(args.workload)
    job_mod = cell.job_module()
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        job = job_mod.Job(cell, seed)
        job.setup()
        job.free()
        ref = job.reference("f32")
        line = {"seed": seed, "program": job_mod.compare(job.readings, ref),
                "ref_losses": ref["losses"],
                "program_losses": job.readings["losses"]}
        if seed in controls:
            for key, precision, tokens in (("control_fp8", "fp8", "all"),
                                           ("fault_half_tokens", "f32",
                                            "first_half")):
                other = job.reference(precision, loss_tokens=tokens)
                line[key] = job_mod.compare(other, ref)
                line[key + "_losses"] = other["losses"]
        line["seconds"] = time.perf_counter() - t0
        text = json.dumps(line)
        print(text, flush=True)
        with open(args.out, "a") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
