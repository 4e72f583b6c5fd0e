#!/usr/bin/env bash
# One-command regression gate: tier-1 tests + core smoke + a host-mesh
# dry-run through the repro.dist spec engine + the 2-device host-mesh
# smoke (compressed-DP, per_layer x grad_accum, distributed fused) + the
# llama_7b fsdp placement gate + paged serve smokes (gathered-view and
# paged-attention-kernel decode) + resilience smokes (chaos kill@3 ->
# relaunch -> bit-exact resume; serve slot-stall under a deadline with
# zero wedged requests). Run from anywhere.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# one unambiguous machine-greppable line naming the property-test engine
if python -c "import hypothesis" 2>/dev/null; then
  echo "property-engine: hypothesis $(python -c 'import hypothesis; print(hypothesis.__version__)') (full shrinking; pin: requirements-dev.txt)"
else
  echo "property-engine: propshim (tests/_propshim.py seeded-loop fallback — no shrinking, fixed examples; install requirements-dev.txt for hypothesis)"
fi

echo "== tier-1: pytest =="
python -m pytest -x -q

echo "== smoke: core SLTrain invariants =="
python scripts/smoke_core.py

echo "== dry-run: llama_60m x train_4k on the 256-chip host mesh =="
python -m repro.launch.dryrun --arch llama_60m --cell train_4k

echo "== host-mesh smoke: compressed-DP + wire model, per_layer+grad_accum=2, fused TP=2 =="
python scripts/hostmesh_smoke.py

echo "== fsdp gate: llama_7b placement residency + lower on the 8-device host mesh =="
python scripts/fsdp_dryrun.py

echo "== fused smoke: exec_mode=fused 3-step train on the Pallas path =="
python -m repro.launch.train --arch llama_60m --smoke --mode sltrain \
  --exec-mode fused --steps 3 --batch 2 --seq 16 --log-every 1 \
  --ckpt-dir "$(mktemp -d)"

echo "== per-layer smoke: update_mode=per_layer 8-bit 3-step train =="
OBS_DIR="$(mktemp -d)"
python -m repro.launch.train --arch llama_60m --smoke --mode sltrain \
  --update-mode per_layer --optimizer adam8bit --steps 3 --batch 2 --seq 16 \
  --log-every 1 --ckpt-dir "$(mktemp -d)" \
  --metrics-out "$OBS_DIR/train.jsonl" --trace-out "$OBS_DIR/train_trace.json"

echo "== serve smoke: paged KV engine, 3 staggered requests =="
python -m repro.launch.serve --arch llama_60m --smoke --paged --block-len 8 \
  --requests 3 --stagger --slots 2 --new-tokens 4 --max-len 64

echo "== serve smoke: paged-attention kernel decode =="
python -m repro.launch.serve --arch llama_60m --smoke --paged \
  --attn-kernel paged --block-len 8 --requests 3 --stagger --slots 2 \
  --new-tokens 4 --max-len 64

echo "== serve smoke: continuous batching + copy-on-write prefix sharing =="
python -m repro.launch.serve --arch llama_60m --smoke --paged --block-len 8 \
  --stream --prefix-sharing --requests 4 --slots 2 --new-tokens 4 \
  --max-len 64 --metrics-out "$OBS_DIR/serve.jsonl" \
  --trace-out "$OBS_DIR/serve_trace.json"

echo "== quant smoke: train -> calibrate -> int8 serve =="
QDIR="$(mktemp -d)"
python -m repro.launch.train --arch llama_60m --smoke --mode sltrain \
  --steps 3 --batch 2 --seq 16 --log-every 1 --ckpt-dir "$QDIR/ckpt"
python -m repro.quant.calibrate --arch llama_60m --smoke \
  --ckpt-dir "$QDIR/ckpt" --out "$QDIR/quant"
python -m repro.launch.serve --arch llama_60m --smoke --paged --block-len 8 \
  --quant-ckpt "$QDIR/quant" --requests 4 --slots 2 --new-tokens 4 \
  --max-len 64 --metrics-out "$OBS_DIR/serve.jsonl"

echo "== resilience smoke: chaos kill@3 -> relaunch -> exact resume =="
RDIR="$(mktemp -d)"
python -m repro.launch.train --arch llama_60m --smoke --steps 6 --batch 2 \
  --seq 16 --log-every 1 --ckpt-every 2 --ckpt-dir "$RDIR/ref" \
  > "$RDIR/ref.log"
rc=0
python -m repro.launch.train --arch llama_60m --smoke --steps 6 --batch 2 \
  --seq 16 --log-every 1 --ckpt-every 2 --ckpt-dir "$RDIR/chaos" \
  --chaos kill@3 > "$RDIR/killed.log" 2>&1 || rc=$?
if [ "$rc" -ne 43 ]; then
  echo "chaos kill did not exit 43 (got $rc)"; exit 1
fi
python -m repro.launch.train --arch llama_60m --smoke --steps 6 --batch 2 \
  --seq 16 --log-every 1 --ckpt-every 2 --ckpt-dir "$RDIR/chaos" \
  > "$RDIR/resumed.log"
grep -q "resumed from step 2" "$RDIR/resumed.log"
diff <(grep '^final step' "$RDIR/ref.log") \
     <(grep '^final step' "$RDIR/resumed.log")
echo "resilience smoke: killed at step 3 (exit 43), resumed from step 2, final loss bit-exact"

echo "== resilience smoke: serve slot-stall + deadline, zero wedged =="
python -m repro.launch.serve --arch llama_60m --smoke --paged --block-len 8 \
  --stream --requests 4 --slots 2 --new-tokens 6 --max-len 64 \
  --chaos "stall@4:64" --deadline-ticks 24 \
  --metrics-out "$OBS_DIR/serve_chaos.jsonl"
python - "$OBS_DIR" <<'EOF'
import json, sys
m = json.loads(open(f"{sys.argv[1]}/serve_chaos.jsonl").read()
               .splitlines()[-1])["metrics"]
assert m["resilience.faults_injected{kind=stall}"]["value"] > 0, m
assert m["serve.deadline_exceeded"]["value"] > 0, \
    "stall@4:64 under a 24-tick deadline must cancel at least one request"
print("resilience smoke: stall injected, deadline cancellation counted, "
      "engine drained")
EOF

echo "== obs smoke: metrics JSONL parses, traces validate =="
python - "$OBS_DIR" <<'EOF'
import json, sys
from repro.obs import trace as obs_trace
d = sys.argv[1]
for name in ("train", "serve"):
    lines = [json.loads(l) for l in open(f"{d}/{name}.jsonl")]
    assert lines and all("metrics" in l and "ts" in l for l in lines), name
    n = obs_trace.validate_file(f"{d}/{name}_trace.json")
    print(f"obs smoke: {name}: {len(lines)} JSONL line(s), "
          f"{n} valid trace events")
tm = lines  # serve lines from the loop's last iteration
h = tm[-1]["metrics"].get("serve.ttft_ticks")
assert h and h["count"] > 0 and "p50" in h, h
# wall-clock TTFT must be populated on every serve run (SLO currency):
# present, non-empty, and with a finite sum
hw = tm[-1]["metrics"].get("serve.ttft_wall_ms")
assert hw and hw["count"] > 0 and hw["sum"] >= 0, hw
EOF

echo "ci_check: all gates passed"
