#!/usr/bin/env bash
# LR-schedule ablation on the freq100 hard task (VERDICT r2 item 6):
# compressed piecewise (the reference's 40k/60k/80k CIFAR recipe scaled
# to the step budget, reference resnet_cifar_train.py:302-311) vs
# constant LR, identical everything else. CPU-mesh scale (resnet8 b64
# 1200 steps) so it runs without a TPU window; the TPU-scale version is
# the r3 battery convergence stage (artifacts: docs/runs/convergence_freq100). The piecewise arm's config is identical
# to tools/convergence_bn_delta.sh's bn_sync arm — if that artifact
# exists it is reused rather than re-run.
#
# Command lines contain "sched_" so tools/tpu_battery.sh pauses these
# while TPU timing runs.
set -euo pipefail
REPO="$(cd "$(dirname "$0")/.." && pwd)"
DEST="$REPO/docs/runs/convergence_freq100"
mkdir -p "$DEST"
cd "$REPO"

COMMON="--preset smoke data.synthetic_learnable=true \
  data.synthetic_task=freq100 data.synthetic_classes=100 \
  data.synthetic_label_noise=0.1 data.synthetic_train_examples=8192 \
  data.synthetic_eval_examples=2048 model.resnet_size=8 \
  train.global_batch_size=64 train.train_steps=1200 \
  train.checkpoint_every=500 train.log_every=100 \
  train.eval_batch_size=64 train.image_summary_every=0"

run_arm () {
  name="$1"; shift
  out="$DEST/sched_$name"
  if [ -f "$out/best_precision.json" ]; then
    echo "[sched] $name already done"; return
  fi
  if [ "$name" = piecewise ] && [ -f "$DEST/bn_sync/best_precision.json" ]; then
    echo "[sched] piecewise == bn_sync arm (identical config); reusing"
    mkdir -p "$out"
    cp "$DEST/bn_sync/"* "$out/"
    return
  fi
  echo "[sched] arm $name start $(date -u +%T)"
  rm -rf "/tmp/sched_${name}_arm"
  env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    nice -n 19 python -m tpu_resnet train_and_eval $COMMON "$@" \
    train.train_dir="/tmp/sched_${name}_arm" 2>&1 | tail -3
  mkdir -p "$out"
  cp "/tmp/sched_${name}_arm/metrics.jsonl" "$out/train_metrics.jsonl"
  cp "/tmp/sched_${name}_arm/eval/metrics.jsonl" "$out/eval_metrics.jsonl" \
    2>/dev/null || true
  cp "/tmp/sched_${name}_arm/eval/best_precision.json" "$out/" \
    2>/dev/null || true
  echo "[sched] arm $name done $(date -u +%T)"
}

run_arm piecewise "optim.schedule=cifar_piecewise" \
  "optim.boundaries=(600,900,1100)" "optim.values=(0.1,0.01,0.001,0.0001)"
run_arm constant "optim.schedule=constant" "optim.base_lr=0.1"

python - "$DEST" <<'EOF'
import json, os, sys
dest = sys.argv[1]
out = {}
for arm in ("piecewise", "constant"):
    p = os.path.join(dest, f"sched_{arm}", "best_precision.json")
    if os.path.exists(p):
        out[arm] = json.load(open(p))
json.dump(out, open(os.path.join(dest, "schedule_ablation.json"), "w"),
          indent=2)
print("[sched] summary:", json.dumps(out))
EOF
