#!/usr/bin/env bash
# Sync-BN vs per-replica-BN accuracy delta (VERDICT r2 item 6): the knob
# config.py offers "so the delta can be measured" — measured here on the
# freq100 hard task over the 8-device virtual CPU mesh (per-replica batch
# 128/8 = 16, the regime where the reference observed its distributed
# accuracy gap, reference README.md:36). Single-chip TPU can't show the
# delta (1 device ⇒ the modes coincide), so this runs on CPU; the TPU
# battery SIGSTOPs it while measuring (the box has one core).
#
# Command lines contain "conv_bn" so tools/tpu_battery.sh can pause and
# resume the whole family with pkill -STOP/-CONT -f conv_bn.
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
  train.eval_batch_size=64 train.image_summary_every=0 \
  optim.schedule=cifar_piecewise optim.boundaries=(600,900,1100) \
  optim.values=(0.1,0.01,0.001,0.0001)"

for mode in sync replica; do
  [ "$mode" = sync ] && flag=true || flag=false
  out="$DEST/bn_$mode"
  if [ -f "$out/best_precision.json" ]; then
    echo "[bn_delta] $mode already done"; continue
  fi
  echo "[bn_delta] arm $mode (sync_bn=$flag) start $(date -u +%T)"
  rm -rf "/tmp/conv_bn_$mode"
  env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    nice -n 19 python -m tpu_resnet train_and_eval $COMMON \
    model.sync_bn=$flag train.train_dir="/tmp/conv_bn_$mode" 2>&1 | tail -4
  mkdir -p "$out"
  cp "/tmp/conv_bn_$mode/metrics.jsonl" "$out/train_metrics.jsonl"
  cp "/tmp/conv_bn_$mode/eval/metrics.jsonl" "$out/eval_metrics.jsonl" \
    2>/dev/null || true
  cp "/tmp/conv_bn_$mode/eval/best_precision.json" "$out/" 2>/dev/null || true
  echo "[bn_delta] arm $mode done $(date -u +%T)"
done

python - "$DEST" <<'EOF'
import json, os, sys
dest = sys.argv[1]
out = {}
for m in ("sync", "replica"):
    p = os.path.join(dest, f"bn_{m}", "best_precision.json")
    if os.path.exists(p):
        out[f"bn_{m}"] = json.load(open(p))
json.dump(out, open(os.path.join(dest, "bn_delta.json"), "w"), indent=2)
print("[bn_delta] summary:", json.dumps(out))
EOF
