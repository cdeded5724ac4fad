#!/usr/bin/env bash
# CPU understudy of the recipe rehearsal (VERDICT r4 item 6): the full
# 90k-step cadence (battery stage 70) stays armed for the chip; this runs
# the SAME orchestration — piecewise-LR boundaries, checkpoint cadence,
# eval sidecar, resume-across-interruption, decay-boundary extraction —
# compressed to CPU scale, so the machinery is proven even if no live
# window ever opens.
#
# Two-phase on purpose: phase 1 is killed mid-run (a simulated window
# close / preemption); phase 2 must RESUME from the latest checkpoint —
# the log line "resumed from step N" (train/loop.py) and a
# monotonically-continuing step series are the proof, recorded in the
# summary as resume_proven.
#
#   tools/recipe_rehearsal_understudy.sh [DEST] [STEPS B1 B2 B3 CKPT]
#
# Defaults: 900 steps, boundaries 400/600/800, ckpt every 100 — the same
# 5:45/60/90-ish proportions as the real 90k/40k/60k/80k/1000 recipe.
set -euo pipefail
REPO="$(cd "$(dirname "$0")/.." && pwd)"
RND="$(cat "$REPO/tools/BATTERY_ROUND")"
DEST="${1:-$REPO/docs/runs/recipe_rehearsal_cpu_r${RND}}"
STEPS="${2:-900}"; B1="${3:-400}"; B2="${4:-600}"; B3="${5:-800}"
CKPT="${6:-100}"
# Phase 1 must LIVE past the first checkpoint (step CKPT) or phase 2 has
# nothing to resume from: at the 1-core box's measured ~0.54 st/s plus
# ~40 s of compile, 300 s lands at step ~140 > 100.
PHASE1_TIMEOUT="${PHASE1_TIMEOUT:-300}"
RUN="${RUN_DIR:-/tmp/recipe_rehearsal_cpu}"
mkdir -p "$DEST"
cd "$REPO"

# A CPU rehearsal: the platform is forced.
run_trainer() {
  local subcmd="$1" tmo="$2"
  timeout -k 15 "$tmo" env JAX_PLATFORMS=cpu \
    python -m tpu_resnet "$subcmd" --preset smoke \
    data.synthetic_learnable=true data.synthetic_task=freq100 \
    data.synthetic_classes=100 data.synthetic_label_noise=0.1 \
    data.synthetic_train_examples=2048 data.synthetic_eval_examples=512 \
    model.resnet_size=8 model.compute_dtype=float32 \
    train.global_batch_size=32 train.eval_batch_size=32 \
    train.train_steps="$STEPS" train.checkpoint_every="$CKPT" \
    train.log_every=20 train.image_summary_every=0 \
    optim.schedule=cifar_piecewise "optim.boundaries=($B1,$B2,$B3)" \
    "optim.values=(0.1,0.01,0.001,0.0001)" \
    train.train_dir="$RUN"
}

rm -rf "$RUN"
echo "[understudy] phase 1: train until interrupted (${PHASE1_TIMEOUT}s)"
set +e
run_trainer train "$PHASE1_TIMEOUT" > "$DEST/phase1.log" 2>&1
p1=$?
set -e
tail -3 "$DEST/phase1.log" || true
if [ "$p1" -eq 0 ]; then
  echo "[understudy] phase 1 finished before the interrupt — increase" \
       "STEPS or lower PHASE1_TIMEOUT for a real resume proof"
fi

echo "[understudy] phase 2: train_and_eval resumes to completion"
run_trainer train_and_eval 3600 > "$DEST/phase2.log" 2>&1
tail -5 "$DEST/phase2.log"

RESUME=""
if grep -q "resumed from step" "$DEST/phase2.log"; then
  RESUME="--resume-proven"
  echo "[understudy] resume across interruption: PROVEN"
else
  echo "[understudy] WARNING: no resume line in phase 2 (phase 1 too short?)"
fi

cp "$RUN/metrics.jsonl" "$DEST/train_metrics.jsonl"
cp "$RUN/eval/metrics.jsonl" "$DEST/eval_metrics.jsonl" 2>/dev/null || true
cp "$RUN/eval/best_precision.json" "$DEST/" 2>/dev/null || true

python tools/rehearsal_summary.py "$DEST" "$B1" "$B2" "$B3" "$CKPT" \
  $RESUME \
  --what "CPU understudy of the 40k/60k/80k recipe orchestration (compressed ${STEPS}-step run, boundaries $B1/$B2/$B3, ckpt every $CKPT, interrupt+resume, live eval sidecar)"
