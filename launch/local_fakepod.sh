#!/usr/bin/env bash
# Single-process fake pod: N virtual CPU devices in one process — the
# smallest way to exercise the data-parallel mesh without hardware.
# Replaces the reference's localhost smoke configs
# (mkl-scripts/run_local.sh, run_dist_tf_local.sh: batch 10, 100 steps).
#
#   ./launch/local_fakepod.sh [num_devices] [extra overrides...]
set -euo pipefail
cd "$(dirname "$0")/.."

N="${1:-8}"; shift || true
export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=${N} ${XLA_FLAGS:-}"

exec python -m tpu_resnet train --preset smoke \
    train.train_dir=/tmp/tpu_resnet/fakepod \
    train.global_batch_size=$((N * 2)) \
    "$@"
