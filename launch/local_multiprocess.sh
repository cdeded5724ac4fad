#!/usr/bin/env bash
# Multi-PROCESS fake cluster on one machine: P processes × D virtual CPU
# devices rendezvous via jax.distributed on a local port — the TPU-native
# analog of the reference's localhost ps/worker cluster
# (mkl-scripts/submit_mac_dist.sh: 1 ps + 2 workers on ports 2230/2220+).
# Validates the real multi-host code path (coordinator rendezvous,
# per-process input shards, cross-process all-reduce) with zero hardware.
# Also the zero1 rehearsal vehicle (docs/PARALLELISM.md): pass
# mesh.partition=zero1 as an override to drill cross-replica optimizer
# sharding across real process boundaries.
#
#   ./launch/local_multiprocess.sh [P] [D] [extra overrides...]
set -euo pipefail
cd "$(dirname "$0")/.."

P="${1:-2}"; shift || true
D="${1:-4}"; shift || true
# Probe for a FREE port instead of rolling RANDOM: a collision with any
# listener (or a previous rehearsal's surviving coordinator) used to
# hang every process in rendezvous until the distributed-init timeout.
# The kernel hands out an unused ephemeral port; the tiny bind-to-launch
# race window is harmless next to a 1-in-dozens collision per run.
PORT=$(python3 -c 'import socket; s = socket.socket();
s.bind(("127.0.0.1", 0)); print(s.getsockname()[1]); s.close()')
LOGDIR="${LOGDIR:-/tmp/tpu_resnet/multiproc}"
mkdir -p "$LOGDIR"

pids=()
for ((i = 0; i < P; i++)); do
  JAX_PLATFORMS=cpu \
  XLA_FLAGS="--xla_force_host_platform_device_count=${D}" \
  TPU_COORDINATOR_ADDRESS="127.0.0.1:${PORT}" \
  TPU_NUM_PROCESSES="$P" \
  TPU_PROCESS_ID="$i" \
  python -m tpu_resnet train --preset smoke \
      train.train_dir="$LOGDIR/run" \
      train.global_batch_size=$((P * D * 2)) \
      "$@" > "$LOGDIR/proc.$i.log" 2>&1 &
  pids+=($!)
done
echo "launched $P processes on port $PORT (logs: $LOGDIR/proc.*.log)"

# Fail fast: the first nonzero exit kills the survivors instead of
# leaving them wedged in a dead collective until the full timeout set
# drains (one crashed process means the rendezvous group is already
# broken — the others can only hang or crash later).
code=0
remaining=$P
while ((remaining > 0)); do
  rc=0
  wait -n || rc=$?
  if ((rc == 0)); then
    remaining=$((remaining - 1))
    continue
  fi
  code=$rc
  echo "a process exited rc=$code — killing $((remaining - 1)) survivor(s)" >&2
  kill "${pids[@]}" 2>/dev/null || true
  wait || true
  break
done
exit $code
