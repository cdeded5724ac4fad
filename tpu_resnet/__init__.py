"""tpu_resnet — a TPU-native deep-learning training framework.

A brand-new JAX/XLA/pjit framework with the capabilities of the reference
``michaelwfc/distributed-tensorflow-resnet`` repo (TF1 parameter-server +
Horovod ResNet trainer), designed TPU-first:

- One SPMD program over a ``jax.sharding.Mesh`` replaces the reference's
  entire ps/worker/gRPC + Horovod/MPI/NCCL machinery
  (reference: resnet_model.py:102-117, resnet_cifar_train.py:371-403).
- A typed config (``tpu_resnet.config``) replaces ~60 tf.app.flags
  re-declared per entry script (reference: resnet_cifar_main.py:32-97).
- Pure-function LR schedules of the step replace feed-dict mutating hooks
  (reference: resnet_cifar_train.py:291-311).
- Orbax checkpoints + a checkpoint-polling evaluator replace
  MonitoredTrainingSession saving + the eval sidecar
  (reference: resnet_cifar_eval.py:85-143).

Subpackages
-----------
``config``      typed run configuration + CLI
``data``        CIFAR binary / ImageNet TFRecord input pipelines (host side)
``models``      Flax ResNet-v2 (CIFAR 6n+2 and ImageNet 18-200) + MLP
``ops``         Pallas TPU kernels for hot ops
``parallel``    mesh construction, sharding, collectives, multi-host init
``train``       train state, optimizer, schedules, jitted step, loop, hooks
``evaluation``  eval-once and checkpoint-polling continuous evaluator
``obs``         step-time breakdown, event spans, run manifest, and the
                per-host /metrics + /healthz telemetry server
``export``      serialized inference export (freeze_graph equivalent)
``tools``       checkpoint inspector, predict, FLOP/param analysis
"""

import time as _time

# The first line of the package's import, on the clock every recorder
# reads: ``process.import`` starts here (obs/breakdown.py), and so does
# ``process.before_train`` where the process's own start cannot be read.
IMPORT_NS = _time.monotonic_ns()

__version__ = "0.1.0"
