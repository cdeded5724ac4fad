# Container image for launch/docker_cluster.sh — the analog of the
# TF+Horovod images the reference's docker launchers assume
# (start-resnet-cifar-train.sh docker exec payloads). Pins the one
# installation the code is written and tested against (pyproject.toml);
# this default targets TPU VM hosts.
FROM python:3.12-slim

# g++ + libjpeg-turbo headers: the native loader's fast JPEG path.
RUN apt-get update && apt-get install -y --no-install-recommends \
        g++ libjpeg-dev && rm -rf /var/lib/apt/lists/*
RUN pip install --no-cache-dir "jax==0.9.0" "jaxlib==0.9.0" \
    "libtpu==0.0.34" "flax==0.12.3" "optax==0.2.6" \
    "orbax-checkpoint==0.11.32" numpy pillow

WORKDIR /workspace
COPY . /workspace
# Build the native C++ data plane (falls back to numpy loaders if absent).
RUN python -m tpu_resnet.native.build || true

ENTRYPOINT []
CMD ["python", "-m", "tpu_resnet", "train", "--preset", "smoke"]
