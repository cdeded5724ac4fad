"""Device mesh construction — the TPU-native replacement for the reference's
entire cluster topology layer.

Where the reference assembles ``ps_hosts``/``worker_hosts`` strings, starts a
gRPC ``tf.train.Server`` per task and places variables on parameter servers
(reference resnet_cifar_train.py:371-403), a JAX program sees every chip in
the slice and expresses distribution as shardings over one
``jax.sharding.Mesh``. Gradient aggregation becomes an XLA all-reduce over
ICI — the single code path that subsumes the reference's PS-sync, async-PS
and Horovod modes (SURVEY.md §2.3).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def create_mesh(mesh_cfg=None, devices: Optional[Sequence[jax.Device]] = None
                ) -> Mesh:
    """Build a (data, model) mesh from MeshConfig.

    ``data=-1`` consumes all devices not claimed by other axes. Reference
    parity only needs the data axis; the model axis (default size 1) keeps
    tensor-style shardings expressible without redesign.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    model = getattr(mesh_cfg, "model", 1) if mesh_cfg is not None else 1
    data = getattr(mesh_cfg, "data", -1) if mesh_cfg is not None else -1
    if data == -1:
        if n % model:
            raise ValueError(f"{n} devices not divisible by model={model}")
        data = n // model
    if data * model != n:
        raise ValueError(f"mesh {data}x{model} != {n} devices")
    axis_names = tuple(getattr(mesh_cfg, "axis_names", ("data", "model"))
                       if mesh_cfg is not None else ("data", "model"))
    dev_array = np.asarray(devices).reshape(data, model)
    return Mesh(dev_array, axis_names)


def fit_mesh(mesh_cfg, n_devices: int):
    """``(data, model, downsized)`` axis sizes that actually fit on
    ``n_devices`` — the elastic-resume primitive (resilience/elastic.py):
    a run that asked for ``mesh.data=8`` but restarted on a host with 4
    chips gets the 4-way mesh it CAN have instead of a dead ValueError.

    The ``model`` axis is a hard constraint (its sharded tensors cannot
    be re-divided without a different partition plan); the ``data`` axis
    is the elastic one: ``-1`` follows the hardware in both directions
    (a device count the model axis doesn't divide drops the remainder —
    7 devices at model=2 train on 6, reported as downsized), an explicit
    size that no longer fits shrinks to the largest whole multiple the
    devices support. Growth is never implicit for an explicit ``data``
    size — the operator asked for that many."""
    model = getattr(mesh_cfg, "model", 1) if mesh_cfg is not None else 1
    data = getattr(mesh_cfg, "data", -1) if mesh_cfg is not None else -1
    if model < 1 or n_devices < model:
        raise ValueError(
            f"mesh model axis {model} cannot fit on {n_devices} "
            f"device(s) — the model axis is not elastic")
    if data != -1 and data < 1:
        raise ValueError(
            f"mesh.data must be -1 (all remaining devices) or >= 1, "
            f"got {data}")
    avail = n_devices // model
    if data == -1:
        return avail, model, avail * model != n_devices
    if data <= avail:
        return data, model, False
    return avail, model, True


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Leading (batch) axis split over 'data'."""
    return NamedSharding(mesh, P("data"))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def staged_batch_sharding(mesh: Mesh) -> NamedSharding:
    """For (stage, batch, ...) superbatches: batch axis (axis 1) split over
    'data', stage axis replicated (pipeline.staged_device_prefetch)."""
    return NamedSharding(mesh, P(None, "data"))


def local_batch_size(global_batch: int, mesh: Mesh) -> int:
    """Per-process batch for the host input pipeline.

    The mesh carries the full divisibility story: the global batch must
    split evenly over the processes feeding it AND over the mesh's
    ``data`` axis consuming it — a batch that divides the process count
    but not the data axis would pass here and then die later inside jit
    with an opaque sharding error, so both are checked up front with the
    mesh named in the message."""
    n_proc = jax.process_count()
    if global_batch % n_proc:
        raise ValueError(
            f"global batch {global_batch} not divisible by {n_proc} "
            f"processes (mesh {dict(mesh.shape)})")
    check_divisible(global_batch, mesh)
    return global_batch // n_proc


def check_divisible(global_batch: int, mesh: Mesh) -> None:
    n_data = mesh.shape["data"]
    if global_batch % n_data:
        raise ValueError(
            f"global batch {global_batch} not divisible by data axis {n_data}")
