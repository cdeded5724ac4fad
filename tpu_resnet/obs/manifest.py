"""Run manifest — one ``manifest.json`` per run, written at startup.

The reference scattered run provenance across shell scripts, flag dumps
and whatever the operator remembered to note (SURVEY.md §2.2's results
artifacts are bare CSVs with no config attached); reproducing a run meant
archaeology. The manifest pins everything needed to re-run or audit:

- the fully-resolved config (post-preset, post-overrides),
- mesh topology, device kinds/counts, process count,
- package + python + jax versions, git revision when available,
- hostname, argv and a wall-clock timestamp.

Written once by the primary process (the chief-only rule every other
writer follows, reference resnet_cifar_train.py:337), atomically (tmp +
rename) so a crash mid-write never leaves a torn manifest.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time
import uuid
from typing import Optional

SCHEMA_VERSION = 2
RUN_ID_FILE = "run_id.json"


def ensure_run_id(train_dir: str, create: bool = True) -> Optional[str]:
    """The run's correlation id — one short hex token shared by every
    process that touches this train_dir (trainer, eval sidecar, serve,
    loadgen, supervise) so their artifacts can be laid on one timeline
    (obs/trace.py) and joined in logs.

    Persisted in ``<train_dir>/run_id.json`` and REUSED across resumes:
    a preempt/resume cycle is one run on one timeline, not three. With
    ``create=False`` (read-only consumers: eval sidecar, serve, tools)
    a missing file returns None instead of minting an id the trainer
    doesn't know about."""
    path = os.path.join(train_dir, RUN_ID_FILE)
    try:
        with open(path) as f:
            rid = json.load(f).get("run_id")
            if rid:
                return str(rid)
    except (OSError, ValueError):
        pass
    if not create:
        return None
    rid = uuid.uuid4().hex[:12]
    try:
        os.makedirs(train_dir, exist_ok=True)
        tmp = path + f".tmp{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"run_id": rid, "created_at": time.time(),
                       "hostname": socket.gethostname()}, f)
        os.replace(tmp, path)
    except OSError:
        pass  # correlation id is best-effort; the run must not die for it
    return rid


def read_run_id(train_dir: str) -> Optional[str]:
    """Read-only run_id lookup (sidecars/tools); None when the trainer
    hasn't created one."""
    return ensure_run_id(train_dir, create=False)


def _git_rev() -> Optional[str]:
    """Best-effort git revision of the package checkout; None outside a
    work tree (installed wheel, bundled container)."""
    here = os.path.dirname(os.path.abspath(__file__))
    try:
        proc = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=here,
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True, timeout=5)
    except (OSError, subprocess.TimeoutExpired):
        return None
    rev = proc.stdout.strip()
    return rev if proc.returncode == 0 and rev else None


def library_versions() -> dict:
    """jax / jaxlib / libtpu as installed (libtpu None where there is no
    TPU runtime) — with the device block, what names the machine a
    number came from."""
    import importlib.metadata

    import jax
    import jaxlib

    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu}


def device_record(devices) -> dict:
    """The device block of the manifest and of the server's ``/info``:
    platform, device kinds and count exactly as JAX reports them."""
    devices = list(devices)
    return {
        "count": len(devices),
        "kinds": sorted({d.device_kind for d in devices}),
        "platform": devices[0].platform if devices else None,
    }


def build_manifest(cfg, mesh, run_id: Optional[str] = None,
                   extra: Optional[dict] = None) -> dict:
    """Assemble the manifest dict (pure; no filesystem writes).
    ``extra`` top-level entries are merged in — e.g. the elastic-resume
    ``topology_change`` record (resilience/elastic.py), so a capacity
    reshape is auditable from the manifest alone."""
    import jax

    import tpu_resnet

    manifest = {
        "schema": SCHEMA_VERSION,
        "run_id": run_id,
        "created_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "config": cfg.to_dict(),
        "mesh": {"shape": dict(mesh.shape),
                 "axis_names": list(mesh.axis_names)},
        "devices": device_record(mesh.devices.flat),
        "processes": {"count": jax.process_count(),
                      "index": jax.process_index()},
        "versions": {
            "tpu_resnet": getattr(tpu_resnet, "__version__", None),
            "python": sys.version.split()[0],
            **library_versions(),
        },
        "git_rev": _git_rev(),
        "hostname": socket.gethostname(),
        "argv": list(sys.argv),
    }
    if extra:
        manifest.update(extra)
    return manifest


def write_manifest(train_dir: str, cfg, mesh,
                   run_id: Optional[str] = None,
                   extra: Optional[dict] = None) -> Optional[str]:
    """Write ``<train_dir>/manifest.json`` (primary process only; atomic).
    Returns the path, or None on a non-primary process."""
    from tpu_resnet import parallel

    if not parallel.is_primary():
        return None
    os.makedirs(train_dir, exist_ok=True)
    path = os.path.join(train_dir, "manifest.json")
    tmp = path + f".tmp{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(build_manifest(cfg, mesh, run_id=run_id, extra=extra),
                  f, indent=1, default=list)
    os.replace(tmp, path)
    return path
