"""Process-environment helpers whose import must not import jax.

Used by the driver entry (`__graft_entry__`), `tpu_resnet doctor` and the
scenario conductor, which spawn CPU-only children, and by every entry
point that compiles (`enable_compile_cache`).
"""

from __future__ import annotations

import json
import os
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# Fixed, never derived from a pid/tempdir/timestamp: the directory is part
# of the persistent cache's key, so a path that moves never hits.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")
_cache_counts: dict = {}  # filled by the one enable_compile_cache() call


def scrubbed_cpu_env(n_devices: int) -> dict:
    """A copy of the environment with the CPU platform forced and every
    TPU/backend-selection knob stripped, so a child process can only ever
    initialize the virtual-device CPU backend."""
    env = dict(os.environ)
    for key in list(env):
        if key.startswith(("TPU_", "LIBTPU", "PJRT_", "CLOUD_TPU")):
            del env[key]
    pypath = [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    env["PYTHONPATH"] = os.pathsep.join([_REPO_ROOT] + pypath)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    return env


def enable_compile_cache():
    """Turn on JAX's persistent compilation cache for this process; call
    once, before the first compile (and before ``parallel.initialize()`` —
    nothing here touches a backend). Returns the cache directory, or None
    when the cache stays off.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and no
    directory is set in code; otherwise the cache lives at the one fixed
    path ``<checkout>/.jax_cache``. The thresholds are zeroed on purpose:
    JAX's defaults skip programs that compile in under a second and small
    entries, which is exactly what the serve buckets of small models and
    the autotune probes are.

    At exit one ``COMPILE_CACHE {json}`` line goes to stderr with the
    compile requests and persistent-cache hits JAX counted; requests
    minus hits is the number of XLA compiles the process paid.
    """
    import jax

    if jax.config.jax_platforms == "cpu":
        # Off where the platform is pinned to the CPU (the tests): on
        # jaxlib 0.9.0 train() + resume in one process over a warm cache
        # no longer crashes or miscounts (6/6 runs identical to cold),
        # but XLA:CPU logs an error for every entry it loads — "machine
        # type used for compilation doesn't match ... could lead to
        # SIGILL" — so it does not vouch for its own reloads.
        return None
    if _cache_counts:
        return _cache_counts["dir"]  # second call in one process
    cache_dir = os.environ.get(COMPILE_CACHE_ENV)
    if cache_dir is None:
        cache_dir = DEFAULT_COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    import atexit

    _cache_counts.update(dir=cache_dir, requests=0, hits=0)
    events = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
              "/jax/compilation_cache/cache_hits": "hits"}

    def on_event(event, **_):
        if event in events:
            _cache_counts[events[event]] += 1

    jax.monitoring.register_event_listener(on_event)
    atexit.register(lambda: print(
        "COMPILE_CACHE " + json.dumps(_cache_counts),
        file=sys.stderr, flush=True))
    return cache_dir


def run_scrubbed_subprocess(argv, n_devices: int, timeout: int):
    """Run ``argv`` under ``scrubbed_cpu_env(n_devices)`` with merged
    stdout/stderr and a timeout that yields (124, partial_output) instead
    of raising — the one subprocess wrapper shared by the driver entry,
    the doctor's CPU-mesh check, and the pod-scaling proof (they had
    drifted: only one handled TimeoutExpired). Returns (rc, output)."""
    import subprocess

    try:
        proc = subprocess.run(argv, env=scrubbed_cpu_env(n_devices),
                              cwd=_REPO_ROOT, stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True,
                              timeout=timeout)
        return proc.returncode, proc.stdout
    except subprocess.TimeoutExpired as e:
        out = e.stdout or b""
        if isinstance(out, bytes):
            out = out.decode(errors="replace")
        return 124, out + f"\n[parent] timeout after {timeout}s"
    except Exception as e:  # spawn failure (missing interpreter etc.)
        print(f"[hostenv] subprocess spawn failed: {e}", file=sys.stderr)
        return 127, f"spawn failed: {e}"
