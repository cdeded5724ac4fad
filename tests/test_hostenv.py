"""hostenv.enable_compile_cache: where JAX's persistent compilation cache
goes. Run in child processes — the helper configures process-global jax
state once, and the test process itself is pinned to the CPU platform,
where the helper (deliberately) leaves the cache off."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Reports what the helper returned, every jax.config.update key it issued,
# where jax then believes the cache is, and whether a backend was touched.
_PROBE = """
import json, sys
import jax
updates = []
real = jax.config.update
jax.config.update = lambda k, v: (updates.append(k), real(k, v))[1]
from tpu_resnet.hostenv import enable_compile_cache
first, second = enable_compile_cache(), enable_compile_cache()
import jax._src.xla_bridge as xb
print(json.dumps({"returned": [first, second], "updates": updates,
                  "dir": jax.config.jax_compilation_cache_dir,
                  "min_secs": jax.config.jax_persistent_cache_min_compile_time_secs,
                  "min_bytes": jax.config.jax_persistent_cache_min_entry_size_bytes,
                  "backend_touched": bool(xb._backends)}))
"""


def _probe(**env_changes):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")}
    env.update(env_changes)
    proc = subprocess.run([sys.executable, "-c", _PROBE], cwd=REPO, env=env,
                          stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


def test_unset_variable_means_one_fixed_path_in_the_checkout():
    a, stderr = _probe()
    b, _ = _probe()  # a second process lands on the same directory
    want = os.path.join(REPO, ".jax_cache")
    assert a["returned"] == [want, want] and a["dir"] == want
    assert b["dir"] == want
    # deliberate thresholds: sub-second programs and small entries count
    assert a["min_secs"] == 0.0 and a["min_bytes"] == -1
    # set once even when called twice; no backend initialized by it
    assert a["updates"].count("jax_compilation_cache_dir") == 1
    assert not a["backend_touched"]
    # the exit line chip_smoke.py reads
    (line,) = [l for l in stderr.splitlines()
               if l.startswith("COMPILE_CACHE ")]
    assert json.loads(line[len("COMPILE_CACHE "):]) == {
        "dir": want, "requests": 0, "hits": 0}


def test_set_variable_means_no_directory_is_set_in_code(tmp_path):
    placed = str(tmp_path / "placed_from_outside")
    out, _ = _probe(JAX_COMPILATION_CACHE_DIR=placed)
    assert "jax_compilation_cache_dir" not in out["updates"]
    assert out["dir"] == placed          # jax read the variable itself
    assert out["returned"] == [placed, placed]
    assert out["min_secs"] == 0.0 and out["min_bytes"] == -1


def test_cpu_platform_leaves_the_cache_off():
    out, stderr = _probe(JAX_PLATFORMS="cpu")
    assert out["returned"] == [None, None] and out["updates"] == []
    assert out["dir"] is None and "COMPILE_CACHE" not in stderr
