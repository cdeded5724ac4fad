"""Environment triage — ``python -m tpu_resnet doctor``.

The reference assumes a working cluster and fails with raw stack traces
when it isn't (e.g. a dead gRPC peer hangs the session, reference
resnet_cifar_train.py:330-344). On TPU the equivalent operational hazards
are a wedged PJRT plugin (backend init that blocks forever with no
message), a missing native data plane, and a dataset directory that
doesn't match the expected layout. ``doctor`` checks each one with
timeouts and prints one line per check plus a final machine-readable JSON
summary — the triage an operator runs before filing the train job.

Checks:
  versions   python/jax/jaxlib/flax/optax/orbax versions
  backend    device probe in a short-timeout subprocess (a hanging
             plugin costs seconds, not a hung job); platform, device
             kind, device count
  cpu_mesh   virtual multi-device CPU mesh + one jitted SPMD reduction
             (proves the sharding machinery without an accelerator)
  native     C++ data plane: built? JPEG decode enabled? (attempts a
             lazy build exactly like first use does)
  dataset    optional --data-dir layout validation (CIFAR binary names /
             ImageNet shard pattern)
  telemetry  optional --train-dir scrape of the run's telemetry server
             (port from <train_dir>/telemetry.json): /metrics parses as
             Prometheus text and /healthz reports a fresh heartbeat
  data_bench optional (--data-bench): ~20 s synthetic-JPEG decode
             throughput probe — images/sec at 1 vs N worker processes
             through the shared-memory data engine plus the implied max
             sustainable steps/sec at global batch 128, so an operator
             can tell host-bound from chip-bound without a full bench
             run (the same probe backs bench.py's host_decode
             worker-scaling curve)
  check      optional (--check): the static-analysis suite (tpu_resnet/
             analysis): AST lints for the repo's JAX/TPU contracts plus
             the config-matrix abstract verifier with golden jaxpr
             hashes and the golden memory-budget engine — `python -m
             tpu_resnet check` for operators who want one doctor line
             instead of the full report
  serve_probe  optional (--serve-probe): a live predict-server smoke —
             train a tiny MLP, start ``tpu_resnet serve`` on an
             ephemeral port in a scrubbed CPU subprocess, wait for
             /healthz readiness, fire predict requests, then SIGTERM
             and verify the graceful drain exits 0. Proves the whole
             serving contract (tpu_resnet/serve; docs/SERVING.md) on
             this machine before a real deployment bets on it.
  coldstart_probe  optional (--coldstart-probe): cold-vs-warm serve
             restart drill (tpu_resnet/programs) — train a small
             ResNet, serve it cold (every bucket program compiles),
             SIGTERM, restart warm against the same train_dir: the warm
             pass must perform ZERO XLA compiles (all bucket programs
             are persistent-cache hits) and reach ready >= 3x faster
             than cold; both time-to-ready points feed
             tools/perfwatch.py as a lower-is-better series
             (docs/PERF.md "Cold start")
  fleet_probe  optional (--fleet-probe): serving-fleet resilience drill
             (tpu_resnet/serve/router.py) — 2 serve replicas + the
             front router on ephemeral ports, 8 clients through the
             router, SIGKILL one replica mid-traffic (zero client
             failures, circuit opens within ~a probe interval), a
             checkpoint hot-reload on the survivor, a rolling admin
             drain (replica exits 0), router SIGTERM exit 0, and a
             trace-export check that router + replica lanes landed on
             one run_id-correlated timeline (docs/SERVING.md)
  fleetmon_probe  optional (--fleetmon-probe): fleet-observability drill
             (tpu_resnet/obs/fleet.py) — 2 replicas (one with an
             injected 150 ms inference fault) + router + fleetmon;
             traced traffic must finish with zero client failures, the
             bucket-wise fleet-merged p99 must exceed the healthy
             replica's own p99, the SLO burn-rate alert must fire, the
             exported request lanes must attribute the tail to the slow
             replica's inference segment, and fleet p99 + burn rate
             feed perfwatch as gated series (docs/OBSERVABILITY.md)
  trace_probe  optional (--trace-probe): a live observability drill —
             tiny CPU train with telemetry up, /metrics scraped MID-RUN
             until the live model_flops_per_sec gauge and the
             train_step_ms histogram carry data, graceful SIGTERM, then trace-export + Chrome-trace
             schema check with run_id correlation
             (docs/OBSERVABILITY.md)
  perfwatch  optional (--perfwatch): perf-regression verdict over the
             archived BENCH_*.json trajectory (tools/perfwatch.py) —
             fails only on a regress verdict outside the noise band
  sweep_probe  optional (--sweep-probe): ~30 s scrubbed-CPU drill of the
             per-knob sweep harness (tpu_resnet/tools/sweep.py): a
             2-point sweep end-to-end — child deadlines honored, the
             RESULT_JSON trajectory complete and parseable, and
             perfwatch able to cohort it — so the MFU-campaign rig
             can't silently rot between chip windows
  mem_probe  optional (--mem-probe): memory-observability drill
             (tpu_resnet/obs/memory.py) — a tiny train must publish the
             hbm_* gauge series live and write a memory.json ledger
             certifying the same program keys as flops.json; a second
             run with an injected RESOURCE_EXHAUSTED must die loudly
             AND leave a schema-valid oom_report.json with a live-array
             census (docs/OBSERVABILITY.md)
  partition_probe  optional (--partition-probe): ZeRO-1 state-partitioner
             drill (tpu_resnet/parallel/{partition,zero}.py) on the
             8-device fakepod — a replicated tiny train and its zero1
             twin must both complete (the zero1 run through an injected
             SIGTERM + exact-step resume), the zero1 ledger's
             optimizer-slot argument bytes must be < 0.3x the
             replicated twin's with the donation credit intact, and
             tools/perfwatch.py must ingest the probe's peak-HBM
             numbers as a lower-is-better series (docs/PARALLELISM.md)
  reshape_drill  optional (--reshape-drill): elastic-capacity drill
             (tpu_resnet/resilience/elastic.py) — a mesh8 train is
             preempted by an injected SIGTERM and resumed in a child
             with only FOUR devices under mesh.partition=zero1; the
             resumed loss stream must equal an uninterrupted mesh8
             reference within 1e-6 at every logged step, a
             topology_change span must land on the run timeline, and
             perfwatch must ingest the pre/post steps/s (post
             normalized by the device ratio) as a tracked series
  autoscale_probe  optional (--autoscale-probe): autopilot control-loop
             drill (tpu_resnet/autopilot) — the checked-in
             ``scenarios/autoscale_burst.json`` end to end: a burst
             against one slow replica must make the autopilot spawn a
             second through supervise + watch-discovery probation
             (within the advertised scale-up-latency budget), the calm
             phase must drain it back via the router's rolling
             contract with zero hard client failures, the freed
             capacity must land in ``capacity_lease.json`` for the
             colocated trainer, and perfwatch must ingest the
             scale-up-latency / SLO-violation-seconds /
             replica-seconds series (docs/AUTOPILOT.md)
  fault_drill  optional (--fault-drill): a live SIGTERM+resume drill
             against a temp train_dir — a tiny CPU run is preempted by an
             injected SIGTERM, must exit with the preemption code with a
             checkpoint at the stop step, and a second run must resume
             from exactly that step and finish. Proves the whole
             preemption contract (tpu_resnet/resilience) on this machine
             before a real job bets on it.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

_PROBE = ("import jax; d = jax.devices(); "
          "print('PROBE', jax.default_backend(), '|', d[0].platform, '|', "
          "d[0].device_kind, '|', len(d))")


def _check_versions() -> dict:
    import importlib

    out = {"python": sys.version.split()[0], "ok": True}
    for mod in ("jax", "jaxlib", "flax", "optax", "orbax.checkpoint"):
        try:
            m = importlib.import_module(mod)
            out[mod] = getattr(m, "__version__", "?")
        except Exception as e:  # pragma: no cover - env-specific
            out[mod] = f"import failed: {type(e).__name__}"
            out["ok"] = False  # broken core dep must fail the summary
    return out


def _check_backend(timeout: int) -> dict:
    """Probe the ambient backend in a subprocess so a PJRT plugin whose
    init blocks (another process holding the chip, a wedged runtime) is
    reported as a timeout instead of hanging the doctor."""
    try:
        proc = subprocess.run([sys.executable, "-c", _PROBE],
                              stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True,
                              timeout=timeout)
    except subprocess.TimeoutExpired:
        return {"ok": False,
                "error": f"backend init hung for {timeout}s — is another "
                         f"process holding the chip? Set "
                         f"JAX_PLATFORMS=cpu to work without it"}
    for line in reversed(proc.stdout.strip().splitlines()):
        if line.startswith("PROBE "):
            backend, platform, kind, n = (
                p.strip() for p in line[len("PROBE "):].split("|"))
            return {"ok": True, "backend": backend, "platform": platform,
                    "device_kind": kind, "devices": int(n)}
    return {"ok": False, "rc": proc.returncode,
            "tail": proc.stdout.strip().splitlines()[-3:]}


def _check_cpu_mesh(n_devices: int, timeout: int) -> dict:
    """Virtual CPU mesh + one jitted psum-style reduction in a clean
    subprocess (same env scrub as dryrun_multichip)."""
    from tpu_resnet.hostenv import run_scrubbed_subprocess

    # Test array sized 2*n_devices so any --mesh-devices value divides it
    # evenly (a fixed 16 failed healthy 3/5/6-device meshes).
    code = (
        "import jax, jax.numpy as jnp\n"
        "from jax.sharding import Mesh, NamedSharding, PartitionSpec as P\n"
        "import numpy as np\n"
        f"devs = jax.devices()[:{n_devices}]\n"
        "mesh = Mesh(np.asarray(devs).reshape(-1, 1), ('data', 'model'))\n"
        f"x = jax.device_put(jnp.arange({2 * n_devices}.0), "
        "NamedSharding(mesh, P('data')))\n"
        "s = jax.jit(lambda v: v.sum(), out_shardings=NamedSharding(mesh, P()))(x)\n"
        "print('MESH_OK', len(devs), float(s))\n")
    rc, stdout = run_scrubbed_subprocess([sys.executable, "-c", code],
                                         n_devices=n_devices,
                                         timeout=timeout)
    if rc == 124:
        return {"ok": False, "error": f"CPU mesh smoke hung for {timeout}s"}
    ok = False
    expect = float(n_devices * (2 * n_devices - 1))  # sum(0..2n-1)
    for line in stdout.splitlines():       # stderr is merged in; scan for
        if line.startswith("MESH_OK"):     # the marker line specifically
            ok = abs(float(line.split()[-1]) - expect) < 1e-6
            break
    out = {"ok": ok, "devices": n_devices}
    if not ok:
        out["tail"] = stdout.strip().splitlines()[-3:]
    return out


def _check_native() -> dict:
    try:
        from tpu_resnet.native import available, jpeg_available
        return {"ok": bool(available()), "built": bool(available()),
                "jpeg": bool(jpeg_available())}
    except Exception as e:
        return {"ok": False, "error": f"{type(e).__name__}: {e}"}


def _check_dataset(dataset: str, data_dir: str) -> dict:
    from tpu_resnet.tools.datasets import validate_layout

    try:
        validate_layout(dataset, data_dir)
        return {"ok": True, "dataset": dataset, "data_dir": data_dir}
    except Exception as e:
        return {"ok": False, "dataset": dataset,
                "error": f"{type(e).__name__}: {e}"}


def _check_telemetry(train_dir: str, timeout: float = 5.0) -> dict:
    """Scrape the run's obs server (tpu_resnet/obs/server.py). Healthy
    means: telemetry.json names a port, /metrics parses as Prometheus text
    with the core ``tpu_resnet_step`` series, and /healthz reports a
    heartbeat younger than the staleness threshold."""
    from tpu_resnet.obs.server import read_telemetry_port, scrape

    port = read_telemetry_port(train_dir)
    if port is None:
        return {"ok": False,
                "error": f"no telemetry.json under {train_dir} — is the "
                         "trainer running with train.telemetry_port >= 0?"}
    try:
        report = scrape(f"http://127.0.0.1:{port}", timeout=timeout)
    except (OSError, ValueError) as e:
        return {"ok": False, "port": port,
                "error": f"{type(e).__name__}: {e}"}
    health, metrics = report["health"], report["metrics"]
    return {"ok": bool(health.get("ok")) and "tpu_resnet_step" in metrics,
            "port": port, "step": health.get("step"),
            "heartbeat_age_sec": health.get("heartbeat_age_sec"),
            "series": len(metrics)}


def _check_data_bench(seconds: float = 4.0) -> dict:
    """Host decode-throughput scaling probe (tpu_resnet/data/engine.py).
    Healthy means the engine moved images at every probed worker count;
    the numbers are the diagnosis: ``data_wait`` high in a run +
    ``implied_max_steps_per_sec_b128`` below the chip's step rate =
    host-bound — raise ``data.num_decode_procs`` (or the host count)."""
    from tpu_resnet.data.engine import decode_scaling_probe

    try:
        probe = decode_scaling_probe(proc_counts=(1, 0), seconds=seconds)
    except Exception as e:
        return {"ok": False, "error": f"{type(e).__name__}: {e}"}
    rates = probe.get("engine_images_per_sec_by_procs", {})
    ok = bool(rates) and all(v > 0 for v in rates.values())
    return {"ok": ok, **probe}


def _check_static_analysis(matrix: bool = True, timeout: int = 900) -> dict:
    """Static-analysis suite (tpu_resnet/analysis) as one doctor line.

    Runs ``python -m tpu_resnet check`` in a FRESH scrubbed-CPU
    subprocess (same env discipline as the cpu_mesh and fault-drill
    checks): the verifier's goldens are defined over the CPU abstract
    trace with 8 virtual devices. In the doctor's own process jax is
    already initialized on the ambient backend by the versions check,
    and an ambient ``JAX_PLATFORMS=tpu``/plugin hook would also defeat
    the check CLI's setdefault-based pin — the golden-hash and lowering
    checks would silently be skipped (reporting ok while verifying much
    less), or the child could hang on a wedged plugin. ``matrix=False``
    is the fast lint-only form (used by tests; the full matrix re-traces
    every supported config, ~1-2 min on CPU)."""
    import tempfile

    from tpu_resnet.hostenv import scrubbed_cpu_env

    cmd = [sys.executable, "-m", "tpu_resnet", "check"]
    if not matrix:
        cmd.append("--skip-matrix")
    with tempfile.TemporaryDirectory(prefix="tpu_resnet_check_") as d:
        out_json = os.path.join(d, "findings.json")
        try:
            proc = subprocess.run(cmd + ["--json", out_json],
                                  env=scrubbed_cpu_env(8),
                                  stdout=subprocess.PIPE,
                                  stderr=subprocess.STDOUT, text=True,
                                  timeout=timeout)
        except subprocess.TimeoutExpired:
            return {"ok": False, "error": f"check hung for {timeout}s"}
        out = {"ok": proc.returncode == 0, "rc": proc.returncode}
        try:
            with open(out_json) as fh:
                payload = json.load(fh)
            errors = [f for f in payload["findings"]
                      if f["severity"] == "error"]
            out.update(errors=len(errors),
                       warnings=len(payload["findings"]) - len(errors),
                       baselined=len(payload["suppressed"]),
                       stale_baseline=len(payload["stale_baseline"]),
                       engines=payload.get("engines", []))
            if matrix:
                out["matrix_traced"] = payload.get("matrix",
                                                   {}).get("traced")
                out["matrix_must_raise"] = payload.get(
                    "matrix", {}).get("must_raise")
                # Engine 5 (analysis/collectives.py) rides the matrix:
                # surface its compile/compare counts so DOCTOR_JSON
                # says the collective structure was actually verified.
                comms = payload.get("matrix", {}).get("collectives")
                if comms:
                    out["collectives_compiled"] = comms.get("compiled")
                    out["collectives_compared"] = comms.get("compared")
            if errors:
                e = errors[0]
                out["first"] = (f"{e['path']}:{e['line']}: "
                                f"{e['message']} [{e['rule']}]")
        except (OSError, ValueError, KeyError):
            out["ok"] = False
            out["tail"] = proc.stdout.strip().splitlines()[-5:]
        return out


def _run_scenario(name: str):
    """Conduct a checked-in ``scenarios/<name>.json`` file and index its
    step/assertion entries by label — the raw material every
    scenario-backed probe below rebuilds its historical DOCTOR_JSON
    dict from. The conductor owns the skeleton (scrubbed children,
    fault env, log files, reaper, survivor kill); the probe adapters
    own only the legacy output shape."""
    from tpu_resnet.scenario.catalog import scenario_path
    from tpu_resnet.scenario.conductor import conduct_file

    result = conduct_file(scenario_path(name))
    return result, {s["label"]: s for s in result.get("steps", [])}


def _scenario_fail(result: dict) -> dict:
    """Failed scenario → the historical probe failure dict: phase,
    error (when the step carried one), every observation (run spans as
    the legacy tuples), the child's log tail."""
    failed = (result.get("steps") or [{}])[-1]
    out = {"ok": False, "phase": result.get("phase")}
    if failed.get("error") or result.get("error"):
        out["error"] = failed.get("error") or result.get("error")
    for key, value in (failed.get("observed") or {}).items():
        if key == "run_spans":
            value = [tuple(s) for s in value]
        out[key] = value
    if failed.get("tail") is not None:
        out["tail"] = failed["tail"]
    return out


def _scenario_perfwatch(result: dict, out: dict) -> bool:
    """Fold the conductor's perfwatch verdict into a legacy probe dict.
    Returns True when the caller should return ``out`` as-is (hung or
    failed ingestion — the historical early-return paths); the legacy
    key spellings (``perfwatch="hung"``, ``perfwatch_ingested``,
    ``perfwatch_tail``) are preserved."""
    pw = result.get("perfwatch") or {}
    if pw.get("hung"):
        out.update(ok=False, perfwatch="hung")
        return True
    if not pw.get("ran"):
        out["perfwatch_ingested"] = (
            "skipped (no tools/perfwatch.py)"
            if pw.get("reason") == "no tools/perfwatch.py"
            else "skipped (no throughput samples)")
        return False
    ingested = all((pw.get("ingested") or {}).values())
    out["perfwatch_ingested"] = ingested
    if pw.get("rc") != 0 or not ingested:
        out.update(ok=False, phase="perfwatch",
                   perfwatch_tail=pw.get("tail", []))
        return True
    return False


def _check_serve_probe(timeout: int = 300) -> dict:
    """Live predict-server drill (tpu_resnet/serve) in scrubbed CPU
    subprocesses: train a tiny MLP, start ``tpu_resnet serve`` on an
    ephemeral port, wait for /healthz readiness (model loaded + every
    bucket compiled), fire a handful of predict requests, scrape
    /metrics, then SIGTERM and verify the graceful-drain exit-code
    contract (0 — the supervisor-facing analog of the trainer's 42).

    Thin alias over ``scenarios/serve_probe.json`` — the scenario
    conductor runs the drill; this adapter rebuilds the historical
    DOCTOR_JSON dict from its observations."""
    result, steps = _run_scenario("serve_probe")
    if not result["ok"]:
        return _scenario_fail(result)
    return {"ok": True,
            "requests_ok": steps["predict"]["observed"]["ok_requests"],
            "served_total": int(
                steps["served"]["observed"]["served_total"]),
            "drain_rc": result["rcs"]["serve"]}


def _check_coldstart_probe(timeout: int = 600) -> dict:
    """Cold-vs-warm serve restart drill (tpu_resnet/programs) in
    scrubbed CPU subprocesses — the executable-cache acceptance
    contract on this box:

    1. train a small ResNet (rn50-depth CIFAR head on synthetic data —
       deep enough that XLA compile, not restore, dominates cold
       start) and serve it COLD: the per-train_dir program cache is
       empty, every bucket program compiles
       (``compile_cache_misses == buckets``), time-to-ready recorded;
    2. SIGTERM (the PR 11 rolling-upgrade window), then restart WARM
       against the same train_dir: the warm pass must perform ZERO XLA
       compiles — ``compile_cache_hits == buckets`` and
       ``compile_cache_misses == 0`` — and reach ready >= 3x faster
       than the cold start (the registry's hard perf deliverable);
    3. both time-to-ready points feed ``tools/perfwatch.py --sweep`` as
       a lower-is-better series (``sweep-ttr:``), so cache regressions
       across probe runs are TRACKED, not folklore."""
    import signal
    import tempfile
    import time
    import urllib.request

    from tpu_resnet.hostenv import run_scrubbed_subprocess, scrubbed_cpu_env
    from tpu_resnet.obs.server import parse_prometheus

    with tempfile.TemporaryDirectory(prefix="tpu_resnet_coldstart_") as d:
        train_cmd = [sys.executable, "-m", "tpu_resnet", "train",
                     "--preset", "smoke", f"train.train_dir={d}",
                     "model.resnet_size=50", "train.train_steps=2",
                     "train.checkpoint_every=2", "train.log_every=2",
                     "train.summary_every=2",
                     "train.image_summary_every=0",
                     "train.steps_per_call=2",
                     "train.global_batch_size=4",
                     "data.device_resident=off", "data.transfer_stage=1"]
        rc, out = run_scrubbed_subprocess(train_cmd, n_devices=1,
                                          timeout=timeout)
        if rc != 0:
            return {"ok": False, "phase": "train", "rc": rc,
                    "tail": out.strip().splitlines()[-5:]}

        serve_cmd = [sys.executable, "-m", "tpu_resnet", "serve",
                     "--preset", "smoke", f"train.train_dir={d}",
                     "model.resnet_size=50", "data.device_resident=off",
                     "serve.port=0", "serve.max_batch=16",
                     "serve.max_wait_ms=5"]

        def one_pass(tag):
            """(metrics dict | None, drain_rc, tail) for one serve
            start→ready→SIGTERM cycle."""
            try:
                os.remove(os.path.join(d, "serve.json"))
            except OSError:
                pass
            log_path = os.path.join(d, f"serve_{tag}.log")
            log_fh = open(log_path, "w")

            def tail():
                log_fh.flush()
                try:
                    with open(log_path) as f:
                        return f.read().strip().splitlines()[-5:]
                except OSError:
                    return []

            proc = subprocess.Popen(serve_cmd, env=scrubbed_cpu_env(1),
                                    stdout=log_fh,
                                    stderr=subprocess.STDOUT, text=True)
            try:
                from tpu_resnet.serve.server import read_serve_port

                base, ready = None, False
                deadline = time.time() + timeout
                while time.time() < deadline and proc.poll() is None:
                    if base is None:
                        port = read_serve_port(d)
                        if port is not None:
                            base = f"http://127.0.0.1:{port}"
                    if base is not None:
                        try:
                            with urllib.request.urlopen(
                                    base + "/healthz", timeout=2) as r:
                                if json.loads(r.read()).get("ok"):
                                    ready = True
                                    break
                        except (OSError, ValueError):
                            pass  # 503 (warming) / not listening yet
                    time.sleep(0.2)
                if not ready:
                    proc.kill()
                    proc.wait(timeout=10)
                    return None, proc.returncode, tail()
                try:
                    with urllib.request.urlopen(base + "/metrics",
                                                timeout=10) as r:
                        metrics = parse_prometheus(r.read().decode())
                    with urllib.request.urlopen(base + "/info",
                                                timeout=10) as r:
                        info = json.loads(r.read())
                except (OSError, ValueError):
                    metrics, info = None, {}
                proc.send_signal(signal.SIGTERM)
                try:
                    rc2 = proc.wait(timeout=60)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    return None, -1, ["server did not exit within 60s "
                                      "of SIGTERM"]
                if metrics is None:
                    return None, rc2, tail()
                pfx = "tpu_resnet_"
                return ({"hits": int(metrics.get(
                             pfx + "compile_cache_hits", -1)),
                         "misses": int(metrics.get(
                             pfx + "compile_cache_misses", -1)),
                         "time_to_ready_s": float(metrics.get(
                             pfx + "serve_time_to_ready_seconds", 0)),
                         "buckets": len(info.get("buckets", []))},
                        rc2, [])
            finally:
                if proc.poll() is None:
                    proc.kill()
                log_fh.close()

        cold, rc_cold, tail_cold = one_pass("cold")
        if cold is None or rc_cold != 0:
            return {"ok": False, "phase": "cold_serve", "rc": rc_cold,
                    "tail": tail_cold}
        warm, rc_warm, tail_warm = one_pass("warm")
        if warm is None or rc_warm != 0:
            return {"ok": False, "phase": "warm_serve", "rc": rc_warm,
                    "tail": tail_warm}

        result = {"cold": cold, "warm": warm,
                  "cold_drain_rc": rc_cold, "warm_drain_rc": rc_warm}
        n = warm["buckets"]
        if n < 1 or warm["hits"] != n or warm["misses"] != 0:
            result.update(ok=False, phase="warm_zero_compiles",
                          error=f"warm restart must be all cache hits: "
                                f"expected hits=={n} misses==0, got "
                                f"hits={warm['hits']} "
                                f"misses={warm['misses']}")
            return result
        if cold["misses"] != n or cold["hits"] != 0:
            result.update(ok=False, phase="cold_all_compiles",
                          error=f"cold start should compile every "
                                f"bucket (hits=0, misses={n}), got "
                                f"{cold} — was the cache dir not "
                                f"fresh?")
            return result
        ratio = (cold["time_to_ready_s"] / warm["time_to_ready_s"]
                 if warm["time_to_ready_s"] else 0.0)
        result["ttr_ratio"] = round(ratio, 2)
        if ratio < 3.0:
            result.update(ok=False, phase="time_to_ready",
                          error=f"warm restart must reach ready >= 3x "
                                f"faster than cold, got {ratio:.2f}x "
                                f"(cold {cold['time_to_ready_s']:.2f}s "
                                f"vs warm "
                                f"{warm['time_to_ready_s']:.2f}s)")
            return result

        # perfwatch ingestion: cold/warm time-to-ready as a sweep-style
        # trajectory judged lower-is-better (sweep-ttr:) — a cache
        # regression across probe runs becomes a tracked regress.
        # Skipped on an installed wheel without tools/.
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        script = os.path.join(root, "tools", "perfwatch.py")
        if os.path.exists(script):
            traj = {"metric": "coldstart_ttr", "backend": "cpu",
                    "points": [
                        {"id": f"coldstart={name}", "status": "ok",
                         "backend": "cpu", "steps_per_sec": 1.0,
                         "time_to_ready_s": m["time_to_ready_s"]}
                        for name, m in (("cold", cold), ("warm", warm))]}
            traj_path = os.path.join(d, "coldstart_probe_sweep.json")
            with open(traj_path, "w") as f:
                json.dump(traj, f)
            try:
                pw = subprocess.run(
                    [sys.executable, script, "--sweep", traj_path],
                    stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                    text=True, timeout=60)
            except subprocess.TimeoutExpired:
                result.update(ok=False, perfwatch="hung")
                return result
            ingested = all(f"sweep-ttr:coldstart={n}" in pw.stdout
                           for n in ("cold", "warm"))
            result["perfwatch_ingested"] = ingested
            if pw.returncode != 0 or not ingested:
                result.update(ok=False, phase="perfwatch",
                              perfwatch_tail=pw.stdout.strip()
                              .splitlines()[-5:])
                return result
        else:
            result["perfwatch_ingested"] = "skipped (no tools/perfwatch.py)"
        result["ok"] = True
        return result


def _check_fleet_probe(timeout: int = 420) -> dict:
    """Serving-fleet resilience drill (tpu_resnet/serve/router.py) in
    scrubbed-CPU subprocesses — the replica-kill chaos + rolling-drain
    acceptance contract on this box:

    1. train a tiny MLP, start TWO serve replicas (serve.replica_name=
       r0/r1, ephemeral ports, shared train_dir) and the front router
       (route.discover_dir) — wait until the router reports both
       replicas healthy;
    2. run 8 closed-loop clients against the ROUTER and SIGKILL r0
       mid-traffic: every client request must still answer 200 (the
       in-flight failover retry covers the kill window), and the
       router's circuit must exclude r0 within ~one probe interval
       (route_replicas_healthy drops to 1);
    3. land a newer checkpoint so the survivor hot-reloads (the
       serve_reload span the rolling-ops timeline needs), then drain r1
       THROUGH the router's admin endpoint — the replica must exit 0
       (the PR 2/5 drain contract) with zero failed requests;
    4. SIGTERM the router (exit 0), then trace-export the train_dir:
       the merged timeline must carry router + replica lanes
       (route_drain, serve_reload, serve_drain, replica_down spans),
       all correlated by the run's run_id."""
    import signal
    import tempfile
    import threading
    import time
    import urllib.error
    import urllib.request

    from tpu_resnet.hostenv import run_scrubbed_subprocess, scrubbed_cpu_env
    from tpu_resnet.obs.server import parse_prometheus
    from tpu_resnet.obs.trace import export_trace
    from tpu_resnet.serve.router import discover_replicas, read_route_port

    ns = "tpu_resnet_"
    with tempfile.TemporaryDirectory(prefix="tpu_resnet_fleet_") as d:
        # Flags first, positional overrides contiguous after (argparse
        # rejects interleaved positionals around optionals).
        model_over = [f"train.train_dir={d}", "model.name=mlp",
                      "data.device_resident=off", "data.transfer_stage=1"]
        train_cmd = [sys.executable, "-m", "tpu_resnet", "train",
                     "--preset", "smoke",
                     "train.train_steps=6", "train.checkpoint_every=3",
                     "train.log_every=3", "train.summary_every=6",
                     "train.image_summary_every=0",
                     "train.steps_per_call=3"] + model_over
        rc, out = run_scrubbed_subprocess(train_cmd, n_devices=1,
                                          timeout=timeout)
        if rc != 0:
            return {"ok": False, "phase": "train", "rc": rc,
                    "tail": out.strip().splitlines()[-5:]}

        procs, logs = {}, {}

        def spawn(name, cmd):
            log_path = os.path.join(d, f"{name}_child.log")
            fh = open(log_path, "w")
            logs[name] = (log_path, fh)
            procs[name] = subprocess.Popen(
                cmd, env=scrubbed_cpu_env(1), stdout=fh,
                stderr=subprocess.STDOUT, text=True)
            return procs[name]

        def tail(name):
            path, fh = logs[name]
            fh.flush()
            try:
                with open(path) as f:
                    return f.read().strip().splitlines()[-5:]
            except OSError:
                return []

        def fail(phase, **extra):
            extra.setdefault("tails", {n: tail(n) for n in procs})
            for p in procs.values():
                if p.poll() is None:
                    p.kill()
            return {"ok": False, "phase": phase, **extra}

        def get_json(url, t=2):
            with urllib.request.urlopen(url, timeout=t) as r:
                return json.loads(r.read())

        try:
            for name in ("r0", "r1"):
                spawn(name, [sys.executable, "-m", "tpu_resnet", "serve",
                             "--preset", "smoke",
                             f"serve.replica_name={name}", "serve.port=0",
                             "serve.max_batch=4", "serve.max_wait_ms=5",
                             "serve.reload_interval_secs=0.5"]
                      + model_over)
            spawn("router", [sys.executable, "-m", "tpu_resnet", "route",
                             "--preset", "smoke",
                             f"route.discover_dir={d}", "route.port=0",
                             "route.probe_interval_secs=0.3",
                             "route.probe_timeout_secs=2",
                             "route.fail_threshold=1",
                             "route.open_secs=2"] + model_over)
            base, healthy = None, 0
            deadline = time.time() + timeout / 2
            while time.time() < deadline:
                if any(p.poll() is not None for p in procs.values()):
                    return fail("startup", rcs={n: p.poll()
                                                for n, p in procs.items()})
                if base is None:
                    port = read_route_port(d)
                    if port is not None:
                        base = f"http://127.0.0.1:{port}"
                if base is not None:
                    try:
                        h = get_json(base + "/healthz")
                        healthy = int(h.get("replicas_healthy", 0))
                        if h.get("ok") and healthy >= 2:
                            break
                    except (OSError, ValueError):
                        pass
                time.sleep(0.3)
            if healthy < 2:
                return fail("readiness", replicas_healthy=healthy)

            # -------- the headline drill: 8-client loadgen through the
            # router, loadgen SIGKILLs r0 at half-duration (--scenario
            # replica_kill). A watcher thread times the circuit: r0's
            # own /healthz going connection-refused marks the death, the
            # router's route_replicas_healthy dropping to 1 marks the
            # exclusion.
            r0_url = next(r["url"] for r in discover_replicas(d)
                          if r["name"] == "r0")
            watch = {"dead_at": None, "excluded_at": None}

            def watcher():
                stop_at = time.monotonic() + 60
                while time.monotonic() < stop_at:
                    if watch["dead_at"] is None:
                        try:
                            with urllib.request.urlopen(
                                    r0_url + "/healthz", timeout=1) as r:
                                r.read()
                        except urllib.error.HTTPError as e:
                            e.read()
                        except OSError:
                            watch["dead_at"] = time.monotonic()
                    else:
                        try:
                            with urllib.request.urlopen(
                                    base + "/metrics", timeout=2) as r:
                                m = parse_prometheus(r.read().decode())
                            if m.get(ns + "route_replicas_healthy") == 1.0:
                                watch["excluded_at"] = time.monotonic()
                                return
                        except (OSError, ValueError):
                            pass
                    time.sleep(0.1)

            w = threading.Thread(target=watcher, daemon=True)
            w.start()
            root = os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))))
            out_json = os.path.join(d, "loadgen_replica_kill.json")
            lg = subprocess.run(
                [sys.executable,
                 os.path.join(root, "tools", "loadgen.py"),
                 "--url", base, "--clients", "8", "--duration", "8",
                 "--scenario", "replica_kill", "--fleet-dir", d,
                 "--deadline-ms", "30000", "--out", out_json],
                env=scrubbed_cpu_env(1), stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True, timeout=timeout)
            w.join(timeout=70)
            try:
                with open(out_json) as f:
                    lg_result = json.load(f)
            except (OSError, ValueError):
                return fail("chaos_traffic", rc=lg.returncode,
                            lg_tail=lg.stdout.strip().splitlines()[-5:])
            hard = (lg_result["failed"] + lg_result["timeouts"]
                    + lg_result["connect_failures"])
            if lg.returncode != 0 or hard or not lg_result["requests_ok"]:
                return fail("chaos_traffic", rc=lg.returncode,
                            result={k: lg_result.get(k) for k in
                                    ("requests_ok", "failed", "timeouts",
                                     "connect_failures", "chaos")})
            if not (lg_result.get("chaos") or {}).get("killed"):
                return fail("chaos_traffic",
                            error="loadgen never delivered the SIGKILL",
                            chaos=lg_result.get("chaos"))
            if watch["excluded_at"] is None:
                return fail("circuit", error="router never excluded the "
                                             "killed replica",
                            watch=watch)
            excluded_in = round(watch["excluded_at"]
                                - watch["dead_at"], 2)
            # perfwatch gates the scenario RESULT_JSON (sweep-shaped
            # points): one sample -> insufficient_data, never regress.
            pw = subprocess.run(
                [sys.executable,
                 os.path.join(root, "tools", "perfwatch.py"),
                 "--sweep", out_json],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, timeout=60)
            if pw.returncode != 0 or \
                    "sweep:scenario=replica_kill" not in pw.stdout:
                return fail("perfwatch", rc=pw.returncode,
                            pw_tail=pw.stdout.strip().splitlines()[-5:])
            metrics = {}
            try:
                with urllib.request.urlopen(base + "/metrics",
                                            timeout=5) as r:
                    metrics = parse_prometheus(r.read().decode())
            except (OSError, ValueError):
                pass

            # -------- hot-reload on the survivor, then rolling drain
            rc, out = run_scrubbed_subprocess(
                [sys.executable, "-m", "tpu_resnet", "train",
                 "--preset", "smoke",
                 "train.train_steps=12", "train.checkpoint_every=3",
                 "train.log_every=3", "train.summary_every=12",
                 "train.image_summary_every=0", "train.steps_per_call=3"]
                + model_over, n_devices=1, timeout=timeout)
            if rc != 0:
                return fail("reload_train", rc=rc,
                            tail_train=out.strip().splitlines()[-5:])
            reload_deadline = time.time() + 30
            reloaded = False
            while time.time() < reload_deadline:
                try:
                    if get_json(base + "/info").get("model_step") == 12:
                        reloaded = True
                        break
                except (OSError, ValueError):
                    pass
                time.sleep(0.5)
            if not reloaded:
                return fail("hot_reload",
                            error="survivor never served step 12")
            req = urllib.request.Request(
                base + "/admin/drain?replica=r1", data=b"{}",
                method="POST")
            try:
                with urllib.request.urlopen(req, timeout=120) as r:
                    drain = json.loads(r.read())
            except urllib.error.HTTPError as e:
                # 409: the drain itself failed — surface its report.
                drain = json.loads(e.read())
            try:
                r1_rc = procs["r1"].wait(timeout=60)
            except subprocess.TimeoutExpired:
                return fail("drain", error="r1 still running after the "
                                           "router drain", drain=drain)
            if not drain.get("ok") or r1_rc != 0:
                return fail("drain", drain=drain, r1_rc=r1_rc)

            # -------- router exit-code contract + merged timeline
            procs["router"].send_signal(signal.SIGTERM)
            try:
                router_rc = procs["router"].wait(timeout=30)
            except subprocess.TimeoutExpired:
                return fail("router_exit",
                            error="router ignored SIGTERM for 30s")
            if router_rc != 0:
                return fail("router_exit", rc=router_rc)
            try:
                _, trace = export_trace(d)
            except (OSError, ValueError) as e:
                return fail("trace", error=f"{type(e).__name__}: {e}")
            names = {e["name"] for e in trace["traceEvents"]}
            need = {"route_drain", "serve_reload", "serve_drain",
                    "replica_down"}
            if not need <= names:
                return fail("trace", missing=sorted(need - names))
            run_ids = trace["metadata"]["source_run_ids"]
            correlated = (len(run_ids.get("serve", [])) == 1
                          and run_ids.get("route") == run_ids["serve"])
            result = {"ok": bool(correlated),
                      "requests_ok": lg_result["requests_ok"],
                      "client_failures": 0,
                      "killed": lg_result["chaos"]["killed"],
                      "excluded_in_sec": excluded_in,
                      "p99_ms": lg_result["latency_ms"]["p99"],
                      "retries": int(metrics.get(
                          ns + "route_retries_total", 0)),
                      "perfwatch_ingested": True,
                      "survivor_model_step": 12,
                      "drain": {k: drain.get(k) for k in
                                ("ok", "replica", "replica_gone")},
                      "r1_rc": r1_rc, "router_rc": router_rc,
                      "trace_run_ids": run_ids}
            if not correlated:
                result["phase"] = "trace_run_ids"
            return result
        finally:
            # r0 was SIGKILLed mid-drill; its zombie must be reaped and
            # every straggler killed even on the failure paths.
            for name, p in procs.items():
                if p.poll() is None:
                    p.kill()
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    pass
            for _, fh in logs.values():
                fh.close()


def _check_fleetmon_probe(timeout: int = 420) -> dict:
    """Fleet-observability drill (tpu_resnet/obs/fleet.py) in
    scrubbed-CPU subprocesses — end-to-end proof that a request-level
    slowdown on ONE replica is attributable from the outside:

    1. train a tiny MLP, start replica r0 with an injected 150 ms
       inference fault (TPU_RESNET_FAULT_SERVE_SLOW_MS), a clean r1,
       the front router, and ``fleetmon`` with a 50 ms SLO — wait for
       router readiness and fleetmon's first scrape round;
    2. drive traced traffic through the router (loadgen stamps
       X-Trace-Id): every request must answer 200 — the slow replica
       makes the fleet SLOW, never broken — and RESULT_JSON must name
       the slowest trace ids;
    3. the fleet-merged p99 (bucket-wise histogram merge across
       replicas) must exceed the healthy replica's OWN p99 — the
       average-of-percentiles lie this plane exists to kill — and the
       SLO burn-rate alert must fire (fleet_alerts_total >= 1, a
       fleet_burn_alert span on the timeline);
    4. trace-export: request lanes rendered, the slowest traced
       requests attribute to r0, and a slow serve_request span's
       inference segment dominates its wall time;
    5. fleet p99 + fast burn rate feed ``perfwatch --sweep`` as
       lower-is-better series; fleetmon and the router exit 0 on
       SIGTERM."""
    import signal
    import tempfile
    import time
    import urllib.error
    import urllib.request

    from tpu_resnet.hostenv import run_scrubbed_subprocess, scrubbed_cpu_env
    from tpu_resnet.obs.fleet import read_fleet_port
    from tpu_resnet.obs.server import (histogram_quantile, parse_histograms,
                                       parse_prometheus)
    from tpu_resnet.obs.trace import export_trace
    from tpu_resnet.serve.router import discover_replicas, read_route_port

    ns = "tpu_resnet_"
    with tempfile.TemporaryDirectory(prefix="tpu_resnet_fleetmon_") as d:
        model_over = [f"train.train_dir={d}", "model.name=mlp",
                      "data.device_resident=off", "data.transfer_stage=1"]
        rc, out = run_scrubbed_subprocess(
            [sys.executable, "-m", "tpu_resnet", "train",
             "--preset", "smoke",
             "train.train_steps=6", "train.checkpoint_every=3",
             "train.log_every=3", "train.summary_every=6",
             "train.image_summary_every=0",
             "train.steps_per_call=3"] + model_over,
            n_devices=1, timeout=timeout)
        if rc != 0:
            return {"ok": False, "phase": "train", "rc": rc,
                    "tail": out.strip().splitlines()[-5:]}

        procs, logs = {}, {}

        def spawn(name, cmd, env_extra=None):
            log_path = os.path.join(d, f"{name}_child.log")
            fh = open(log_path, "w")
            logs[name] = (log_path, fh)
            env = scrubbed_cpu_env(1)
            env.update(env_extra or {})
            procs[name] = subprocess.Popen(
                cmd, env=env, stdout=fh, stderr=subprocess.STDOUT,
                text=True)
            return procs[name]

        def tail(name):
            path, fh = logs[name]
            fh.flush()
            try:
                with open(path) as f:
                    return f.read().strip().splitlines()[-5:]
            except OSError:
                return []

        def fail(phase, **extra):
            extra.setdefault("tails", {n: tail(n) for n in procs})
            for p in procs.values():
                if p.poll() is None:
                    p.kill()
            return {"ok": False, "phase": phase, **extra}

        def get_json(url, t=2):
            with urllib.request.urlopen(url, timeout=t) as r:
                return json.loads(r.read())

        def get_metrics(url, t=5):
            with urllib.request.urlopen(url + "/metrics", timeout=t) as r:
                text = r.read().decode()
            return parse_prometheus(text), parse_histograms(text)

        try:
            # r0 carries the injected 150ms-per-batch inference fault —
            # the "one bad machine" the whole plane must attribute.
            for name, env_extra in (
                    ("r0", {"TPU_RESNET_FAULT_SERVE_SLOW_MS": "150"}),
                    ("r1", None)):
                spawn(name, [sys.executable, "-m", "tpu_resnet", "serve",
                             "--preset", "smoke",
                             f"serve.replica_name={name}", "serve.port=0",
                             "serve.max_batch=4", "serve.max_wait_ms=5",
                             "serve.reload_interval_secs=0.5"]
                      + model_over, env_extra=env_extra)
            spawn("router", [sys.executable, "-m", "tpu_resnet", "route",
                             "--preset", "smoke",
                             f"route.discover_dir={d}", "route.port=0",
                             "route.probe_interval_secs=0.3",
                             "route.probe_timeout_secs=2",
                             "route.fail_threshold=2",
                             "route.open_secs=2"] + model_over)
            spawn("fleetmon",
                  [sys.executable, "-m", "tpu_resnet", "fleetmon",
                   "--preset", "smoke", f"fleet.discover_dir={d}",
                   "fleet.port=0", "fleet.scrape_interval_secs=0.5",
                   "fleet.slo_ms=50"] + model_over)
            base = fm_base = None
            healthy = 0
            fm_ok = False
            deadline = time.time() + timeout / 2
            while time.time() < deadline:
                if any(p.poll() is not None for p in procs.values()):
                    return fail("startup", rcs={n: p.poll()
                                                for n, p in procs.items()})
                if base is None:
                    port = read_route_port(d)
                    if port is not None:
                        base = f"http://127.0.0.1:{port}"
                if fm_base is None:
                    port = read_fleet_port(d)
                    if port is not None:
                        fm_base = f"http://127.0.0.1:{port}"
                try:
                    if base is not None and healthy < 2:
                        h = get_json(base + "/healthz")
                        healthy = int(h.get("replicas_healthy", 0))
                    if fm_base is not None and not fm_ok:
                        fm_ok = bool(get_json(fm_base
                                              + "/healthz").get("ok"))
                except (OSError, ValueError):
                    pass
                if healthy >= 2 and fm_ok:
                    break
                time.sleep(0.3)
            if healthy < 2 or not fm_ok:
                return fail("readiness", replicas_healthy=healthy,
                            fleetmon_ok=fm_ok)

            # -------- traced traffic through the router. The slow
            # replica must make the fleet SLOW, never broken: 0 hard
            # failures is the headline gate.
            root = os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))))
            out_json = os.path.join(d, "loadgen_fleetmon.json")
            lg = subprocess.run(
                [sys.executable,
                 os.path.join(root, "tools", "loadgen.py"),
                 "--url", base, "--clients", "6", "--duration", "10",
                 "--deadline-ms", "30000", "--out", out_json],
                env=scrubbed_cpu_env(1), stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True, timeout=timeout)
            try:
                with open(out_json) as f:
                    lg_result = json.load(f)
            except (OSError, ValueError):
                return fail("traffic", rc=lg.returncode,
                            lg_tail=lg.stdout.strip().splitlines()[-5:])
            hard = (lg_result["failed"] + lg_result["timeouts"]
                    + lg_result["connect_failures"])
            if lg.returncode != 0 or hard or not lg_result["requests_ok"]:
                return fail("traffic", rc=lg.returncode,
                            result={k: lg_result.get(k) for k in
                                    ("requests_ok", "failed", "timeouts",
                                     "connect_failures")})
            slowest = lg_result.get("slowest_traces") or []
            if not slowest or not all(
                    s.get("trace_id", "").startswith("lg")
                    for s in slowest):
                return fail("traffic", error="RESULT_JSON carries no "
                            "client-minted slowest trace ids",
                            slowest=slowest)

            # -------- fleet percentiles + burn alert: poll fleetmon
            # through a few scrape rounds.
            fm = {}
            alert_deadline = time.time() + 30
            while time.time() < alert_deadline:
                try:
                    fm, _ = get_metrics(fm_base)
                except (OSError, ValueError):
                    fm = {}
                if fm.get(ns + "fleet_alerts_total", 0) >= 1 and \
                        fm.get(ns + "fleet_requests_total", 0) > 0:
                    break
                time.sleep(0.5)
            r1_url = next(r["url"] for r in discover_replicas(d)
                          if r["name"] == "r1")
            _, r1_hists = get_metrics(r1_url)
            r1_p99 = histogram_quantile(
                r1_hists.get(ns + "serve_latency_ms", {}), 0.99)
            fleet_p99 = fm.get(ns + "fleet_serve_p99_ms", 0.0)
            burn_fast = fm.get(ns + "fleet_burn_rate_fast", 0.0)
            if fm.get(ns + "fleet_alerts_total", 0) < 1:
                return fail("burn_alert", metrics={
                    k: v for k, v in sorted(fm.items())
                    if k.startswith(ns + "fleet_")})
            if not fleet_p99 > r1_p99 > 0:
                # The merged percentile MUST see r0's slow mode that the
                # healthy replica's own histogram cannot contain.
                return fail("fleet_percentiles", fleet_p99_ms=fleet_p99,
                            r1_p99_ms=r1_p99)

            # -------- exit-code contract BEFORE reading the timeline,
            # so every span writer has flushed and closed.
            for name in ("fleetmon", "router"):
                procs[name].send_signal(signal.SIGTERM)
            rcs = {}
            for name in ("fleetmon", "router"):
                try:
                    rcs[name] = procs[name].wait(timeout=30)
                except subprocess.TimeoutExpired:
                    return fail("exit", error=f"{name} ignored SIGTERM")
            if any(rcs.values()):
                return fail("exit", rcs=rcs)

            # -------- attribution on the merged timeline.
            try:
                _, trace = export_trace(d)
            except (OSError, ValueError) as e:
                return fail("trace", error=f"{type(e).__name__}: {e}")
            events = trace["traceEvents"]
            names = {e["name"] for e in events}
            need = {"route_request", "serve_request", "fleet_start",
                    "fleet_burn_alert"}
            if not need <= names:
                return fail("trace", missing=sorted(need - names))
            lanes = (trace["metadata"].get("request_lanes") or {})
            if not lanes.get("rendered"):
                return fail("trace", error="no request lanes rendered",
                            request_lanes=lanes)
            routed = [e["args"] for e in events
                      if e["name"] == "route_request"
                      and e.get("args", {}).get("replica")]
            served = [e["args"] for e in events
                      if e["name"] == "serve_request"
                      and e.get("args", {}).get("replica")]
            if not routed:
                return fail("attribution",
                            error="no replica-attributed route spans")
            tail_spans = sorted(routed, key=lambda a:
                                a.get("latency_ms", 0.0))[-5:]
            slow_share = sum(1 for a in tail_spans
                             if a["replica"] == "r0") / len(tail_spans)
            if slow_share < 0.6:
                return fail("attribution", error="tail traces do not "
                            "attribute to the slowed replica",
                            tail=tail_spans)
            r0_served = [a for a in served if a["replica"] == "r0"
                         and a.get("infer_ms") and a.get("latency_ms")]
            infer_dominates = bool(r0_served) and max(
                a["infer_ms"] / a["latency_ms"] for a in r0_served) > 0.5
            if r0_served and not infer_dominates:
                return fail("attribution", error="r0 inference segment "
                            "does not dominate its request time",
                            r0_served=r0_served[:5])

            # -------- fleet p99 + burn rate as perfwatch-gated series
            # (lower-is-better latency twins; one sample each ->
            # insufficient_data, never regress).
            traj = os.path.join(d, "fleetmon_traj.json")
            with open(traj, "w") as f:
                json.dump({"metric": "fleetmon_probe", "backend": "cpu",
                           "points": [
                               {"id": "fleet-p99", "status": "ok",
                                "backend": "cpu",
                                "latency_ms": fleet_p99},
                               {"id": "fleet-burn-fast", "status": "ok",
                                "backend": "cpu",
                                "latency_ms": max(burn_fast, 1e-3)},
                           ]}, f)
            pw = subprocess.run(
                [sys.executable,
                 os.path.join(root, "tools", "perfwatch.py"),
                 "--sweep", traj],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, timeout=60)
            if pw.returncode != 0 or \
                    "sweep-lat:fleet-p99" not in pw.stdout:
                return fail("perfwatch", rc=pw.returncode,
                            pw_tail=pw.stdout.strip().splitlines()[-5:])

            return {"ok": True,
                    "requests_ok": lg_result["requests_ok"],
                    "client_failures": 0,
                    "slowest_traces": slowest,
                    "fleet_p99_ms": fleet_p99,
                    "r1_p99_ms": round(r1_p99, 2),
                    "burn_rate_fast": burn_fast,
                    "alerts_total": int(
                        fm.get(ns + "fleet_alerts_total", 0)),
                    "tail_slow_replica_share": slow_share,
                    "infer_segment_dominates": infer_dominates,
                    "request_lanes": lanes,
                    "perfwatch_ingested": True,
                    "rcs": rcs}
        finally:
            for name, p in procs.items():
                if p.poll() is None:
                    p.kill()
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    pass
            for _, fh in logs.values():
                fh.close()


def _check_trace_probe(timeout: int = 300) -> dict:
    """Live observability drill (tpu_resnet/obs): tiny CPU train with the
    telemetry server up, scrape /metrics MID-RUN until the live
    ``model_flops_per_sec`` gauge and the ``train_step_ms`` histogram series carry data, SIGTERM
    the run (graceful-preemption contract), then ``trace-export`` the
    train_dir and schema-check the merged Chrome trace — run_id in the
    trace must match the manifest's. Proves the whole performance-
    observability chain (gauges → histograms → spans → timeline) on this
    machine in one check.

    Thin alias over ``scenarios/trace_probe.json`` — the scenario
    conductor runs the drill; this adapter rebuilds the historical
    DOCTOR_JSON dict from its observations."""
    result, steps = _run_scenario("trace_probe")
    live = (steps.get("live") or {}).get("observed") or {}

    def _shaped(obs, ok):
        return {"ok": ok, "run_id": obs.get("run_id"),
                "trace_events": obs.get("trace_events", 0),
                "preempt_rc": result["rcs"].get("train"), **live}

    if not result["ok"]:
        failed = (result.get("steps") or [{}])[-1]
        # A run_id/span mismatch after a successful export is the
        # historical success-shaped ok=False dict, not a phase failure.
        if (result.get("phase") == "trace_export"
                and "run_id" in (failed.get("observed") or {})):
            return _shaped(failed["observed"], False)
        return _scenario_fail(result)
    return _shaped(steps["trace"]["observed"], True)


def _check_perfwatch() -> dict:
    """Perf-regression verdict over the repo's archived BENCH_*.json
    trajectory (tools/perfwatch.py). ``ok`` is False only on a REGRESS
    verdict — flat/improving/insufficient-data trajectories pass, and a
    checkout without bench artifacts (installed wheel) reports
    skipped=True."""
    import tempfile

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    script = os.path.join(root, "tools", "perfwatch.py")
    if not os.path.exists(script):
        return {"ok": True, "skipped": True,
                "reason": "tools/perfwatch.py not present (installed "
                          "package?)"}
    with tempfile.TemporaryDirectory(prefix="tpu_resnet_pw_") as d:
        out_json = os.path.join(d, "verdict.json")
        try:
            proc = subprocess.run(
                [sys.executable, script, "--root", root,
                 "--json", out_json],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, timeout=60)
        except subprocess.TimeoutExpired:
            return {"ok": False, "error": "perfwatch hung for 60s"}
        try:
            with open(out_json) as f:
                verdict = json.load(f)
        except (OSError, ValueError):
            return {"ok": False, "rc": proc.returncode,
                    "tail": proc.stdout.strip().splitlines()[-5:]}
        out = {"ok": proc.returncode == 0, "rc": proc.returncode,
               "overall": verdict.get("overall")}
        for name, m in (verdict.get("metrics") or {}).items():
            out[name] = {k: m.get(k) for k in
                         ("verdict", "latest", "reference", "ratio")}
        return out


def _check_sweep_probe(timeout: int = 300) -> dict:
    """~30 s scrubbed-CPU drill of the per-knob sweep harness
    (tpu_resnet/tools/sweep.py): a 2-point MLP sweep runs end-to-end —
    every child under the BENCH_CHILD_DEADLINE contract (each ok point
    must report a positive ``deadline_margin_sec``), the final
    RESULT_JSON trajectory is COMPLETE (every declared point has a
    status; a lost point is the BENCH_r04 failure mode), and
    ``tools/perfwatch.py --sweep`` must ingest the artifact. Proves the
    sweep rig on this machine before a chip campaign bets on it.

    Thin alias over ``scenarios/sweep_probe.json`` — the scenario
    conductor runs the drill; this adapter rebuilds the historical
    DOCTOR_JSON dict from its observations."""
    from tpu_resnet.resilience.exitcodes import HOSTENV_TIMEOUT

    result, steps = _run_scenario("sweep_probe")
    rc = result["rcs"].get("sweep")
    sweep_tail = (steps.get("sweep") or {}).get("tail", [])
    if rc == HOSTENV_TIMEOUT:
        return {"ok": False, "error": f"sweep hung for {timeout}s"}
    traj = (steps.get("trajectory") or {}).get("observed") or {}
    if "complete" not in traj:
        return {"ok": False, "rc": rc,
                "error": "no trajectory JSON written",
                "tail": sweep_tail}
    out = {"ok": bool(steps["trajectory"].get("ok")), "rc": rc,
           "complete": traj["complete"], "statuses": traj["statuses"],
           "deadline_honored": traj["deadline_honored"]}
    if (result.get("perfwatch") or {}).get("hung"):
        out.update(ok=False, perfwatch="hung")
        return out
    if _scenario_perfwatch(result, out):
        # The historical sweep shape carried perfwatch_tail, not a phase.
        out.pop("phase", None)
    if not out["ok"]:
        out["tail"] = sweep_tail
    return out


def _check_mem_probe(timeout: int = 300) -> dict:
    """Memory-observability drill (tpu_resnet/obs/memory.py), two
    scrubbed-CPU children:

    1. a tiny train with telemetry up — the ``hbm_*`` gauge series must
       be present in a LIVE /metrics scrape (explicit zeros on CPU,
       where memory_stats is unsupported — presence, never absence, is
       the contract), and after a graceful SIGTERM the ledger
       ``memory.json`` must hold the step's budget with nonzero
       argument/temp bytes, a donation credit, and EXACTLY the program
       keys ``flops.json`` certified (one registry spelling for space
       and time);
    2. a train with a fault-injected RESOURCE_EXHAUSTED
       (resilience.inject_oom_at_step) — the crash must leave a
       schema-valid ``oom_report.json`` carrying a live-array census,
       and the child must still die loudly (forensics never swallow the
       OOM).

    Thin alias over ``scenarios/mem_probe.json`` — the scenario
    conductor runs both children; this adapter rebuilds the historical
    DOCTOR_JSON dict from its observations."""
    result, steps = _run_scenario("mem_probe")
    if not result["ok"]:
        failed = (result.get("steps") or [{}])[-1]
        # An exit code of 0 from the OOM child is the one failure whose
        # historical wording names the contract, not the rc.
        if failed.get("label") == "oom_run":
            return {"ok": False, "phase": "oom",
                    "error": "injected RESOURCE_EXHAUSTED did not fail "
                             "the run (forensics must re-raise)",
                    "tail": failed.get("tail", [])}
        return _scenario_fail(result)
    oom = steps["oom"]["observed"]
    return {"ok": True, **steps["live"]["observed"],
            "ledger_keys": steps["ledger_keys"]["observed"]
            ["ledger_keys"],
            "oom_rc": result["rcs"]["train_oom"],
            "oom_census_buckets": oom["oom_census_buckets"],
            "oom_census_bytes": oom["oom_census_bytes"]}


def _check_partition_probe(timeout: int = 420) -> dict:
    """ZeRO-1 state-partitioner drill on the 8-device fakepod, scrubbed
    CPU children (tiny MLP, momentum slots, global batch 16 over an
    8-way data axis):

    1. a replicated train completes and writes its memory.json ledger
       entry — the twin baseline;
    2. the SAME config under ``mesh.partition=zero1`` is preempted by an
       injected SIGTERM (must exit with the preemption code, checkpoint
       at the stop step) and a second run must resume to completion —
       cross-replica optimizer sharding has to survive the save/restore
       boundary, not just a fresh start;
    3. the zero1 ledger entry's ``opt_state_argument_bytes`` must be
       < 0.3x the replicated twin's (the ~1/8 cut of arXiv:2004.13336
       with generous slack) with the donation credit intact;
    4. ``tools/perfwatch.py --sweep`` must ingest both runs' peak-HBM
       numbers as the lower-is-better ``sweep-mem:`` series, so the
       memory win is a TRACKED trajectory, not a one-shot assertion.

    Thin alias over ``scenarios/partition_probe.json`` — the scenario
    conductor runs the three children; this adapter rebuilds the
    historical DOCTOR_JSON dict from its observations."""
    result, steps = _run_scenario("partition_probe")
    if "opt_bytes" not in steps:
        return _scenario_fail(result)
    out = dict(steps["opt_bytes"]["observed"])
    out.update(preempt_rc=result["rcs"].get("zero1_preempt"),
               resume_rc=result["rcs"].get("zero1_resume"),
               ckpt_at_stop=20)
    if not steps["opt_bytes"].get("ok"):
        # The ratio-check observation is already the historical shape;
        # a missing opt_state entry is the historical ledger phase.
        if "opt_bytes_zero1" not in out:
            return _scenario_fail(dict(result, phase="ledger"))
        out.update(ok=False, phase="opt_bytes",
                   error=steps["opt_bytes"].get("error"))
        return out
    if _scenario_perfwatch(result, out):
        return out
    out["ok"] = True
    return out


def _check_reshape_drill(timeout: int = 480) -> dict:
    """Elastic-capacity drill (tpu_resnet/resilience/elastic.py),
    scrubbed-CPU children (tiny MLP, global batch 16):

    1. a reference run trains straight through 40 steps on the 8-device
       fakepod — the loss stream the reshaped run must reproduce;
    2. an elastic run on the same config is preempted by an injected
       SIGTERM at step 20 (must exit with the preemption code, step-20
       checkpoint on disk), then resumed in a child that only has FOUR
       devices under ``mesh.partition=zero1`` — mesh8→mesh4 AND
       replicated→zero1 in one restore, through the partitioner
       template's explicit cross-topology reshard;
    3. the resumed run must finish, its metrics.jsonl loss stream must
       equal the reference's within 1e-6 at EVERY logged step (the
       deterministic (seed, step) contract across the reshape), a
       ``topology_change`` span must sit on the events.jsonl timeline
       (trace-export's capacity-wave lane) and topology.json must
       record the new shape;
    4. ``tools/perfwatch.py --sweep`` must ingest the drill's pre/post
       steps/s (post normalized by the 8/4 device ratio) — a reshape
       that silently loses throughput beyond the device ratio becomes a
       TRACKED regression, not folklore.

    Thin alias over ``scenarios/reshape_drill.json`` — the scenario
    conductor runs the three children; this adapter rebuilds the
    historical DOCTOR_JSON dict from its observations."""
    result, steps = _run_scenario("reshape_drill")
    if not result["ok"] and result.get("phase") != "perfwatch":
        failed = (result.get("steps") or [{}])[-1]
        observed = failed.get("observed") or {}
        # Two assertion wordings the historical dict spelled differently
        # from the scenario checkers' per-attribute messages.
        if result.get("phase") == "topology_span":
            return {"ok": False, "phase": "topology_span",
                    "error": "topology_change span missing or wrong",
                    "spans": observed.get("spans", [])}
        if (result.get("phase") == "topology_record"
                and "artifact" in observed):
            return {"ok": False, "phase": "topology_record",
                    "error": "topology.json does not record the "
                             "post-reshape shape",
                    "topology": observed["artifact"]}
        return _scenario_fail(result)
    points = {p["id"]: p for p in result.get("series") or []}
    pre_point = points.get("reshape=mesh8_pre")
    post_point = points.get("reshape=mesh4_post")
    out = {"loss_steps": steps["loss_stream"]["observed"]["loss_steps"],
           "max_loss_drift":
               steps["loss_stream"]["observed"]["max_loss_drift"],
           "preempt_rc": result["rcs"].get("elastic_preempt"),
           "resume_rc": result["rcs"].get("elastic_resume"),
           "reshape": steps["topology_span"]["observed"]["spans"][-1],
           "pre_steps_per_sec":
               pre_point["steps_per_sec"] if pre_point else None,
           "post_steps_per_sec":
               post_point.get("raw_value", post_point["steps_per_sec"])
               if post_point else None}
    if _scenario_perfwatch(result, out):
        return out
    out["ok"] = True
    return out


def _check_autoscale_probe(timeout: int = 900) -> dict:
    """Autopilot autoscaling drill in scrubbed CPU subprocesses.

    Thin alias over ``scenarios/autoscale_burst.json`` — the scenario
    conductor runs the whole loop (burst → spawn → admit → calm →
    drain → capacity handoff); this adapter rebuilds the historical
    DOCTOR_JSON dict from its observations."""
    result, steps = _run_scenario("autoscale_burst")
    if not result["ok"]:
        return _scenario_fail(result)
    out = {"scale_up_latency_ms":
               steps["scaleup"]["observed"]["scale_up_latency_ms"],
           "scale_ups": int(steps["scaleup"]["observed"]["scale_ups"]),
           "scale_downs":
               int(steps["rampdown"]["observed"]["scale_downs"]),
           "capacity_lease":
               steps["capacity_lease"]["observed"].get("state",
                                                       "granted"),
           "burst_failed":
               steps["burst_verdict"]["observed"]["failed"],
           "calm_failed":
               steps["calm_verdict"]["observed"]["failed"],
           "colocated_trainer_rc": result["rcs"]["trainer"]}
    if _scenario_perfwatch(result, out):
        return out
    out["ok"] = True
    return out


def _check_fault_drill(timeout: int = 240) -> dict:
    """SIGTERM + resume drill in scrubbed CPU subprocesses (~30 s on a
    healthy box: tiny MLP, 40 steps). Stdlib-only checks: exit codes, the
    checkpoint step directories, and the events.jsonl run spans.

    Thin alias over ``scenarios/fault_drill.json`` — the scenario
    conductor runs both children; this adapter rebuilds the historical
    DOCTOR_JSON dict from its observations."""
    result, steps = _run_scenario("fault_drill")
    if not result["ok"]:
        return _scenario_fail(result)
    spans = [tuple(s) for s in
             steps["resume"]["observed"]["run_spans"]]
    return {"ok": True, "preempt_rc": result["rcs"]["train_preempt"],
            "ckpt_at_stop": 20, "run_spans": spans}


def run_doctor(dataset: str = "", data_dir: str = "", train_dir: str = "",
               probe_timeout: int = 60, mesh_devices: int = 8,
               fault_drill: bool = False, data_bench: bool = False,
               data_bench_secs: float = 4.0, check: bool = False,
               check_matrix: bool = True, serve_probe: bool = False,
               coldstart_probe: bool = False,
               fleet_probe: bool = False, fleetmon_probe: bool = False,
               autoscale_probe: bool = False,
               trace_probe: bool = False, perfwatch: bool = False,
               sweep_probe: bool = False, mem_probe: bool = False,
               partition_probe: bool = False, reshape_drill: bool = False,
               stream=None) -> dict:
    """Run all checks; print human lines to ``stream`` (default stdout),
    return the summary dict (also printed as one final JSON line)."""
    stream = stream or sys.stdout

    def emit(name, result):
        status = "ok" if result.get("ok", True) else "FAIL"
        detail = {k: v for k, v in result.items() if k != "ok"}
        print(f"[doctor] {name:10s} {status}  {detail}", file=stream)

    summary = {"versions": _check_versions()}
    emit("versions", summary["versions"])
    summary["backend"] = _check_backend(probe_timeout)
    emit("backend", summary["backend"])
    summary["cpu_mesh"] = _check_cpu_mesh(mesh_devices, timeout=300)
    emit("cpu_mesh", summary["cpu_mesh"])
    summary["native"] = _check_native()
    emit("native", summary["native"])
    if data_dir:
        summary["dataset"] = _check_dataset(dataset or "cifar10", data_dir)
        emit("dataset", summary["dataset"])
    if train_dir:
        summary["telemetry"] = _check_telemetry(train_dir)
        emit("telemetry", summary["telemetry"])
    if data_bench:
        summary["data_bench"] = _check_data_bench(seconds=data_bench_secs)
        emit("data_bench", summary["data_bench"])
    if check:
        summary["check"] = _check_static_analysis(matrix=check_matrix)
        emit("check", summary["check"])
    if fault_drill:
        summary["fault_drill"] = _check_fault_drill()
        emit("fault_drill", summary["fault_drill"])
    if serve_probe:
        summary["serve_probe"] = _check_serve_probe()
        emit("serve_probe", summary["serve_probe"])
    if coldstart_probe:
        summary["coldstart_probe"] = _check_coldstart_probe()
        emit("coldstart_probe", summary["coldstart_probe"])
    if fleet_probe:
        summary["fleet_probe"] = _check_fleet_probe()
        emit("fleet_probe", summary["fleet_probe"])
    if fleetmon_probe:
        summary["fleetmon_probe"] = _check_fleetmon_probe()
        emit("fleetmon_probe", summary["fleetmon_probe"])
    if autoscale_probe:
        summary["autoscale_probe"] = _check_autoscale_probe()
        emit("autoscale_probe", summary["autoscale_probe"])
    if trace_probe:
        summary["trace_probe"] = _check_trace_probe()
        emit("trace_probe", summary["trace_probe"])
    if perfwatch:
        summary["perfwatch"] = _check_perfwatch()
        emit("perfwatch", summary["perfwatch"])
    if sweep_probe:
        summary["sweep_probe"] = _check_sweep_probe()
        emit("sweep_probe", summary["sweep_probe"])
    if mem_probe:
        summary["mem_probe"] = _check_mem_probe()
        emit("mem_probe", summary["mem_probe"])
    if partition_probe:
        summary["partition_probe"] = _check_partition_probe()
        emit("partition_probe", summary["partition_probe"])
    if reshape_drill:
        summary["reshape_drill"] = _check_reshape_drill()
        emit("reshape_drill", summary["reshape_drill"])
    summary["ok"] = all(v.get("ok", True) for v in summary.values()
                        if isinstance(v, dict))
    print("DOCTOR_JSON: " + json.dumps(summary), file=stream, flush=True)
    return summary
