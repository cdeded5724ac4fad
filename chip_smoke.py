"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py

Drives the main path once, through the CLI a user would call, at the full
width of ImageNet ResNet-50 v2 (25,549,352 parameters, 224x224, 1000
classes, bf16 compute, global batch 128, random weights from a seed):

    data   seeded TFRecord shards of photo-like JPEGs (tools/input_edge.py),
           the native loader rebuilt from loader.cc
    train  python -m tpu_resnet train --preset imagenet ...   (32 steps)
    eval   python -m tpu_resnet eval --once ...
    serve  python -m tpu_resnet serve ... + POST /predict (n = 1, 3, 16),
           GET /info, SIGTERM, the drain's exit 0

and judges each phase by what it left behind, not by its exit code alone.
It runs on whatever chips the children see (mesh.data=-1).

One process per chip: this parent never imports jax (asserted at exit),
and the children run strictly one after another. Without a TPU it says
which platform JAX found, exits 1 and prints no result. On success the
last stdout line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Everything before it — compile seconds, wall time, the JPEG decode
path, the autotune table, per-child compile-cache counts — is a set-up
fact about this run, not a result to compare.
"""

import json
import math
import os
import shutil
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "chip_smoke_out")

TRAIN_STEPS = 32
CHECKPOINT_EVERY = 16
VAL_EXAMPLES = 128
BUCKETS = (1, 2, 4, 8, 16)
REQUEST_SIZES = (1, 3, 16)
NUM_CLASSES = 1000
# ResNet-50 v2 at its published width: parameters + BN running statistics,
# float32 — what /info must report as the bucket programs' weight argument.
RN50_PARAMS = 25_549_352
RN50_BN_STATS = 45_440
PROGRAM_KEY_FAMILY = "imagenet_rn50_bf16"


class SmokeFailure(Exception):
    """A phase failed; the message says which check and what it saw."""


def _require(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def _read_json(path):
    _require(os.path.exists(path), f"missing {path}")
    with open(path) as f:
        return json.load(f)


def _read_jsonl(path):
    _require(os.path.exists(path), f"missing {path}")
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


# ------------------------------------------------------------ phase checks
# Pure functions of a train_dir: tier-1 runs them against a tiny
# `--preset smoke` run on the CPU (tests/test_chip_smoke.py), with
# `tpu=False` dropping only what a CPU cannot produce (an mfu, a probe).

def check_train(train_dir, steps, checkpoint_every, tpu=True):
    """What `train` must leave behind. Returns set-up facts to print."""
    manifest = _read_json(os.path.join(train_dir, "manifest.json"))
    records = [r for r in _read_jsonl(os.path.join(train_dir,
                                                   "metrics.jsonl"))
               if "loss" in r]
    _require(records, "metrics.jsonl has no loss record")
    last = records[-1]
    _require(last.get("step") == steps,
             f"last metrics step {last.get('step')} != {steps}")
    for r in records:
        _require(math.isfinite(r["loss"]),
                 f"non-finite loss {r['loss']} at step {r.get('step')}")
    for step in range(checkpoint_every, steps + 1, checkpoint_every):
        _require(os.path.isdir(os.path.join(train_dir, str(step))),
                 f"no checkpoint for step {step}")
    ledgers = {}
    for name in ("flops.json", "memory.json", "comms.json"):
        ledgers[name] = _read_json(os.path.join(train_dir,
                                                name)).get("entries")
        _require(ledgers[name], f"{name} has no entries")
    spans = _read_jsonl(os.path.join(train_dir, "events.jsonl"))
    compiles = [s for s in spans if s["span"] == "compile"]
    _require(compiles, "events.jsonl has no compile span")
    facts = {
        "devices": manifest["devices"], "mesh": manifest["mesh"]["shape"],
        "versions": manifest["versions"],
        "ledger_keys": {name: sorted(e) for name, e in ledgers.items()},
        "flops_source": sorted({str(e.get("flops_source")) for e in
                                ledgers["flops.json"].values()}),
        "compile_seconds": compiles[0]["seconds"],
        "loss": [(r["step"], round(r["loss"], 4)) for r in records],
    }
    if tpu:
        _require(manifest["devices"]["platform"] == "tpu",
                 f"trained on {manifest['devices']}")
        mfus = [r["mfu"] for r in records if "mfu" in r]
        _require(mfus and all(isinstance(u, float) and 0 < u < 1
                              for u in mfus),
                 "no mfu in metrics.jsonl — device_kind "
                 f"{manifest['devices']['kinds']} not in PEAK_FLOPS_BY_KIND?")
        table = _read_json(os.path.join(train_dir,
                                        "autotune.json"))["decisions"]
        _require(table, "autotune.json has no decision")
        for key, d in table.items():
            _require(not d.get("error") and math.isfinite(d["pallas_us"])
                     and d["pallas_us"] > 0,
                     f"autotune {key}: Pallas candidate did not run: {d}")
        facts["autotune"] = table
    return facts


def check_eval(train_dir, step, examples=None):
    """What `eval --once` must leave behind."""
    best = _read_json(os.path.join(train_dir, "eval", "best_precision.json"))
    _require(best.get("step") == step,
             f"best_precision.json names step {best.get('step')}, "
             f"trained to {step}")
    _require(0.0 <= best["best_precision"] <= 1.0, f"precision {best}")
    spans = [s for s in _read_jsonl(os.path.join(train_dir, "eval",
                                                 "events.jsonl"))
             if s["span"] == "eval_pass" and s.get("step") == step]
    _require(spans and "error" not in spans[-1],
             f"no clean eval_pass span for step {step}")
    if examples is not None:
        _require(spans[-1].get("examples") == examples,
                 f"evaluated {spans[-1].get('examples')} examples, the "
                 f"validation split holds {examples}")
    return {"precision": best["best_precision"],
            "examples": spans[-1].get("examples")}


def check_serve_events(train_dir, buckets):
    """Which buckets warmed, and whether each was a compile or a load."""
    spans = _read_jsonl(os.path.join(train_dir, "serve_events.jsonl"))
    warmed = {s["bucket"]: bool(s["cache_hit"]) for s in spans
              if s["span"] == "serve_warmup_bucket"}
    _require(sorted(warmed) == sorted(buckets),
             f"warmed buckets {sorted(warmed)} != {sorted(buckets)}")
    ready = [s for s in spans if s["span"] == "serve_ready"]
    _require(ready, "no serve_ready event")
    drains = [s for s in spans if s["span"] == "serve_drain"]
    _require(drains and drains[-1].get("clean") is True,
             f"no clean serve_drain span: {drains[-1:]}")
    return {"bucket_cache_hit": warmed,
            "time_to_ready_seconds": ready[-1]["seconds"],
            "compile_cache_hits": ready[-1].get("compile_cache_hits"),
            "compile_cache_misses": ready[-1].get("compile_cache_misses")}


def check_predict(n, status, payload):
    """One /predict?logits=1 answer for a request of ``n`` images."""
    _require(status == 200, f"/predict n={n}: HTTP {status}: {payload}")
    logits = payload.get("logits")
    _require(isinstance(logits, list) and len(logits) == n
             and all(len(row) == NUM_CLASSES for row in logits),
             f"/predict n={n}: logits are not [{n}, {NUM_CLASSES}]")
    _require(all(math.isfinite(v) for row in logits for v in row),
             f"/predict n={n}: non-finite logits")
    argmax = [max(range(NUM_CLASSES), key=row.__getitem__)
              for row in logits]
    _require(payload.get("predictions") == argmax,
             f"/predict n={n}: predictions are not the logits' argmax")
    return logits


# ------------------------------------------------------------------ driving
def _child_env():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [HERE] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                  if p])
    return env


def _kill_group(proc):
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()


def _tail(path, n=30):
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return ""


def _cache_line(log_path):
    """The child's COMPILE_CACHE exit line (hostenv.enable_compile_cache):
    compile requests and persistent-cache hits JAX counted."""
    try:
        with open(log_path, errors="replace") as f:
            for line in reversed(f.readlines()):
                if line.startswith("COMPILE_CACHE "):
                    return json.loads(line[len("COMPILE_CACHE "):])
    except (OSError, ValueError):
        pass
    return None


def run_child(name, argv, timeout):
    """Run one CLI child to its end in its own process group; its output
    goes to chip_smoke_out/logs/<name>.log. Returns the log path."""
    log_path = os.path.join(OUT, "logs", f"{name}.log")
    with open(log_path, "w") as log:
        proc = subprocess.Popen(argv, cwd=HERE, env=_child_env(),
                                stdout=log, stderr=subprocess.STDOUT,
                                start_new_session=True)
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"{name}: no exit within {timeout}s\n"
                               + _tail(log_path))
        finally:
            _kill_group(proc)
    _require(rc == 0, f"{name}: exit code {rc}\n" + _tail(log_path))
    return log_path


def probe_device():
    """Ask a child what JAX finds: the parent must stay off jax, and a CPU
    must be refused before a ResNet-50 is sent to it."""
    code = ("import json, jax; from tpu_resnet.obs.manifest import "
            "device_record, library_versions; "
            "print('DEVICE ' + json.dumps(dict(device_record(jax.devices()),"
            " **library_versions())))")
    proc = subprocess.run([sys.executable, "-c", code], cwd=HERE,
                          env=_child_env(), stdout=subprocess.PIPE,
                          stderr=subprocess.PIPE, text=True, timeout=300)
    for line in proc.stdout.splitlines():
        if line.startswith("DEVICE "):
            return json.loads(line[len("DEVICE "):])
    raise SmokeFailure("JAX found no device (exit code "
                       f"{proc.returncode}):\n{proc.stderr[-2000:]}")


def make_data(data_dir):
    """Seeded shards through the repo's own writer; the native loader is
    rebuilt from loader.cc so no stale .so can serve the run."""
    from tools.input_edge import make_shards
    from tpu_resnet import native
    from tpu_resnet.native.build import build

    build(force=True)
    os.makedirs(data_dir)
    make_shards(data_dir, n_shards=4, per_shard=64, seed=0, train=True)
    make_shards(data_dir, n_shards=2, per_shard=VAL_EXAMPLES // 2, seed=1,
                train=False)
    return native.decode_path()


def _http(url, data=None, headers=None, timeout=120):
    req = urllib.request.Request(url, data=data, headers=headers or {})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, {"error": e.read().decode(errors="replace")[:500]}


def serve_phase(cli, train_dir, n_devices):
    """Start the server, send the requests, SIGTERM it, expect exit 0."""
    import numpy as np

    log_path = os.path.join(OUT, "logs", "serve.log")
    discovery = os.path.join(train_dir, "serve.json")
    if os.path.exists(discovery):
        os.remove(discovery)  # an earlier server's: its port is dead
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            cli + ["serve", "--preset", "imagenet"] + _overrides(train_dir)
            + ["serve.host=127.0.0.1", "serve.port=0",
               f"serve.max_batch={max(BUCKETS)}",
               "serve.reload_interval_secs=0"],
            cwd=HERE, env=_child_env(), stdout=log,
            stderr=subprocess.STDOUT, start_new_session=True)
        try:
            deadline = time.time() + 600
            while not os.path.exists(discovery):
                _require(proc.poll() is None,
                         f"serve exited {proc.returncode} before ready\n"
                         + _tail(log_path))
                _require(time.time() < deadline,
                         "serve not ready within 600s\n" + _tail(log_path))
                time.sleep(0.5)
            base = f"http://127.0.0.1:{_read_json(discovery)['port']}"
            status, health = _http(base + "/healthz")
            _require(status == 200, f"/healthz {status}: {health}")

            rng = np.random.default_rng(0)
            images = rng.integers(0, 256, (max(REQUEST_SIZES), 224, 224, 3),
                                  dtype=np.uint8)
            answers = {}
            for n in REQUEST_SIZES:
                status, payload = _http(
                    base + "/predict?logits=1", data=images[:n].tobytes(),
                    headers={"Content-Type": "application/octet-stream",
                             "X-Shape": f"{n},224,224,3"})
                answers[n] = np.asarray(check_predict(n, status, payload))
            # The same image must get the same logits alone and inside a
            # larger, differently padded batch: inference BN uses running
            # statistics, so any disagreement beyond bf16 rounding is a
            # batching, padding or bucket-program defect.
            scale = float(np.max(np.abs(answers[1][0]))) + 1e-6
            spread = float(np.max(np.abs(
                answers[1][0] - answers[max(REQUEST_SIZES)][0]))) / scale
            _require(spread <= 0.05,
                     f"image 0 alone vs in a batch of {max(REQUEST_SIZES)}:"
                     f" logits differ by {spread:.3f} of their scale")

            status, info = _http(base + "/info")
            _require(status == 200, f"/info {status}: {info}")
            _require(info["devices"]["platform"] == "tpu"
                     and info["devices"]["count"] == n_devices,
                     f"/info names {info.get('devices')}")
            _require(info["weight_bytes"] == 4 * (RN50_PARAMS
                                                  + RN50_BN_STATS),
                     f"/info weight_bytes {info['weight_bytes']}: not the "
                     "full-width ResNet-50")
            _require(info["model_step"] == TRAIN_STEPS
                     and info["num_classes"] == NUM_CLASSES
                     and info["buckets"] == list(BUCKETS),
                     f"/info {info}")
            _require(info["stats"]["failed"] == 0,
                     f"server counted failures: {info['stats']}")

            os.kill(proc.pid, signal.SIGTERM)
            try:
                rc = proc.wait(timeout=180)
            except subprocess.TimeoutExpired:
                raise SmokeFailure("serve: no exit within 180s of SIGTERM\n"
                                   + _tail(log_path))
            _require(rc == 0, f"serve: drain exit code {rc}\n"
                     + _tail(log_path))
        finally:
            _kill_group(proc)
    facts = check_serve_events(train_dir, BUCKETS)
    facts["same_image_logit_rel_spread"] = round(spread, 5)
    return facts, log_path


def _overrides(train_dir):
    return [f"data.data_dir={os.path.join(OUT, 'data')}",
            f"train.train_dir={train_dir}",
            "train.global_batch_size=128", "train.eval_batch_size=128",
            "model.compute_dtype=bfloat16", "mesh.data=-1"]


def main():
    t0 = time.time()
    if not os.path.isdir(os.path.join(HERE, "tpu_resnet")):
        print("chip_smoke.py drives the tpu_resnet checkout it sits in; "
              f"there is none in {HERE}", file=sys.stderr)
        return 2
    device = probe_device()
    print(f"[chip_smoke] device: {json.dumps(device)}", flush=True)
    if device["platform"] != "tpu":
        print(f"chip_smoke: no TPU found — JAX reports platform="
              f"{device['platform']} ({device['kinds']}, {device['count']} "
              "device(s)); nothing was run", file=sys.stderr)
        return 1

    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(os.path.join(OUT, "logs"))
    train_dir = os.path.join(OUT, "train")
    cli = [sys.executable, "-m", "tpu_resnet"]

    t = time.time()
    decode_path = make_data(os.path.join(OUT, "data"))
    print(f"[chip_smoke] data: 256 train + {VAL_EXAMPLES} validation JPEGs "
          f"in {time.time() - t:.0f}s; decode path: {decode_path}",
          flush=True)

    t = time.time()
    train_log = run_child(
        "train", cli + ["train", "--preset", "imagenet"]
        + _overrides(train_dir)
        + [f"train.train_steps={TRAIN_STEPS}",
           f"train.checkpoint_every={CHECKPOINT_EVERY}",
           "train.log_every=8", "train.summary_every=8"], timeout=900)
    facts = check_train(train_dir, TRAIN_STEPS, CHECKPOINT_EVERY)
    _require(all(PROGRAM_KEY_FAMILY in k for keys in
                 facts["ledger_keys"].values() for k in keys),
             f"ledger keys {facts['ledger_keys']} are not "
             f"{PROGRAM_KEY_FAMILY}")
    _require(facts["devices"]["count"] == device["count"],
             f"probe saw {device['count']} devices, train used "
             f"{facts['devices']}")
    print(f"[chip_smoke] train: {time.time() - t:.0f}s, mesh "
          f"{facts['mesh']}, first-dispatch compile of the step "
          f"{facts['compile_seconds']}s, FLOPs source "
          f"{facts['flops_source']}, loss by step {facts['loss']}",
          flush=True)
    print(f"[chip_smoke] train compile cache: {_cache_line(train_log)}")
    print("[chip_smoke] autotune decisions: "
          + json.dumps(facts["autotune"]), flush=True)

    t = time.time()
    eval_log = run_child(
        "eval", cli + ["eval", "--once", "--preset", "imagenet"]
        + _overrides(train_dir), timeout=600)
    ev = check_eval(train_dir, TRAIN_STEPS, VAL_EXAMPLES)
    print(f"[chip_smoke] eval: {time.time() - t:.0f}s, {ev}; compile "
          f"cache: {_cache_line(eval_log)}", flush=True)

    t = time.time()
    sv, serve_log = serve_phase(cli, train_dir, device["count"])
    print(f"[chip_smoke] serve: {time.time() - t:.0f}s, {sv}; compile "
          f"cache: {_cache_line(serve_log)}", flush=True)

    print(f"[chip_smoke] wall time {time.time() - t0:.0f}s "
          f"(jax {device['jax']}, jaxlib {device['jaxlib']}, libtpu "
          f"{device['libtpu']})", flush=True)
    assert "jax" not in sys.modules, "the parent must never import jax"
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kinds"][0],
        "count": device["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED — {e}", file=sys.stderr)
        sys.exit(1)
