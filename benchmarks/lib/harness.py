"""One run of one cell: set-up, window, comparison, one result line.

The entry the window drives is ``tpu_resnet.train.loop.train(cfg,
metrics=<WindowWriter>)``, the call behind ``python -m tpu_resnet train``.
The loop calls the writer at its log boundaries, right after it has fetched
the newest loss from the device, so every call is a synced timestamp. The
window opens on such a boundary once the warm-up boundaries have passed,
closes on the first boundary at least ``--seconds`` later, and the loop is
then stopped the way a preemption stops it (SIGTERM to the shutdown
coordinator; its final save falls after the window). The rate is the
examples of all steps (steps x global batch; for the families so far an
example is an image) between the two boundaries over the time between them.

What belongs to a model family is the family's module to say
(``families/<family>.py``, named by the configuration's file and found by
``Manifest.family_of``): the example input the weights are drawn on, the
host copy of a state that is compared, the plain reference and its
stand-ins in a lower precision, the numbers read from the two, and the
FLOPs of one example.

``FirstDispatch`` wraps the chunk runner the loop builds
(``device_data.compile_staged_stream_steps``, which both input edges go
through) and passes every call through unchanged. Around the first call
only, during set-up, it copies to the host what the comparison needs: the
state before (the program donates it), the rows the input edge fed, the
state and the metrics after. That one compiled object then serves the
window.
"""

from __future__ import annotations

import atexit
import math
import os
import shutil
import signal
import sys
import time
from types import SimpleNamespace
from typing import Callable, Dict, List, Optional

import numpy as np

from benchmarks.lib import check, lastline, peaks, trace_reduce
from benchmarks.lib.manifest import Manifest

CACHE_DIR = ".bench_cache"  # inside the checkout; data sets and train_dirs
_COMPILE_EVENTS = ("/jax/compilation_cache/compile_requests_use_cache",
                   "/jax/core/compile/backend_compile_duration")


class BenchmarkError(RuntimeError):
    """The run cannot produce a result; exit non-zero, print no line."""


def log(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


# ------------------------------------------------------------ tree helpers
def flat(tree) -> Dict[str, np.ndarray]:
    """A pytree as ``{"a/b/c": host array}``."""
    import jax

    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = {}
    for path, leaf in leaves:
        key = "/".join(str(getattr(p, "key", getattr(p, "name", getattr(
            p, "idx", p)))) for p in path)
        out[key] = np.asarray(jax.device_get(leaf))
    return out


# ---------------------------------------------------------- first dispatch
class FirstDispatch:
    """Observes the loop's chunk runner; see the module docstring.
    ``snapshot`` is the family's: the host copy of a state that is
    compared. ``fault`` (tests and fault readings only) replaces the
    runner by a broken one underneath the observation."""

    def __init__(self, snapshot: Callable,
                 fault: Optional[Callable] = None):
        self.snapshot = snapshot
        self.fault = fault
        self.calls = 0
        self.steps = 0          # steps dispatched so far
        self.raised = 0         # steps whose dispatch raised
        self.before = self.after = self.metrics = None
        self.rows = None        # (inputs, labels) the first call consumed
        self.spans: List[tuple] = []  # (enter_ns, exit_ns) of every call
        self._original = None

    def install(self) -> "FirstDispatch":
        from tpu_resnet.data import device_data

        self._module = device_data
        self._original = device_data.compile_staged_stream_steps

        def compile_observed(*args, **kwargs):
            inner = self._original(*args, **kwargs)
            if self.fault is not None:
                inner = self.fault(inner)
            return self._wrap(inner)

        device_data.compile_staged_stream_steps = compile_observed
        return self

    def uninstall(self) -> None:
        if self._original is not None:
            self._module.compile_staged_stream_steps = self._original
            self._original = None

    def _wrap(self, inner):
        import jax

        def run(state, gi, gl, off, c):
            first = self.calls == 0
            self.calls += 1
            if first:
                self.before = self.snapshot(state)
                # fetched whole and cut on the host: a slice on the
                # device would be a copy counted in the program's peak
                self.rows = (np.asarray(jax.device_get(gi))[off:off + c],
                             np.asarray(jax.device_get(gl))[off:off + c])
            t_in = time.monotonic_ns()
            try:
                out = inner(state, gi, gl, off, c)
            except Exception:
                self.raised += c
                raise
            self.spans.append((t_in, time.monotonic_ns()))
            self.steps += c
            if first:
                self.after = self.snapshot(out[0])
                self.metrics = {k: float(v) for k, v in
                                jax.device_get(out[1]).items()}
            return out

        return run

    def host_spans(self, lo_ns: int, hi_ns: int) -> List[tuple]:
        """What the loop was doing between ``lo_ns`` and ``hi_ns`` on the
        monotonic clock, as far as the harness can see from outside: in a
        dispatch, or between two (``next(data_iter)``, the log boundary's
        fetch, bookkeeping). In nanoseconds since ``lo_ns``."""
        calls = [(a - lo_ns, b - lo_ns) for a, b in self.spans
                 if b > lo_ns and a < hi_ns]
        out = [("train.loop dispatch", a, b) for a, b in calls]
        edges = [0] + [t for ab in calls for t in ab] + [hi_ns - lo_ns]
        out += [("train.loop between dispatches (data wait, log fetch)",
                 edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        return out


# ------------------------------------------------------------------ window
class WindowWriter:
    """The ``metrics=`` argument of ``train()``: a clock on the loop's
    synced log boundaries, and the collector of what the loop hands over
    there (``data_wait_sec``, ``dispatch_sec``, the iterators' stats)."""

    enabled = True

    def __init__(self, seconds: float, warmup_boundaries: int,
                 observer: FirstDispatch, trace_dir: Optional[str],
                 started: float, host_tracer_level: int = 0):
        self.seconds = seconds
        self.warmup_boundaries = warmup_boundaries
        self.observer = observer
        self.trace_dir = trace_dir
        self.started = started
        self.host_tracer_level = host_tracer_level
        self.phase = "warmup"
        self.boundaries = 0
        self.records: List[Dict] = []
        self.t0 = self.t1 = self.step0 = self.step1 = None
        # The capture's clock counts from the entry into start_trace (the
        # profiler shifts every timestamp by its session's start); the
        # traced window runs from its return to the closing boundary.
        self.trace_zero_ns = self.trace_from_ns = self.trace_until_ns = 0
        self.dispatched0 = self.dispatched1 = 0
        self.raised0 = 0
        self.compiles: List[float] = []   # clock of every compile request
        self.nonfinite_steps = 0
        self._last_t = self._last_step = None

    # jax.monitoring listeners (registered by run_cell)
    def on_event(self, event, **_):
        if event in _COMPILE_EVENTS:
            self.compiles.append(time.perf_counter())

    def on_duration(self, event, duration, **_):
        del duration
        self.on_event(event)

    def compiles_in_window(self) -> int:
        return sum(1 for t in self.compiles if self.t0 < t <= self.t1)

    def write(self, step: int, scalars: Dict[str, float]) -> None:
        now, now_ns = time.perf_counter(), time.monotonic_ns()
        self.boundaries += 1
        if self.phase == "window":
            rec = dict(scalars)
            rec["_dt"] = now - self._last_t
            rec["_steps"] = step - self._last_step
            self.records.append(rec)
            if not math.isfinite(rec.get("loss", 0.0)):
                self.nonfinite_steps += rec["_steps"]
            if now - self.t0 >= self.seconds:
                self.t1, self.step1 = now, step
                self.trace_until_ns = now_ns
                self.dispatched1 = self.observer.steps
                if self.trace_dir:
                    import jax
                    jax.profiler.stop_trace()
                self.phase = "done"
                log(f"window closed at step {step}: "
                    f"{self.step1 - self.step0} steps in "
                    f"{self.t1 - self.t0:.3f} s; stopping the loop")
                os.kill(os.getpid(), signal.SIGTERM)
        elif self.phase == "warmup" and \
                self.boundaries >= self.warmup_boundaries:
            if self.trace_dir:
                import jax
                # Device events only. The Python tracer logs every call
                # of every thread (a 15 s capture was 826 MB), and the
                # host tracer at its lowest level still records one event
                # per chunk of every host-to-device transposition: 22
                # million in 15 s of cell 1, which slowed the decode
                # workers by more than half, took stop_trace 150 s and the
                # reduction 138 s (my chip runs, PR 25).
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                options.host_tracer_level = self.host_tracer_level
                self.trace_zero_ns = time.monotonic_ns()
                jax.profiler.start_trace(self.trace_dir,
                                         profiler_options=options)
                self.trace_from_ns = time.monotonic_ns()
            self.t0, self.step0 = time.perf_counter(), step
            self.dispatched0 = self.observer.steps
            self.raised0 = self.observer.raised
            self.phase = "window"
            log(f"window opened at step {step}, "
                f"{self.t0 - self.started:.1f} s after start")
            now = self.t0
        self._last_t, self._last_step = now, step

    def write_images(self, *args, **kwargs) -> None:
        pass

    def close(self) -> None:
        if self.phase == "window" and self.trace_dir:
            import jax
            jax.profiler.stop_trace()  # the loop died inside the window


# ----------------------------------------------------------------- the run
# ``train.seed`` is the same in every run. The loop draws its weights from
# it, but it also bakes it into every compiled step as a constant (the
# preprocessing key and the resident shuffle close over it), so a seed of
# its own for each run would compile every program anew in every run. The
# run's weights come from ``--seed`` all the same: ``plant_weights``.
TRAIN_SEED = 0


def build_config(config: Dict, traffic: Dict, chips: int,
                 data_dir: str, train_dir: str):
    """The program's ``RunConfig`` for the cell: the preset, the
    configuration's and the traffic's overrides, then only what a run
    needs (directories, the mesh, accounting off, and a summary at every
    log boundary, which adds no device sync)."""
    from tpu_resnet.config import load_config

    cfg = load_config(config["preset"], overrides=(
        list(config.get("overrides", ())) + list(traffic.get("overrides", ()))
        + ["train.mfu_accounting=false", "train.memory_ledger=false",
           "train.comms_ledger=false", f"mesh.data={chips}"]))
    cfg.train.seed = TRAIN_SEED
    cfg.train.train_dir = train_dir
    cfg.data.data_dir = data_dir
    cfg.train.summary_every = cfg.train.log_every
    return cfg


def plant_weights(cfg, seed: int, start_step: int, example_input) -> None:
    """Draw the run's initial state from ``--seed`` with the program's own
    initializer, shown the family's ``example_input``, and save it as a
    checkpoint of the fresh ``train_dir``: the loop resumes from it (its
    documented restart path) and so trains the seed's weights under the
    fixed ``train.seed``.

    The checkpoint is labelled ``start_step`` (the traffic file's; the
    state's step and the optimizer's counts say the same). The loop ends
    its fused chunks on its log boundaries, so a streaming run resumed at
    step 16 makes its first dispatch the 4-step program (16 to 20) that
    every later interval runs too, and the comparison follows 4 steps,
    not 8: the first steps from a fresh initialization amplify rounding
    by about 1.4 x a step (PERF.md)."""
    import jax
    import jax.numpy as jnp

    from tpu_resnet import parallel, resilience
    from tpu_resnet.models import build_model
    from tpu_resnet.train import schedule as sched_lib
    from tpu_resnet.train.checkpoint import CheckpointManager
    from tpu_resnet.train.state import init_partitioned_state

    mesh = resilience.elastic.resolve(cfg).mesh
    state = init_partitioned_state(
        build_model(cfg), cfg.optim,
        sched_lib.build_schedule(cfg.optim, cfg.train),
        jax.random.PRNGKey(seed % (2 ** 31 - 1)),
        example_input, parallel.make_partitioner(cfg.mesh, mesh))
    if start_step:
        def at_start(x):
            whole = x.ndim == 0 and jnp.issubdtype(x.dtype, jnp.integer)
            return jnp.full_like(x, start_step) if whole else x

        state = state.replace(
            step=at_start(state.step),
            opt_state=jax.tree_util.tree_map(at_start, state.opt_state))
    ckpt = CheckpointManager(cfg.train.train_dir, keep=1)
    try:
        if not ckpt.save(start_step, state, force=True):
            raise BenchmarkError("the planted checkpoint was not saved")
        ckpt.wait()
    finally:
        ckpt.close()
    for leaf in jax.tree_util.tree_leaves(state):
        leaf.delete()


def device_facts(chips: int, require_tpu: bool):
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if require_tpu and d0.platform != "tpu":
        raise BenchmarkError(
            f"JAX found platform {d0.platform!r} ({d0.device_kind}), not a "
            f"TPU; the benchmark measures on the chip only")
    if len(devices) < chips:
        raise BenchmarkError(f"the cell needs {chips} chip(s), JAX found "
                             f"{len(devices)}")
    return devices[:chips]


def memory_peak(devices) -> int:
    """Peak bytes held on the fullest device: the allocator's peak of live
    buffers plus its peak of memory reserved for running programs. On the
    TPU the two are counted apart: ``peak_bytes_in_use`` leaves out a
    program's temporaries, which stand under ``peak_bytes_reserved`` (a
    jitted function with 1.07 GB of temporaries moved the first by 5 MB
    and the second by 1.07 GB; my chip run, PR 25). 0 where the backend
    keeps no such count (the CPU), which the result line then refuses."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             started: float, manifest: Optional[Manifest] = None,
             require_tpu: bool = True, control: str = "",
             fault: Optional[Callable] = None, out=sys.stdout) -> int:
    """Run one cell once and print its result line on ``out``. Returns the
    exit code. ``control`` (comma-separated names of the family's
    ``STAND_INS``) also reads the numbers of the reference in a lower
    precision put in the
    program's place (never part of a driver's run); ``fault`` breaks the
    timed path underneath (tests, fault readings)."""
    manifest = manifest or Manifest()
    cell = manifest.workload(workload)
    config = manifest.config_of(workload)
    traffic = manifest.traffic_of(workload)
    limits = manifest.limits_of(workload)
    family = manifest.family_of(workload)
    make_data = manifest.data_kind_of(workload)
    metrics = manifest.metrics_for(workload, trace)
    readers = {m["name"]: manifest.reader(m["name"])
               for m in metrics if trace}
    chips = int(cell["chips"])

    import jax

    from tpu_resnet import hostenv
    from tpu_resnet.resilience import Preempted
    from tpu_resnet.train.loop import train

    devices = device_facts(chips, require_tpu)
    kind = devices[0].device_kind
    # Off the chip (rehearsals, tests) the v5e's row stands in so that the
    # arithmetic runs; such a line is never reported.
    peak = peaks.peaks_for(kind if require_tpu else "TPU v5 lite")
    # Registered before the program registers its own exit line, so that
    # it runs after it: the numbers compared are the last lines on stderr.
    last_words: List[str] = []
    atexit.register(lambda: print(*last_words, sep="\n", file=sys.stderr,
                                  flush=True) if last_words else None)
    # The compile cache is the benchmark's own directory inside the
    # checkout, with no cap on its size; the program takes it through the
    # variable it honours. (A capped directory that the machine provided
    # never kept the 178 MB step programs: every run compiled them again,
    # set-up 233-293 s with 244 of 247 requests hit; my chip runs, PR 25.)
    cache_dir = os.path.join(manifest.root, CACHE_DIR, "jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_compilation_cache_max_size", -1)
    hostenv.enable_compile_cache()

    run_dir = os.path.join(manifest.root, CACHE_DIR, workload)
    data_dir = os.path.join(run_dir, "data")
    train_dir = os.path.join(run_dir, "train")
    trace_dir = os.path.join(run_dir, "trace") if trace else None
    for d in (train_dir, trace_dir):
        if d:
            shutil.rmtree(d, ignore_errors=True)
    os.makedirs(train_dir)
    # anew from the seed: a run leaves one data set behind, not one a seed
    shutil.rmtree(data_dir, ignore_errors=True)
    os.makedirs(data_dir)
    make_data(data_dir, traffic["data"], seed)
    log(f"data set made from seed {seed} "
        f"({time.perf_counter() - started:.1f} s after start)")

    cfg = build_config(config, traffic, chips, data_dir, train_dir)
    start_step = int(traffic.get("start_step", 0))
    plant_weights(cfg, seed, start_step, family.example_input(cfg))
    log(f"weights drawn from seed {seed} and planted as step {start_step} "
        f"({time.perf_counter() - started:.1f} s after start)")
    observer = FirstDispatch(family.snapshot, fault).install()
    window_s = float(traffic["trace_seconds"] if trace else seconds)
    writer = WindowWriter(window_s, int(traffic["warmup_boundaries"]),
                          observer, trace_dir, started,
                          int(traffic.get("host_tracer_level", 0)))
    jax.monitoring.register_event_listener(writer.on_event)
    jax.monitoring.register_event_duration_secs_listener(writer.on_duration)
    state = None
    try:
        state = train(cfg, metrics=writer)
    except Preempted as stop:
        state = stop.state
    finally:
        observer.uninstall()
    if writer.phase != "done":
        raise BenchmarkError(f"the loop ended in phase {writer.phase!r} "
                             f"before the window closed")
    if observer.after is None:
        raise BenchmarkError("the loop dispatched nothing through "
                             "compile_staged_stream_steps")
    n_compiles = writer.compiles_in_window()
    if n_compiles:
        raise BenchmarkError(f"{n_compiles} compile request(s) fell inside "
                             f"the measured window")

    # For reading what a shorter or longer window would have measured.
    log(f"window intervals, s: "
        f"{[round(r['_dt'], 4) for r in writer.records]} of "
        f"{writer.records[0]['_steps']} steps each")
    mem_peak = memory_peak(devices)
    log(f"memory stats of device 0: {devices[0].memory_stats()}")
    autotune = os.path.join(train_dir, "autotune.json")
    if os.path.exists(autotune):
        with open(autotune) as f:
            log(f"autotune decisions: {f.read()[:600]}")
    if not require_tpu and not mem_peak:
        mem_peak = 1  # rehearsal off the chip: the CPU keeps no count
    del state
    for arr in jax.live_arrays():
        arr.delete()
    jax.clear_caches()

    # ------------------------------------------------ the window's numbers
    steps = writer.step1 - writer.step0
    span = writer.t1 - writer.t0
    batch = cfg.train.global_batch_size
    attempted = writer.dispatched1 - writer.dispatched0
    failed = min(attempted, writer.nonfinite_steps
                 + observer.raised - writer.raised0)
    values: Dict[str, float] = {}
    breakdown = None
    device = {"platform": devices[0].platform, "kind": kind, "count": chips,
              "memory_peak_bytes": mem_peak}
    if trace:
        # The capture carries no host events (see WindowWriter): the
        # harness's own spans name the idle gaps instead.
        zero = writer.trace_zero_ns
        reduction = trace_reduce.reduce(
            trace_reduce.load(trace_reduce.find_capture(trace_dir)),
            window_ns=(writer.trace_from_ns - zero,
                       writer.trace_until_ns - zero),
            cpu_stand_in=not require_tpu,
            host_spans=observer.host_spans(zero, writer.trace_until_ns))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = reduction["busy_s"]
        device["window_s"] = reduction["window_s"]
        breakdown = {"device_ops": reduction["device_ops"],
                     "idle_gaps": reduction["idle_gaps"]}
        run = SimpleNamespace(
            window_s=span, steps=steps, images=steps * batch,
            global_batch=batch, chips=chips, records=writer.records,
            trace=reduction, peaks=peak, arch=config["model"],
            example=family.example(config["model"]),
            flops_per_image=family.train_flops_per_example(
                config["model"]))
        for name, read in readers.items():
            values[name] = read(run)
        log(f"trace: busy {reduction['busy_s']:.4f} s of "
            f"{reduction['window_s']:.4f} s (host clock {span:.4f} s); per "
            f"device {reduction['per_device_busy_s']}, clipped away outside "
            f"the window {reduction['outside_window_s']}; start_trace took "
            f"{(writer.trace_from_ns - zero) / 1e9:.3f} s, operations "
            f"{reduction['first_op_s']:.3f}..{reduction['last_op_s']:.3f} s "
            f"into the capture")
    else:
        values["train_images_per_s"] = steps * batch / span
        values["setup_s"] = writer.t0 - started

    # ------------------------------------------------------ the comparison
    t_ref = time.perf_counter()
    train_seed = cfg.train.seed
    reference = family.follow(observer.before, observer.rows, config,
                              train_seed)
    # the state after the chunk, and every part of the shared start under
    # its name with a 0 (``params0``, ``step0``)
    program = dict(observer.after,
                   **{k + "0": v for k, v in observer.before.items()},
                   loss=observer.metrics["loss"],
                   gnorm=observer.metrics["grad_norm"],
                   rows=len(observer.rows[0]))
    read = family.readings(program, reference)
    correct, compared = check.judge(read, limits)
    log(f"reference followed {program['rows']} steps in "
        f"{time.perf_counter() - t_ref:.1f} s; losses {reference['losses']}"
        f" | program's last {program['loss']}")
    for name in filter(None, control.split(",")):
        if name not in family.STAND_INS:
            raise BenchmarkError(f"unknown stand-in {name!r}; the family "
                                 f"has {family.STAND_INS}")
        t_ctl = time.perf_counter()
        ctl = family.follow(observer.before, observer.rows, config,
                            train_seed, quantize=name)
        # the stand-in's state and last step in the program's place
        ctl_prog = dict(program, **{k: v for k, v in ctl.items()
                                    if k in program})
        ctl_ok, ctl_cmp = check.judge(family.readings(ctl_prog, reference),
                                      limits)
        log(f"CONTROL {name} correct={ctl_ok} "
            f"({time.perf_counter() - t_ctl:.1f} s): {ctl_cmp}")
        log(f"CONTROL {name} losses {ctl['losses']} worst leaves "
            f"{check.worst_leaves(family.groups(ctl_prog, reference))}")
    if control:
        log(f"PROGRAM worst leaves "
            f"{check.worst_leaves(family.groups(program, reference))}")

    line = lastline.build(correct=correct, attempted=attempted,
                          failed=failed, values=values, metrics=metrics,
                          device=device, trace=trace, breakdown=breakdown,
                          compared=compared)
    last_words.extend(f"compared {name} = {value} (limit {limit})"
                      for name, (value, limit) in compared.items())
    print(line, file=out, flush=True)
    return 0
