"""Benchmark harness — one process on the chip, ONE JSON line at the end.

Headline metric: ResNet-50 CIFAR-10 training steps/sec at global batch 128
on the available chips — directly comparable to the reference's published
'local' number: 13.94 steps/s, README.md:28 (BASELINE.md row 1), which is
``vs_baseline``'s denominator. A second entry times the ImageNet-shaped
workload (ResNet-50 @ 224x224, batch 128, bf16) against the reference's
single-node 1ps-1wk b128 line (0.96 steps/s, README.md:48) and reports MFU
(measured train-step FLOPs over the chip's peak).

The measured step is the full training step: on-device augmentation
(pad/crop/flip/standardize), bf16 forward/backward, L2-in-loss, momentum
update, BN stats update — i.e. what the reference's
``mon_sess.run(train_op)`` covered (resnet_cifar_train.py:343-344), input
included. The CIFAR input edge is the framework's device-resident path
(tpu_resnet/data/device_data.py): the training split lives in HBM, batches
are cut on-device, and ``train.steps_per_call`` steps run per dispatch —
the same configuration a real CIFAR training run uses by default.
Synthetic data is used so the benchmark needs no dataset download; the
compute path is identical.

A number from this file is a number from a TPU: without one it says which
platform JAX found, prints no rate and exits 1 — there is no CPU
fallback. Every section runs even if an earlier one raised; a section
that raised is filed under ``errors`` in the final line and makes the
exit code 1, so a green run means every section measured.

    python bench.py            # measure (needs the chip)
    python bench.py --sweep    # per-knob sweep (tpu_resnet/tools/sweep.py)
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

BASELINE_CIFAR_SPS = 13.94     # reference README.md:28 (local b128)
BASELINE_IMAGENET_SPS = 0.96   # reference README.md:48 (1ps-1wk b128)

HEADLINE_METRIC = "cifar10_resnet50_train_steps_per_sec_b128"

def _peak_flops(device_kind: str):
    """Peak dense bf16 FLOP/s per chip — the shared table lives with the
    MFU accounting layer (tpu_resnet/obs/mfu.py); None for a chip it does
    not know."""
    from tpu_resnet.obs.mfu import peak_flops_per_chip

    return peak_flops_per_chip(device_kind)


def _hbm_bytes(device_kind: str):
    """HBM capacity per chip — the peak-FLOPs table's memory twin
    (tpu_resnet/obs/memory.py). Lets bench report hbm_utilization next to
    MFU on chips whose memory_stats() reports usage but no bytes_limit."""
    from tpu_resnet.obs.memory import hbm_bytes_per_chip

    return hbm_bytes_per_chip(device_kind)


def _hbm_snapshot(device_kind: str):
    """Post-measurement HBM utilization from live device stats
    (obs/memory.py sample_device_memory): peak bytes vs the reported or
    table capacity. {} on backends without memory_stats (CPU) — bench
    lines then simply omit the hbm fields, like mfu without a peak."""
    from tpu_resnet.obs.memory import sample_device_memory

    sample = sample_device_memory()
    if not sample:
        return {}
    out = {"hbm_bytes_peak": int(sample["hbm_bytes_peak"])}
    limit = sample.get("hbm_bytes_limit") or _hbm_bytes(device_kind)
    if limit:
        out["hbm_bytes_limit"] = int(limit)
        out["hbm_utilization"] = round(
            sample["hbm_bytes_peak"] / limit, 4)
    return out


# --------------------------------------------------------------------------
# measurements
# --------------------------------------------------------------------------

def _build_train_setup(mesh, preset, resnet_size, batch, dtype, image,
                       synthetic=False, width=None, num_classes=None,
                       mutate_cfg=None):
    """Shared measurement scaffolding: resolved config + model + schedule
    + replicated initial state (one copy of what every measurement
    needs). ``None`` overrides keep the preset's values; ``synthetic``
    swaps the dataset for download-free data with the same class count
    (unless ``num_classes`` overrides it). ``mutate_cfg`` (cfg -> None)
    applies arbitrary overrides after the named ones — the hook
    tools/fused_model_ab.py uses to flip ``model.fused_blocks``."""
    import jax
    import jax.numpy as jnp

    from tpu_resnet.config import load_config
    from tpu_resnet import parallel
    from tpu_resnet.models import build_model
    from tpu_resnet.train import build_schedule, init_state

    cfg = load_config(preset)
    if synthetic:
        classes = num_classes or cfg.data.num_classes
        cfg.data.dataset = "synthetic"
        cfg.data.synthetic_classes = classes
    elif num_classes is not None and num_classes != cfg.data.num_classes:
        raise ValueError(f"num_classes={num_classes} conflicts with "
                         f"preset {preset!r} ({cfg.data.num_classes})")
    cfg.data.image_size = image
    cfg.train.global_batch_size = batch
    if resnet_size is not None:
        cfg.model.resnet_size = resnet_size
    if width is not None:
        cfg.model.width_multiplier = width
    cfg.model.compute_dtype = dtype
    if mutate_cfg is not None:
        mutate_cfg(cfg)

    model = build_model(cfg)
    sched = build_schedule(cfg.optim, cfg.train)
    rng = jax.random.PRNGKey(0)
    state = init_state(model, cfg.optim, sched, rng,
                       jnp.zeros((1, image, image, 3)))
    state = jax.device_put(state, parallel.replicated(mesh))
    return cfg, model, sched, state, rng


def _fetch_sync(x) -> float:
    """Timing barrier: fetch the scalar to the host. A device→host copy of
    the result cannot complete before every step it depends on, so every
    timed loop closes over this (docs/PERF.md, "RETRACTION": a round-3
    sweep timed with ``block_until_ready`` alone once recorded the
    dispatch-enqueue rate as a step rate)."""
    import jax
    import numpy as np
    return float(np.asarray(jax.device_get(x)))


def _measure_cifar(mesh, plans, preset="cifar10", resnet_size=None,
                   batch=128, dtype="bfloat16", split=50_000, width=None,
                   num_classes=None, mutate_cfg=None, breakdown_out=None):
    """Resident-path CIFAR-shaped measurement over one shared setup; model
    and optimizer come from ``preset`` (overridable for smoke tests).

    ``plans`` is a list of (steps_per_call, warmup_chunks, measure_chunks);
    each plan starts at an epoch boundary and must fit within one epoch
    (compile_resident_steps' no-boundary-crossing contract). Returns
    {steps_per_call: steps/sec}. ``breakdown_out`` (a dict) gains
    ``compile_seconds`` — the fetch-synced wall time of the first dispatch
    (trace + XLA compile + first chunk), the same number a real run
    reports via tpu_resnet/obs/breakdown.py."""
    import jax

    from tpu_resnet.data import cifar as cifar_data
    from tpu_resnet.data import device_data
    from tpu_resnet.data.augment import get_augment_fns
    from tpu_resnet.train.step import make_train_step

    cfg, model, sched, state, rng = _build_train_setup(
        mesh, preset, resnet_size=resnet_size, batch=batch, dtype=dtype,
        image=32, synthetic=True, width=width, num_classes=num_classes,
        mutate_cfg=mutate_cfg)

    # CIFAR-sized synthetic split, resident in HBM like a real run.
    images, labels = cifar_data.synthetic_data(split, 32,
                                               cfg.data.num_classes)
    ds = device_data.DeviceDataset(mesh, images, labels,
                                   cfg.train.global_batch_size, seed=0)
    augment_fn, _ = get_augment_fns("cifar10")
    run_chunk = device_data.compile_resident_steps(
        make_train_step(model, cfg.optim, sched, cfg.data.num_classes,
                        augment_fn, base_rng=rng, mesh=mesh), ds, mesh,
        max(k for k, _, _ in plans))

    spe = ds.steps_per_epoch
    results = {}
    step = 0
    first_t0 = time.perf_counter()
    first_dispatch = True
    for k, warmup_chunks, measure_chunks in plans:
        if warmup_chunks < 1:
            raise ValueError(f"plan k={k}: warmup_chunks must be >= 1 "
                             "(the timed loop reads the warmed metrics)")
        if measure_chunks < 1:
            raise ValueError(f"plan k={k}: measure_chunks must be >= 1 "
                             "(zero measured chunks would report 0 st/s "
                             "as a real number)")
        if (warmup_chunks + measure_chunks) * k > spe:
            raise ValueError(f"plan k={k} spans more than one epoch")
        step = -(-step // spe) * spe  # align to the next epoch boundary
        for _ in range(warmup_chunks):
            state, metrics = run_chunk(state, step, k)
            step += k
            if first_dispatch:
                first_dispatch = False
                _fetch_sync(metrics["loss"])
                if breakdown_out is not None:
                    breakdown_out["compile_seconds"] = round(
                        time.perf_counter() - first_t0, 3)
        _fetch_sync(metrics["loss"])

        t0 = time.perf_counter()
        for _ in range(measure_chunks):
            state, metrics = run_chunk(state, step, k)
            step += k
        _fetch_sync(metrics["loss"])
        results[k] = measure_chunks * k / (time.perf_counter() - t0)
    return results


def _measure_cifar_streaming(mesh, warmup_super, measure_super, stage=8,
                             resnet_size=50, batch=128,
                             dtype="bfloat16", split=50_000):
    """CIFAR through the *streaming* input edge (host batcher → staged
    superbatch transfers → fused dispatch) — the path multi-host and
    ImageNet runs use. Comparable to the same 13.94 baseline: the
    reference's step also included its host input pipeline. Returns
    ``(steps/sec, breakdown)`` where breakdown is the measured window's
    data_wait/dispatch decomposition (tpu_resnet/obs/breakdown.py) — the
    bench line answers "was this measurement input-bound" directly."""
    import jax
    import numpy as np

    from tpu_resnet.obs import StepBreakdown

    from tpu_resnet import parallel
    from tpu_resnet.data import device_data, pipeline
    from tpu_resnet.data import cifar as cifar_data
    from tpu_resnet.data.augment import get_augment_fns
    from tpu_resnet.train.step import make_train_step

    cfg, model, sched, state, rng = _build_train_setup(
        mesh, "cifar10", resnet_size=resnet_size, batch=batch, dtype=dtype,
        image=32, synthetic=True)

    images, labels = cifar_data.synthetic_data(split, 32, 10)
    batcher = pipeline.ShardedBatcher(images, labels.astype(np.int32),
                                      batch, seed=0, process_index=0,
                                      process_count=1)
    host_iter = pipeline.BackgroundIterator(iter(batcher),
                                            capacity=2 * stage + 2)
    it = pipeline.staged_superbatch_prefetch(
        host_iter, parallel.staged_batch_sharding(mesh), stage=stage)
    augment_fn, _ = get_augment_fns("cifar10")
    run = device_data.compile_staged_stream_steps(
        make_train_step(model, cfg.optim, sched, 10, augment_fn,
                        base_rng=rng, mesh=mesh), mesh)

    try:
        for _ in range(warmup_super):
            gi, gl, k = next(it)
            state, metrics = run(state, gi, gl, 0, k)
        _fetch_sync(metrics["loss"])

        bd = StepBreakdown()
        t0 = time.perf_counter()
        measured = 0
        for _ in range(measure_super):
            with bd.data_wait():
                gi, gl, k = next(it)
            with bd.dispatch():
                state, metrics = run(state, gi, gl, 0, k)
            measured += k
        _fetch_sync(metrics["loss"])
        return measured / (time.perf_counter() - t0), bd.interval()
    finally:
        it.close()          # drop the depth-2 staged device buffers
        host_iter.close()   # release the producer thread + host split


def _train_step_flops(compiled):
    """Per-step, per-device FLOPs from XLA's compiled cost analysis (the
    post-SPMD module is per-device); None if the backend doesn't report
    them. Extraction shared with the live gauges (obs/mfu.py)."""
    from tpu_resnet.obs.mfu import program_flops

    try:
        return program_flops(compiled.cost_analysis())
    except Exception:
        return None


def _train_step_comms(compiled, mesh):
    """Bench fields from the compiled step's collective summary
    (obs/comms.py over the post-partitioner HLO): per-device
    bytes-on-wire per step (the perfwatch sweep-comm series,
    lower-is-better), collective count and — when the chip's ICI
    bandwidth is known — the predicted time-on-wire. {} if the backend
    reports no HLO; bench lines then omit the comms fields, like mfu
    without a peak."""
    from tpu_resnet.obs.comms import (comms_from_compiled, ici_bytes_per_chip,
                                      predicted_time_on_wire)

    try:
        shape = dict(mesh.shape)
        summary = comms_from_compiled(compiled, shape.get("data", 1),
                                      shape.get("model", 1))
    except Exception:
        return {}
    if summary is None:
        return {}
    out = {"comms_bytes_per_step": summary["wire_bytes_per_device"],
           "comms_collective_count": summary["collective_count"]}
    kind = mesh.devices.flat[0].device_kind
    if ici_bytes_per_chip(kind):
        out["predicted_time_on_wire_s"] = round(
            predicted_time_on_wire(summary, kind), 6)
    return out


def _measure_imagenet(mesh, warmup_steps, measure_steps, resnet_size=50,
                      batch=128, image=224, dtype="bfloat16",
                      stem_s2d=None):
    """ImageNet-shaped training step: ResNet-50 @ 224, batch 128, bf16,
    synthetic pre-processed input resident on device. Returns
    (steps/s, flops_per_step or None, comms bench fields — possibly {}).
    ``stem_s2d`` overrides model.stem_space_to_depth (None = config
    default) for the stem A/B."""
    import jax
    import numpy as np

    from tpu_resnet import parallel
    from tpu_resnet.train.step import make_train_step, shard_step

    cfg, model, sched, state, rng = _build_train_setup(
        mesh, "imagenet", resnet_size=resnet_size, batch=batch,
        dtype=dtype, image=image)
    if stem_s2d is not None and stem_s2d != cfg.model.stem_space_to_depth:
        from tpu_resnet.models import build_model
        cfg.model.stem_space_to_depth = stem_s2d
        model = build_model(cfg)  # same param tree either way

    # Pre-processed (VGG mean-subtracted) float input, as the host pipeline
    # would deliver it; one resident batch re-fed each step so the
    # measurement isolates the training step itself.
    bs = parallel.batch_sharding(mesh)
    images = jax.device_put(
        np.random.RandomState(0)
        .uniform(-114.0, 141.0, (batch, image, image, 3))
        .astype(np.float32), bs)
    labels = jax.device_put(
        np.random.RandomState(1).randint(0, 1000, batch)
        .astype(np.int32), bs)

    step_fn = shard_step(
        make_train_step(model, cfg.optim, sched, 1000, None,
                        base_rng=rng, mesh=mesh), mesh)
    # donate_state=True (the default, what train/loop.py runs): XLA may
    # update params in place instead of allocating a fresh state tree —
    # the measured step is the production configuration.
    compiled = step_fn.lower(state, images, labels).compile()
    flops = _train_step_flops(compiled)
    comms = _train_step_comms(compiled, mesh)

    for _ in range(warmup_steps):
        state, metrics = compiled(state, images, labels)
    _fetch_sync(metrics["loss"])

    t0 = time.perf_counter()
    for _ in range(measure_steps):
        state, metrics = compiled(state, images, labels)
    _fetch_sync(metrics["loss"])
    dt = time.perf_counter() - t0
    return measure_steps / dt, flops, comms


def _synthetic_photo_jpeg(size=(640, 480), quality=90, rng=None,
                          freqs=(8.0, 6.0)):
    """A photo-like test JPEG: smooth structure + mild noise compresses
    ~10:1 like real ImageNet photos. (Uniform noise — the old test image —
    is the pathological worst case: ~1.5:1, entropy-decode-bound, and made
    every decode-path optimization invisible.) Canonical implementation
    lives with the data engine (tpu_resnet/data/engine.py) so the bench,
    ``doctor --data-bench`` and tools/input_edge.py rest on the same
    entropy premise; this name is kept as the tools' import point."""
    from tpu_resnet.data.engine import synthetic_photo_jpeg

    return synthetic_photo_jpeg(size=size, quality=quality, rng=rng,
                                freqs=freqs)


def _measure_host_decode(n_images=200, size=(640, 480), engine_curve=True,
                         engine_secs=4.0):
    """Host-side JPEG decode + VGG preprocess throughput (images/s),
    native C++ (libjpeg-turbo partial decode + window resize) vs PIL, on
    the train path (random side 256-512 + random crop) and the eval path
    (side 256 + central crop) — the ImageNet input edge the reference
    bounded with 16 queue threads + num_parallel_calls=4
    (cifar_input.py:99-100, resnet_imagenet_train.py:170-171). Backend-
    independent; run per host."""
    import numpy as np

    from tpu_resnet.data.imagenet import decode_and_crop
    from tpu_resnet.native import jpeg_available

    jpeg = _synthetic_photo_jpeg(size)
    out = {"native_jpeg_built": bool(jpeg_available()),
           "jpeg_bytes": len(jpeg)}
    for label, use_native in (("native", True), ("pil", False)):
        for mode, train in (("train", True), ("eval", False)):
            d_rng = np.random.default_rng(1)
            decode_and_crop(jpeg, train, d_rng, use_native=use_native)
            t0 = time.perf_counter()
            for _ in range(n_images):
                decode_and_crop(jpeg, train, d_rng, use_native=use_native)
            rate = n_images / (time.perf_counter() - t0)
            out[f"{label}_{mode}_images_per_sec"] = round(rate, 1)
    out["native_images_per_sec"] = out["native_train_images_per_sec"]
    out["pil_images_per_sec"] = out["pil_train_images_per_sec"]
    out["native_speedup"] = round(
        out["native_images_per_sec"] / out["pil_images_per_sec"], 2)
    if engine_curve:
        # Process-engine worker-scaling curve (tpu_resnet/data/engine.py):
        # the multiprocess answer to the GIL wall this section measured
        # (round 4: a 372 img/s single-host ceiling against the chip's
        # ~3032). Same probe as `doctor --data-bench`, so a bench line
        # and an operator triage are directly comparable.
        from tpu_resnet.data.engine import decode_scaling_probe
        cpus = os.cpu_count() or 1
        out["engine_scaling"] = decode_scaling_probe(
            proc_counts=(1, min(8, cpus)), seconds=engine_secs)
    return out


def _measure_record_split(n_records=400, record_bytes=60_000):
    """CRC32C-verified TFRecord shard read throughput (MB/s), native C++
    plane vs pure-python — the tf.data C++ reader role (SURVEY.md §2.4).
    Verified reads are the native plane's headline win (~200x measured);
    plain framing reads are memcpy-bound either way and reported too."""
    import os
    import tempfile

    import numpy as np

    from tpu_resnet.data import tfrecord
    from tpu_resnet.data.imagenet import read_shard_records

    rng = np.random.default_rng(0)
    payload = [rng.integers(0, 256, record_bytes, dtype=np.uint8).tobytes()
               for _ in range(8)]
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "shard")
        tfrecord.write_records(
            path, [payload[i % 8] for i in range(n_records)])
        from tpu_resnet.native import available

        mb = os.path.getsize(path) / 1e6
        # Label honesty: without the built library the "native" cases
        # silently measure the python fallback.
        out = {"native_built": bool(available())}
        cases = (
            ("native_crc", lambda: read_shard_records(path, use_native=True,
                                                      verify_crc=True)),
            ("python_crc", lambda: tfrecord.read_records(path,
                                                         verify_crc=True)),
            ("native_plain", lambda: read_shard_records(path,
                                                        use_native=True)),
            ("python_plain", lambda: tfrecord.read_records(path)),
        )
        for label, fn in cases:
            sum(len(r) for r in fn())  # warm page cache
            t0 = time.perf_counter()
            n = sum(1 for _ in fn())
            dt = time.perf_counter() - t0
            assert n == n_records
            out[f"{label}_mb_per_sec"] = round(mb / dt, 1)
        out["native_crc_speedup"] = round(
            out["native_crc_mb_per_sec"] / out["python_crc_mb_per_sec"], 1)
        return out


def _measure_pallas_ab(iters=200):
    """A/B the Pallas fused softmax-xent (fwd+bwd) against the XLA/optax
    chain at b128x10 and b128x1000 (VERDICT round 1 item 6).

    The ``iters`` grad evaluations are fused into ONE dispatch with
    ``lax.scan`` (each iteration's input is perturbed by the running
    accumulator so XLA can neither hoist the loop-invariant computation
    nor overlap iterations) — per-dispatch command latency would otherwise
    swamp a ~µs kernel."""
    import jax
    import jax.numpy as jnp

    from tpu_resnet.ops import softmax_xent_mean
    from tpu_resnet.train.step import softmax_xent

    out = {}
    for classes in (10, 1000):
        rng = jax.random.PRNGKey(classes)
        logits = jax.random.normal(rng, (128, classes), jnp.float32)
        labels = jax.random.randint(jax.random.PRNGKey(1), (128,), 0, classes)

        def time_fn(fn):
            g = jax.grad(fn)

            @jax.jit
            def many(x):
                def body(acc, _):
                    dx = g(x + acc * 1e-30)  # accumulator-dependent input
                    return acc + jnp.sum(dx), None

                acc, _ = jax.lax.scan(body, jnp.float32(0.0), None,
                                      length=iters)
                return acc

            _fetch_sync(many(logits))  # compile + warm
            t0 = time.perf_counter()
            _fetch_sync(many(logits))
            return (time.perf_counter() - t0) / iters * 1e6  # us

        pallas_us = time_fn(lambda x: softmax_xent_mean(x, labels))
        xla_us = time_fn(lambda x: softmax_xent(x, labels, classes))
        out[f"b128x{classes}"] = {
            "pallas_us": round(pallas_us, 2), "xla_us": round(xla_us, 2),
            "speedup": round(xla_us / pallas_us, 3)}
    return out


def run_sections(mesh, devices):
    """Every measurement section on the ambient TPU backend. Returns
    ``(result, errors)``: a section that raises lands in ``errors`` under
    its result key and the remaining sections still run."""
    import jax

    kinds = devices[0].device_kind
    result = {"backend": jax.default_backend(), "device_kind": kinds,
              "n_devices": len(devices)}
    errors = {}

    def section(name, fn):
        try:
            result[name] = fn()
            print(f"[bench] {name}: {result[name]}", file=sys.stderr)
        except Exception as e:  # noqa: BLE001 - reported, and fails the run
            errors[name] = f"{type(e).__name__}: {e}"[:500]
            print(f"[bench] {name} FAILED: {errors[name]}", file=sys.stderr)

    def cifar():
        # The HEADLINE stays at steps_per_call=10 (comparable across
        # rounds); k=50 is reported alongside to show what more dispatch
        # fusion buys. Both plans share one setup.
        bd = {}
        by_k = _measure_cifar(mesh, [(10, 4, 30), (50, 2, 5)],
                              breakdown_out=bd)
        return {"steps_per_sec": round(by_k[10], 2), "steps_per_call": 10,
                "by_steps_per_call": {k: round(v, 2)
                                      for k, v in by_k.items()}, **bd}

    def cifar_streaming():
        sps, bd = _measure_cifar_streaming(mesh, warmup_super=2,
                                           measure_super=12)
        return {"steps_per_sec": round(sps, 2),
                "vs_baseline": round(sps / BASELINE_CIFAR_SPS, 2), **bd}

    def imagenet_entry(sps, flops, batch):
        """steps/s + images/s + MFU from per-device FLOPs (XLA cost
        analysis, analytic ResNet-50 estimate as fallback)."""
        entry = {"value": round(sps, 3), "unit": "steps/sec",
                 "images_per_sec": round(sps * batch, 1)}
        if flops:
            entry["flops_per_step_per_device"] = flops
            entry["flops_source"] = "xla_cost_analysis"
        else:
            # Analytic: ResNet-50@224 fwd ~= 4.09 GF/img; train ~= 3x;
            # normalized per device like the cost-analysis branch.
            entry["flops_per_step_per_device"] = (
                3 * 4.09e9 * batch / len(devices))
            entry["flops_source"] = "analytic"
        peak = _peak_flops(kinds)
        if peak:
            # peak is per chip, flops are per device → MFU per chip.
            entry["mfu"] = round(
                entry["flops_per_step_per_device"] * sps / peak, 4)
            entry["peak_flops_assumed_per_chip"] = peak
        # HBM twin: peak device memory of the measurement just run vs
        # capacity — a knob that "wins" MFU by blowing the memory budget
        # shows it here (and perfwatch gates on it).
        entry.update(_hbm_snapshot(kinds))
        return entry

    def imagenet():
        sps, flops, comms = _measure_imagenet(mesh, warmup_steps=5,
                                              measure_steps=30)
        entry = imagenet_entry(sps, flops, 128)
        entry.update(comms)
        entry["metric"] = "imagenet_resnet50_train_steps_per_sec_b128"
        entry["vs_baseline"] = round(sps / BASELINE_IMAGENET_SPS, 2)
        return entry

    def imagenet_b256():
        # The b128 line stays the baseline-comparable headline; this one
        # shows how utilization scales with bigger MXU tiles.
        sps, flops, comms = _measure_imagenet(mesh, warmup_steps=3,
                                              measure_steps=15, batch=256)
        return {**imagenet_entry(sps, flops, 256), **comms}

    def imagenet_stem_ab():
        # The space-to-depth stem (default ON, exact-equivalent math) vs
        # the plain 7x7/2 form at the headline batch.
        sps_plain, _, _ = _measure_imagenet(mesh, warmup_steps=3,
                                            measure_steps=15,
                                            stem_s2d=False)
        base = result.get("imagenet", {}).get("value")
        return {"plain_stem_steps_per_sec": round(sps_plain, 3),
                "s2d_stem_steps_per_sec": base,
                "s2d_speedup": round(base / sps_plain, 3) if base else None}

    def wrn28_10_cifar100():
        # BASELINE.json config 4: the reference's wide-variant exercise,
        # no published speed line (our absolute number, for tracking).
        wrn = _measure_cifar(mesh, [(10, 2, 10)],
                             preset="wrn28_10_cifar100", batch=128)
        return {"steps_per_sec": round(wrn[10], 2),
                "images_per_sec": round(wrn[10] * 128, 1)}

    for name, fn in (("cifar", cifar),
                     ("cifar_streaming", cifar_streaming),
                     ("imagenet", imagenet),
                     ("imagenet_b256", imagenet_b256),
                     ("imagenet_stem_ab", imagenet_stem_ab),
                     ("wrn28_10_cifar100", wrn28_10_cifar100),
                     ("pallas_xent_ab", _measure_pallas_ab),
                     ("host_decode", _measure_host_decode),
                     ("record_split", _measure_record_split)):
        section(name, fn)
    return result, errors


def main():
    from tpu_resnet.hostenv import enable_compile_cache
    enable_compile_cache()
    import jax

    from tpu_resnet import parallel

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU found — JAX reports platform="
              f"{devices[0].platform} ({devices[0].device_kind}, "
              f"{len(devices)} device(s)); nothing was measured",
              file=sys.stderr)
        return 1
    result, errors = run_sections(parallel.create_mesh(None), devices)
    result["captured_at"] = time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                          time.gmtime())
    cifar = result.pop("cifar", {})
    sps = cifar.get("steps_per_sec")
    line = {"metric": HEADLINE_METRIC, "value": sps, "unit": "steps/sec",
            "vs_baseline": (round(sps / BASELINE_CIFAR_SPS, 2)
                            if sps else None),
            **result}
    if cifar:
        line["cifar_detail"] = cifar
    if errors:
        line["errors"] = errors
    print(json.dumps(line), flush=True)
    return 1 if errors else 0


if __name__ == "__main__":
    if len(sys.argv) >= 2 and sys.argv[1] == "--sweep":
        # Per-knob sweep (tpu_resnet/tools/sweep.py): a jax-free parent
        # that runs one budgeted measurement child per point, one after
        # another.
        from tpu_resnet.tools.sweep import main as sweep_main
        sys.exit(sweep_main(sys.argv[2:]))
    sys.exit(main())
