"""Data-layer tests: CIFAR binary parsing against hand-built fixtures
(format per reference cifar_input.py:39-68), sharded batching, augmentation
semantics (cifar_input.py:70-79)."""

import numpy as np
import jax
import pytest

from tpu_resnet.data import augment, cifar, pipeline


# ---------------------------------------------------------------- fixtures
def write_cifar10_fixture(tmp_path, n_per_file=20):
    d = tmp_path / "cifar-10-batches-bin"
    d.mkdir()
    rng = np.random.default_rng(0)
    all_images, all_labels = [], []
    for name in [f"data_batch_{i}.bin" for i in range(1, 6)] + ["test_batch.bin"]:
        labels = rng.integers(0, 10, n_per_file, dtype=np.uint8)
        images = rng.integers(0, 256, (n_per_file, 3, 32, 32), dtype=np.uint8)
        records = np.concatenate(
            [labels[:, None], images.reshape(n_per_file, -1)], axis=1)
        (d / name).write_bytes(records.tobytes())
        if name != "test_batch.bin":
            all_images.append(images)
            all_labels.append(labels)
    return (np.concatenate(all_images).transpose(0, 2, 3, 1),
            np.concatenate(all_labels).astype(np.int32))


def test_cifar10_parse_roundtrip(tmp_path):
    want_images, want_labels = write_cifar10_fixture(tmp_path)
    images, labels = cifar.load_cifar("cifar10", str(tmp_path), train=True)
    assert images.shape == (100, 32, 32, 3)
    np.testing.assert_array_equal(images, want_images)
    np.testing.assert_array_equal(labels, want_labels)


def test_cifar100_fine_label_offset(tmp_path):
    # cifar100 records: [coarse, fine, 3072 bytes]; reference reads the fine
    # label via label_offset=1 (cifar_input.py:44-47).
    d = tmp_path / "cifar-100-binary"
    d.mkdir()
    n = 10
    rng = np.random.default_rng(1)
    coarse = rng.integers(0, 20, n, dtype=np.uint8)
    fine = rng.integers(0, 100, n, dtype=np.uint8)
    images = rng.integers(0, 256, (n, 3072), dtype=np.uint8)
    rec = np.concatenate([coarse[:, None], fine[:, None], images], axis=1)
    (d / "train.bin").write_bytes(rec.tobytes())
    (d / "test.bin").write_bytes(rec.tobytes())
    _, labels = cifar.load_cifar("cifar100", str(tmp_path), train=True)
    np.testing.assert_array_equal(labels, fine.astype(np.int32))


def test_missing_files_raise(tmp_path):
    with pytest.raises(FileNotFoundError):
        cifar.load_cifar("cifar10", str(tmp_path), train=True)


def test_synthetic_freq100_task():
    """The hard convergence task: 100 classes, signal present, label noise
    train-only and at the requested fraction."""
    import numpy as np

    imgs, labels = cifar.synthetic_data(256, 32, 100, seed=3,
                                        learnable=True, task="freq100")
    assert labels.min() >= 0 and labels.max() <= 99
    # determinism
    imgs2, labels2 = cifar.synthetic_data(256, 32, 100, seed=3,
                                          learnable=True, task="freq100")
    assert np.array_equal(imgs, imgs2) and np.array_equal(labels, labels2)
    # the sinusoid signal must be recoverable: the per-row mean of an
    # image carries its vertical frequency above the noise floor
    i = 0
    fy = labels[i] // 10
    rows = imgs[i].astype(np.float64).mean(axis=(1, 2))
    spec = np.abs(np.fft.rfft(rows - rows.mean()))
    assert np.argmax(spec[1:]) + 1 == fy + 1

    # label noise: ~frac of labels resampled, images unchanged
    _, noisy = cifar.synthetic_data(256, 32, 100, seed=3, learnable=True,
                                    task="freq100", label_noise=0.25)
    frac = (noisy != labels).mean()
    assert 0.1 < frac < 0.3  # 0.25 requested; resamples can collide


def test_synthetic_unknown_task_rejected():
    import pytest

    with pytest.raises(ValueError, match="unknown synthetic task"):
        cifar.synthetic_data(8, 32, 10, learnable=True, task="nope")


def test_synthetic_deterministic():
    a = cifar.synthetic_data(16, 32, 10, seed=3)
    b = cifar.synthetic_data(16, 32, 10, seed=3)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])


# ---------------------------------------------------------------- batching
def test_sharded_batcher_epoch_coverage():
    images = np.arange(40, dtype=np.uint8).reshape(40, 1, 1, 1)
    labels = np.arange(40, dtype=np.int32)
    b = pipeline.ShardedBatcher(images, labels, local_batch=8, seed=0,
                                process_index=0, process_count=1)
    seen = []
    it = iter(b)
    for _ in range(5):  # one epoch
        _, lab = next(it)
        seen.extend(lab.tolist())
    assert sorted(seen) == list(range(40))


def test_sharded_batcher_process_disjoint():
    images = np.zeros((40, 1, 1, 1), np.uint8)
    labels = np.arange(40, dtype=np.int32)
    got = []
    for pi in range(4):
        b = pipeline.ShardedBatcher(images, labels, local_batch=10, seed=0,
                                    shuffle=False, process_index=pi,
                                    process_count=4)
        _, lab = next(iter(b))
        got.append(set(lab.tolist()))
    # 4 processes own disjoint stripes covering all records
    assert set.union(*got) == set(range(40))
    for i in range(4):
        for j in range(i + 1, 4):
            assert not (got[i] & got[j])


def test_batcher_deterministic_across_restarts():
    images = np.zeros((64, 1, 1, 1), np.uint8)
    labels = np.arange(64, dtype=np.int32)
    runs = []
    for _ in range(2):
        b = iter(pipeline.ShardedBatcher(images, labels, 16, seed=7,
                                         process_index=0, process_count=1))
        runs.append([next(b)[1].tolist() for _ in range(8)])
    assert runs[0] == runs[1]


def test_batcher_start_step_fast_forward():
    """Resume contract: a batcher started at step k yields exactly what an
    uninterrupted run yields from its (k+1)-th batch on."""
    images = np.zeros((64, 1, 1, 1), np.uint8)
    labels = np.arange(64, dtype=np.int32)
    full = iter(pipeline.ShardedBatcher(images, labels, 16, seed=7,
                                        process_index=0, process_count=1))
    stream = [next(full)[1].tolist() for _ in range(12)]
    resumed = iter(pipeline.ShardedBatcher(images, labels, 16, seed=7,
                                           process_index=0, process_count=1,
                                           start_step=5))
    resumed_stream = [next(resumed)[1].tolist() for _ in range(7)]
    assert resumed_stream == stream[5:]


def test_eval_batches_padding():
    images = np.zeros((25, 2, 2, 3), np.uint8)
    labels = np.arange(25, dtype=np.int32)
    batches = list(pipeline.eval_batches(images, labels, 10))
    assert len(batches) == 3
    assert batches[-1][0].shape[0] == 10
    assert (batches[-1][1][5:] == -1).all()  # padded slots marked invalid
    total_valid = sum((lab >= 0).sum() for _, lab in batches)
    assert total_valid == 25


def test_background_iterator_propagates_errors():
    def gen():
        yield 1
        raise RuntimeError("boom")

    it = pipeline.BackgroundIterator(gen())
    assert next(it) == 1
    with pytest.raises(RuntimeError):
        next(it)


def test_background_iterator_producer_death_raises_not_hangs(monkeypatch):
    """A producer thread that dies without enqueueing its error (here:
    SystemExit, which the error path deliberately doesn't catch) must
    surface as a loud error at the consumer, not block get() forever."""
    import time

    monkeypatch.setattr(pipeline, "GET_POLL_SEC", 0.05)

    def gen():
        yield 1
        raise SystemExit  # kills the thread outside the Exception path

    it = pipeline.BackgroundIterator(gen(), capacity=2)
    assert next(it) == 1
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="producer thread died"):
        next(it)
    assert time.monotonic() - t0 < 10
    assert not it._thread.is_alive()


def test_background_iterator_error_path_full_queue_no_deadlock(monkeypatch):
    """Loader error with the queue full and the consumer not draining:
    the old put(e) blocked forever; the producer must instead free a slot
    (drain) and deliver the exception."""
    monkeypatch.setattr(pipeline, "ERROR_PUT_TIMEOUT_SEC", 0.1)

    def gen():
        yield "only"
        raise ValueError("boom")

    it = pipeline.BackgroundIterator(gen(), capacity=1)
    # don't consume anything: the queue is full when the error fires
    it._thread.join(timeout=10)
    assert not it._thread.is_alive(), "producer deadlocked on its error"
    with pytest.raises(ValueError, match="boom"):
        next(it)  # buffered item was dropped in favor of the error


def test_background_iterator_external_stop_unblocks_consumer(monkeypatch):
    """The preemption hook: with the producer stalled (alive but not
    yielding), setting the external stop event must end iteration at the
    consumer within ~one poll cycle — a preempted trainer blocked in
    next(data_iter) can still save its final checkpoint in the grace
    window."""
    import threading
    import time

    monkeypatch.setattr(pipeline, "GET_POLL_SEC", 0.05)
    stall = threading.Event()

    def gen():
        yield 1
        stall.wait(30)  # a dead data source, as far as the consumer knows
        yield 2

    stop = threading.Event()
    it = pipeline.BackgroundIterator(gen(), capacity=2, external_stop=stop)
    assert next(it) == 1
    threading.Timer(0.1, stop.set).start()
    t0 = time.monotonic()
    with pytest.raises(StopIteration):
        next(it)
    assert time.monotonic() - t0 < 5  # unblocked by the event, not data
    stall.set()  # release the producer thread


# -------------------------------------------------------------- augmentation
def test_per_image_standardization_matches_tf_semantics():
    rng = np.random.default_rng(0)
    imgs = rng.uniform(0, 255, (4, 32, 32, 3)).astype(np.float32)
    out = np.asarray(augment.per_image_standardization(imgs))
    for i in range(4):
        np.testing.assert_allclose(out[i].mean(), 0.0, atol=1e-4)
        np.testing.assert_allclose(out[i].std(), 1.0, atol=1e-3)
    # constant image: adjusted_stddev = 1/sqrt(N) floor, no NaN/Inf
    const = np.full((1, 32, 32, 3), 7.0, np.float32)
    out = np.asarray(augment.per_image_standardization(const))
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out, 0.0, atol=1e-5)


def test_cifar_train_augment_shapes_and_determinism():
    rng = np.random.default_rng(0)
    imgs = rng.integers(0, 256, (8, 32, 32, 3), dtype=np.uint8)
    key = jax.random.PRNGKey(0)
    a = np.asarray(augment.cifar_train_augment(key, imgs))
    b = np.asarray(augment.cifar_train_augment(key, imgs))
    assert a.shape == (8, 32, 32, 3)
    np.testing.assert_array_equal(a, b)  # same key → same augmentation
    c = np.asarray(augment.cifar_train_augment(jax.random.PRNGKey(1), imgs))
    assert not np.allclose(a, c)  # different key → different crops/flips


def _numpy_crop(key, images, pad):
    """The crop as a plain loop: offsets from the same split keys, then
    ``padded[i, oh:oh+h, ow:ow+w]`` image by image."""
    b, h, w, _ = images.shape
    key_h, key_w = jax.random.split(key)
    off_h = np.asarray(jax.random.randint(key_h, (b,), 0, 2 * pad + 1))
    off_w = np.asarray(jax.random.randint(key_w, (b,), 0, 2 * pad + 1))
    padded = np.pad(images, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    want = np.stack([padded[i, oh:oh + h, ow:ow + w]
                     for i, (oh, ow) in enumerate(zip(off_h, off_w))])
    return want, off_h, off_w


@pytest.mark.parametrize("shape", [(8, 32, 32, 3), (5, 12, 20, 3),
                                   (1, 32, 32, 3)],
                         ids=["square", "nonsquare", "batch1"])
@pytest.mark.parametrize("dtype", [np.uint8, np.float32])
@pytest.mark.parametrize("pad", [1, 2, 4])
def test_random_crop_batch_matches_numpy_loop(pad, dtype, shape):
    imgs = np.random.default_rng(pad).integers(
        0, 256, shape).astype(dtype)
    for seed in (0, 7):
        key = jax.random.PRNGKey(seed)
        got = augment._random_crop_batch(key, imgs, pad)
        want, _, _ = _numpy_crop(key, imgs, pad)
        assert got.dtype == dtype and got.shape == shape
        np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("pad", [1, 2, 4])
def test_random_crop_batch_hits_every_offset_pair(pad):
    n = (2 * pad + 1) ** 2
    b, h, w = 40 * n, 6, 5
    # every pixel of an image distinct and non-zero: a wrong shift on
    # either axis, or padding in the wrong place, cannot pass
    imgs = (1 + np.arange(h * w * 2, dtype=np.float32)).reshape(1, h, w, 2)
    imgs = imgs + 100.0 * np.arange(b, dtype=np.float32)[:, None, None, None]
    key = jax.random.PRNGKey(3)
    want, off_h, off_w = _numpy_crop(key, imgs, pad)
    assert len(set(zip(off_h.tolist(), off_w.tolist()))) == n
    got = np.asarray(augment._random_crop_batch(key, imgs, pad))
    np.testing.assert_array_equal(got, want)


def _primitives(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub)


def test_cifar_train_augment_has_no_per_image_op():
    imgs = np.zeros((16, 32, 32, 3), np.uint8)
    jaxpr = jax.make_jaxpr(augment.cifar_train_augment)(
        jax.random.PRNGKey(0), imgs)
    found = set(_primitives(jaxpr.jaxpr))
    assert "select_n" in found  # the walk reaches the crop
    assert not found & {"gather", "dynamic_slice", "dynamic_update_slice",
                        "scan", "while"}


@pytest.mark.parametrize("how", ["jit_sharded", "shard_map_folded_key"])
def test_cifar_train_augment_shards_over_data_without_collectives(how):
    """Batch split over ``data`` (auto-sharded jit) and the per-replica
    key folding of ``train_step`` under ``shard_map``: the rows the
    unsharded function gives, and nothing crosses devices."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from tpu_resnet.config import load_config
    from tpu_resnet.obs.comms import extract_collectives
    from tpu_resnet.parallel import create_mesh

    mesh = create_mesh(load_config("smoke").mesh, devices=jax.devices()[:8])
    imgs = np.random.default_rng(0).integers(
        0, 256, (32, 32, 32, 3), dtype=np.uint8)
    key = jax.random.PRNGKey(5)
    if how == "jit_sharded":
        fn = jax.jit(augment.cifar_train_augment,
                     in_shardings=(NamedSharding(mesh, P()),
                                   NamedSharding(mesh, P("data"))),
                     out_shardings=NamedSharding(mesh, P("data")))
        want = np.asarray(jax.jit(augment.cifar_train_augment)(key, imgs))
    else:
        def body(rng, images):
            rng = jax.random.fold_in(rng, jax.lax.axis_index("data"))
            return augment.cifar_train_augment(rng, images)

        fn = jax.jit(jax.shard_map(body, mesh=mesh,
                                   in_specs=(P(), P("data")),
                                   out_specs=P("data"), check_vma=False))
        want = np.concatenate([
            np.asarray(jax.jit(augment.cifar_train_augment)(
                jax.random.fold_in(key, i), shard))
            for i, shard in enumerate(np.split(imgs, 8))])
    np.testing.assert_array_equal(np.asarray(fn(key, imgs)), want)
    hlo = fn.lower(key, imgs).compile().as_text()
    assert extract_collectives(hlo, data_axis=8, model_axis=1) == []


def test_imagenet_mean_subtraction():
    imgs = np.full((2, 8, 8, 3), 255, np.uint8)
    out = np.asarray(augment.imagenet_eval_preprocess(imgs))
    want = 1.0 - np.asarray(augment.VGG_MEANS_01)
    np.testing.assert_allclose(out[0, 0, 0], want, rtol=1e-5)


def test_staged_device_prefetch_matches_unstaged():
    """Staged (k batches per transfer) must yield the exact same stream as
    per-batch transfers, including a partial final stage."""
    import jax

    from tpu_resnet.parallel import (batch_sharding, create_mesh,
                                     staged_batch_sharding)
    from tpu_resnet.config import load_config

    mesh = create_mesh(load_config("smoke").mesh, devices=jax.devices()[:8])
    rng = np.random.default_rng(0)
    n_batches, B = 11, 16  # 11 batches, stage=4 -> stages of 4,4,3
    batches = [(rng.integers(0, 255, (B, 8, 8, 3)).astype(np.uint8),
                rng.integers(0, 10, B).astype(np.int32))
               for _ in range(n_batches)]

    plain = list(pipeline.device_prefetch(iter(batches),
                                          batch_sharding(mesh)))
    staged = list(pipeline.staged_device_prefetch(
        iter(batches), staged_batch_sharding(mesh), stage=4))
    assert len(plain) == len(staged) == n_batches
    for (pi, pl), (si, sl) in zip(plain, staged):
        np.testing.assert_array_equal(np.asarray(pi), np.asarray(si))
        np.testing.assert_array_equal(np.asarray(pl), np.asarray(sl))


def _h2d_setup(n_batches=11, B=16, hw=8):
    from tpu_resnet.config import load_config
    from tpu_resnet.parallel import create_mesh, staged_batch_sharding

    mesh = create_mesh(load_config("smoke").mesh, devices=jax.devices()[:8])
    rng = np.random.default_rng(0)
    batches = [(rng.integers(0, 255, (B, hw, hw, 3)).astype(np.uint8),
                rng.integers(0, 10, B).astype(np.int32))
               for _ in range(n_batches)]
    return batches, staged_batch_sharding(mesh)


def test_double_buffered_h2d_matches_generator_form():
    """The double-buffered path must yield byte-identical superbatches to
    staged_superbatch_prefetch — including the partial final stage — so
    staged-vs-unstaged loss bit-equality carries over unchanged."""
    batches, sharding = _h2d_setup()
    ref = list(pipeline.staged_superbatch_prefetch(iter(batches), sharding,
                                                   stage=4))
    db = pipeline.DoubleBufferedH2D(iter(batches), sharding, stage=4)
    got = list(db)
    db.close()
    assert [k for _, _, k in ref] == [k for _, _, k in got] == [4, 4, 3]
    for (gi, gl, _), (hi, hl, _) in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(gi), np.asarray(hi))
        np.testing.assert_array_equal(np.asarray(gl), np.asarray(hl))


def test_double_buffered_h2d_two_slot_bound():
    """The producer must never run ahead of the two-slot device buffer:
    with an unconsumed ready slot, at most one further transfer lands
    (that's the staging-HBM cap 'donated between stages' relies on)."""
    import time as time_mod

    batches, sharding = _h2d_setup(n_batches=12)
    db = pipeline.DoubleBufferedH2D(iter(batches), sharding, stage=2,
                                    depth=2)
    try:
        deadline = time_mod.time() + 5
        while len(db.drain_transfers()) < 2 and time_mod.time() < deadline:
            time_mod.sleep(0.02)  # let it fill both slots
        time_mod.sleep(0.3)       # ample time to (wrongly) run ahead
        assert len(db.drain_transfers()) == 0  # blocked at two slots
    finally:
        db.close()


def test_double_buffered_h2d_stats_and_events():
    batches, sharding = _h2d_setup(n_batches=8)
    db = pipeline.DoubleBufferedH2D(iter(batches), sharding, stage=4)
    consumed = list(db)
    stats = db.stats()
    events = db.drain_transfers()
    db.close()
    assert len(consumed) == 2 and len(events) == 2
    expect = sum(im.nbytes + lb.nbytes for im, lb in batches)
    assert sum(e[2] for e in events) == expect
    assert all(e[1] >= e[0] for e in events)
    assert stats["h2d_bytes_per_sec"] > 0
    assert 0.0 <= stats["h2d_overlap_frac"] <= 1.0
    # interval semantics: a drained window reads zero
    assert db.stats()["h2d_bytes_per_sec"] == 0.0


def test_double_buffered_h2d_propagates_errors_in_order():
    batches, sharding = _h2d_setup(n_batches=3)

    def stream():
        yield batches[0]
        yield batches[1]
        raise RuntimeError("shard went away")

    db = pipeline.DoubleBufferedH2D(stream(), sharding, stage=2)
    try:
        gi, gl, k = next(db)  # the complete first stage arrives
        assert k == 2
        with pytest.raises(RuntimeError, match="shard went away"):
            next(db)
    finally:
        db.close()


def test_double_buffered_h2d_external_stop_unblocks(monkeypatch):
    import threading

    monkeypatch.setattr(pipeline, "GET_POLL_SEC", 0.05)
    _, sharding = _h2d_setup(n_batches=1)
    stall = threading.Event()
    stop = threading.Event()

    def stalled():
        stall.wait(30)
        return iter(())

    def stream():
        yield from stalled()

    db = pipeline.DoubleBufferedH2D(stream(), sharding, stage=2,
                                    external_stop=stop)
    try:
        stop.set()
        with pytest.raises(StopIteration):
            next(db)
    finally:
        stall.set()
        db.close()
