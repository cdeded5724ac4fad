"""Unified compiled-program registry (tpu_resnet/programs).

Three layers:

- **key parity**: one spelling source — ``obs.mfu.train_program_key``,
  ``ops.autotune.shape_key``, the memory ledger and the config-matrix
  coverage map must all derive from ``programs.spell*`` (no drift);
- **executable cache**: round-trip, precondition fast path,
  fingerprint verification, version-mismatch eviction, corrupt-entry
  recovery, the once-per-process deserialization guard (the PR 1
  double-deserialization hazard, regression-locked) and the env
  kill-switch;
- **integration**: the train loop's warm restart reuses cached
  programs value-identically, and serve warms buckets smallest-first
  with per-bucket ``cache_hit`` spans.
"""

import json
import os

import numpy as np
import pytest

from tpu_resnet import programs
from tpu_resnet.config import load_config
from tpu_resnet.programs import registry as registry_mod
from tpu_resnet.programs.registry import ExecutableCache, ProgramRegistry


def _cache_cfg(tmp_path, **overrides):
    cfg = load_config("smoke")
    cfg.programs.cache = "on"
    cfg.programs.cache_dir = str(tmp_path / "progcache")
    for k, v in overrides.items():
        section, field = k.split(".")
        setattr(getattr(cfg, section), field, v)
    return cfg


def _fresh_process():
    """Simulate a process restart for the cache: drop the
    once-per-process deserialization ledger (each real process starts
    with it empty)."""
    registry_mod._loaded_once.clear()


# ------------------------------------------------------------- key parity
def test_spell_is_the_one_source_for_flops_and_memory_keys():
    from tpu_resnet.obs import mfu

    for preset, mesh in (("cifar10", {"data": 8, "model": 1}),
                         ("smoke", {"data": 1, "model": 1}),
                         ("wrn28_10_cifar100", {"data": 4, "model": 2})):
        cfg = load_config(preset)
        assert mfu.train_program_key(cfg, mesh) == \
            programs.spell(cfg, mesh)
    cfg = load_config("cifar10")
    cfg.model.compute_dtype = "bfloat16"
    assert programs.spell(cfg, {"data": 8, "model": 1}) == \
        "train|cifar10_rn50_bf16|mesh8x1|b128"
    cfg.mesh.partition = "zero1"
    assert programs.spell(cfg, {"data": 8}) == \
        "train|cifar10_rn50_bf16_zero1|mesh8x1|b128"


def test_spell_shape_is_the_autotune_key():
    from tpu_resnet.ops import autotune

    assert autotune.shape_key(128, 1000) == \
        programs.spell_shape(128, 1000) == "128x1000"


def test_spell_distinguishes_program_changing_dimensions():
    """Every config dimension that changes the traced program must
    change the key (one key = one program — the coverage check's
    invariant), and the deliberately-keyless dimension (data.engine)
    must not."""
    base = load_config("cifar10")
    key = programs.spell(base, {"data": 8})
    # per-replica BN (shard_map dispatch) is a different program
    pr = load_config("cifar10")
    pr.model.sync_bn = False
    assert programs.spell(pr, {"data": 8}) != key
    assert "_pr" in programs.spell(pr, {"data": 8})
    # ...but only on a multi-chip data axis (mesh1 per-replica == sync)
    assert programs.spell(pr, {"data": 1}) == \
        programs.spell(base, {"data": 1})
    # forced fused epilogue
    ep = load_config("cifar10")
    ep.model.fused_epilogue = "on"
    assert programs.spell(ep, {"data": 8}) != key
    # ImageNet stem variant
    imagenet = load_config("imagenet")
    plain = load_config("imagenet")
    plain.model.stem_space_to_depth = False
    assert programs.spell(imagenet, {}) != programs.spell(plain, {})
    # synthetic head size
    smoke = load_config("smoke")
    smoke100 = load_config("smoke")
    smoke100.data.synthetic_classes = 100
    assert programs.spell(smoke, {}) != programs.spell(smoke100, {})
    assert "synthetic100" in programs.spell(smoke100, {})
    # data.engine is deliberately NOT in the key (engine-invariance)
    proc = load_config("cifar10")
    proc.data.engine = "process"
    assert programs.spell(proc, {"data": 8}) == key


def test_spell_entry_covers_every_traced_matrix_row():
    from tpu_resnet.analysis.configmatrix import MATRIX

    keys = {}
    for entry in MATRIX:
        if entry.expect_error is not None or entry.builder == "ctor-bn-axis":
            continue
        key = programs.spell_entry(entry)
        assert key.split("|")[0] in ("train", "chunk", "serve")
        keys.setdefault(key, []).append(entry.name)
    # the only entries allowed to share a key are declared-identical
    # program twins (same_program_as)
    twins = {e.name: e.same_program_as for e in MATRIX if e.same_program_as}
    for key, names in keys.items():
        if len(names) > 1:
            assert any(twins.get(n) in names for n in names), \
                f"key {key} shared by non-twin entries {names}"


def test_registry_coverage_flags_key_collisions(monkeypatch, tmp_path):
    """Two matrix entries tracing DIFFERENT programs under one key is
    the wrong-executable incident class — verify_matrix must flag it."""
    from tpu_resnet.analysis import configmatrix
    from tpu_resnet.analysis.configmatrix import MATRIX

    entries = tuple(e for e in MATRIX
                    if e.name in ("cifar10_rn8_f32",
                                  "cifar10_rn8_f32_remat"))
    assert len(entries) == 2
    golden = str(tmp_path / "golden.json")
    findings, _ = configmatrix.verify_matrix(
        entries=entries, update_golden=True, golden_path=golden)
    assert not [f for f in findings if f.rule == "registry-coverage"]

    # collapse the spelling: both entries now share a key
    import tpu_resnet.programs as programs_pkg

    real = programs_pkg.spell_entry
    monkeypatch.setattr(programs_pkg, "spell_entry",
                        lambda e: real(e).replace("_remat", ""))
    findings, _ = configmatrix.verify_matrix(
        entries=entries, update_golden=True, golden_path=golden)
    collisions = [f for f in findings if f.rule == "registry-coverage"]
    assert collisions and "collision" in collisions[0].message


# -------------------------------------------------------- executable cache
def _toy_program(scale=2.0):
    import jax

    return jax.jit(lambda x: x * scale)


def _toy_avals():
    import jax

    return (jax.ShapeDtypeStruct((4,), "float32"),)


def test_cache_round_trip_and_fast_path(tmp_path):
    cfg = _cache_cfg(tmp_path)
    reg = ProgramRegistry(cfg)
    program, hit = reg.wrap("train|toy|mesh1x1|b4", _toy_program(),
                            _toy_avals())
    assert not hit and reg.misses == 1
    out_cold = np.asarray(program(np.ones((4,), np.float32)))
    files = os.listdir(cfg.programs.cache_dir)
    assert len(files) == 1 and files[0].endswith(".aotx")

    _fresh_process()
    reg2 = ProgramRegistry(cfg)
    program2, hit2 = reg2.wrap("train|toy|mesh1x1|b4", _toy_program(),
                               _toy_avals())
    assert hit2 and reg2.hits == 1 and reg2.misses == 0
    np.testing.assert_array_equal(
        out_cold, np.asarray(program2(np.ones((4,), np.float32))))


def test_cache_loads_onto_the_devices_it_was_compiled_for(tmp_path):
    """A program compiled for some of the host's devices (here 2 of the
    8; a one-chip replica on a four-chip host is the same case) records
    them, and a load hands exactly those back — jax would otherwise
    default to every device of the backend and the loaded executable
    would reject its arguments ("expected 8 shards")."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()[2:4]
    sharding = NamedSharding(Mesh(np.array(devs), ("data",)), P("data"))
    avals = (jax.ShapeDtypeStruct((4,), "float32", sharding=sharding),)
    key = "train|toy|mesh2x1|b4"
    cfg = _cache_cfg(tmp_path)
    reg = ProgramRegistry(cfg)
    reg.wrap(key, _toy_program(), avals)
    (entry,) = os.listdir(cfg.programs.cache_dir)
    header = reg.cache.read_header(
        os.path.join(cfg.programs.cache_dir, entry))
    assert header["device_ids"] == [d.id for d in devs]

    _fresh_process()
    program, hit = ProgramRegistry(cfg).wrap(key, _toy_program(), avals)
    assert hit
    out = program(jax.device_put(np.ones((4,), np.float32), sharding))
    assert {d.id for d in out.sharding.device_set} == {d.id for d in devs}
    np.testing.assert_array_equal(np.asarray(out), np.full((4,), 2.0))


def test_cache_fingerprint_rejects_drifted_program(tmp_path):
    """Same key, different math: the entry must be evicted and
    recompiled, never served (the PR 1 silently-wrong-executable
    class). The drifted program also flips the precondition (different
    avals? no — different nothing the digest sees), so this goes
    through the full fingerprint path via the verify env switch."""
    cfg = _cache_cfg(tmp_path)
    reg = ProgramRegistry(cfg)
    key = "train|toy|mesh1x1|b4"
    reg.wrap(key, _toy_program(scale=2.0), _toy_avals())

    _fresh_process()
    os.environ["TPU_RESNET_PROGRAM_CACHE_VERIFY"] = "1"
    try:
        reg2 = ProgramRegistry(cfg)
        program, hit = reg2.wrap(key, _toy_program(scale=3.0),
                                 _toy_avals())
    finally:
        del os.environ["TPU_RESNET_PROGRAM_CACHE_VERIFY"]
    assert not hit  # evicted + recompiled
    assert float(program(np.ones((4,), np.float32))[0]) == 3.0


def test_cache_version_mismatch_evicts(tmp_path):
    cfg = _cache_cfg(tmp_path)
    reg = ProgramRegistry(cfg)
    key = "train|toy|mesh1x1|b4"
    reg.wrap(key, _toy_program(), _toy_avals())
    cache = reg.cache
    path = os.path.join(cache.dir, os.listdir(cache.dir)[0])
    header = cache.read_header(path)

    # rewrite the entry as if an older jaxlib had produced it
    with open(path, "rb") as f:
        blob = f.read()
    import struct

    (n,) = struct.unpack(">I", blob[6:10])
    payload = blob[10 + n:]
    header["jaxlib"] = "0.0.1"
    cache._write(path, header, payload)

    _fresh_process()
    assert cache.load_fast(key, "whatever") is None
    assert not os.path.exists(path), "stale entry must be deleted"


@pytest.mark.parametrize("corruption", ["truncate", "flip", "garbage"])
def test_cache_corrupt_entry_recovers(tmp_path, corruption):
    cfg = _cache_cfg(tmp_path)
    reg = ProgramRegistry(cfg)
    key = "train|toy|mesh1x1|b4"
    reg.wrap(key, _toy_program(), _toy_avals())
    path = os.path.join(reg.cache.dir, os.listdir(reg.cache.dir)[0])
    with open(path, "rb") as f:
        blob = f.read()
    if corruption == "truncate":
        blob = blob[: len(blob) // 2]
    elif corruption == "flip":
        blob = blob[:-20] + bytes([blob[-20] ^ 0xFF]) + blob[-19:]
    else:
        blob = b"not a cache entry at all"
    with open(path, "wb") as f:
        f.write(blob)

    _fresh_process()
    reg2 = ProgramRegistry(cfg)
    program, hit = reg2.wrap(key, _toy_program(), _toy_avals())
    assert not hit, "corrupt entry must be a miss, never deserialized"
    assert float(program(np.ones((4,), np.float32))[0]) == 2.0
    # ...and the recompile overwrote it with a loadable entry
    _fresh_process()
    _, hit3 = ProgramRegistry(cfg).wrap(key, _toy_program(),
                                        _toy_avals())
    assert hit3


def test_cache_loads_each_entry_at_most_once_per_process(tmp_path):
    """The PR 1 hazard lock: this jaxlib segfaults on the SECOND
    in-process deserialization of an entry — the cache must refuse it
    and recompile instead."""
    cfg = _cache_cfg(tmp_path)
    key = "train|toy|mesh1x1|b4"
    ProgramRegistry(cfg).wrap(key, _toy_program(), _toy_avals())

    _fresh_process()
    reg = ProgramRegistry(cfg)
    _, hit1 = reg.wrap(key, _toy_program(), _toy_avals())
    assert hit1
    # same process asks again (e.g. train()+resume building a fresh
    # wrapper): must NOT deserialize a second time
    program, hit2 = reg.wrap(key, _toy_program(), _toy_avals())
    assert not hit2
    assert float(program(np.ones((4,), np.float32))[0]) == 2.0


def test_cache_kill_switch_and_modes(tmp_path, monkeypatch):
    cfg = _cache_cfg(tmp_path)
    assert ProgramRegistry(cfg).cache_enabled
    monkeypatch.setenv("TPU_RESNET_PROGRAM_CACHE", "0")
    assert not ProgramRegistry(cfg).cache_enabled  # kill-switch wins
    monkeypatch.delenv("TPU_RESNET_PROGRAM_CACHE")

    off = load_config("smoke")
    off.programs.cache = "off"
    assert not ProgramRegistry(off).cache_enabled
    auto = load_config("smoke")
    assert not ProgramRegistry(auto, context="train").cache_enabled
    assert ProgramRegistry(auto, context="serve").cache_enabled
    monkeypatch.setenv("TPU_RESNET_PROGRAM_CACHE_DIR",
                       str(tmp_path / "envcache"))
    assert ProgramRegistry(auto, context="train").cache_enabled
    bad = load_config("smoke")
    bad.programs.cache = "always"
    with pytest.raises(ValueError, match="auto|on|off"):
        ProgramRegistry(bad)


def test_cache_disabled_registry_is_identity(tmp_path):
    cfg = load_config("smoke")
    cfg.programs.cache = "off"
    reg = ProgramRegistry(cfg)
    jitted = _toy_program()
    program, hit = reg.wrap("train|toy|mesh1x1|b4", jitted, _toy_avals())
    assert program is jitted and not hit


def test_program_falls_back_to_jit_on_signature_mismatch(tmp_path):
    """An AOT executable rejecting a call (unexpected batch shape) must
    degrade to plain jit dispatch — one extra compile, never a crash."""
    cfg = _cache_cfg(tmp_path)
    reg = ProgramRegistry(cfg)
    program, _ = reg.wrap("train|toy|mesh1x1|b4", _toy_program(),
                          _toy_avals())
    out = program(np.ones((8,), np.float32))  # aval said (4,)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.full((8,), 2.0, np.float32))


def test_precondition_changes_take_verified_path_and_rebless(tmp_path):
    cfg = _cache_cfg(tmp_path)
    reg = ProgramRegistry(cfg)
    key = "train|toy|mesh1x1|b4"
    reg.wrap(key, _toy_program(), _toy_avals())
    cache = reg.cache
    path = os.path.join(cache.dir, os.listdir(cache.dir)[0])
    # a changed precondition (e.g. an irrelevant config edit) must not
    # serve the fast path...
    assert cache.load_fast(key, "different-precondition") is None
    assert os.path.exists(path), \
        "precondition mismatch alone must not evict"
    # ...but the fingerprint-verified path re-blesses the entry
    _fresh_process()
    reg2 = ProgramRegistry(cfg)
    import jax

    lowered = _toy_program().lower(*_toy_avals())
    fp = registry_mod.fingerprint_lowered(lowered)
    assert cache.load_verified(key, fp, precondition="new-pre") is not None
    assert cache.read_header(path)["precondition"] == "new-pre"
    # wrong fingerprint evicts
    _fresh_process()
    assert cache.load_verified(key, "wrong", precondition="x") is None
    assert not os.path.exists(path)
    _ = jax  # (import kept local to the cache paths above)


def test_donation_assertion_fires_on_contract_break(tmp_path):
    import jax

    cfg = _cache_cfg(tmp_path)
    reg = ProgramRegistry(cfg)
    jitted = jax.jit(lambda s, x: (s + x, x.sum()), donate_argnums=(0,))
    avals = (jax.ShapeDtypeStruct((4,), "float32"),
             jax.ShapeDtypeStruct((4,), "float32"))
    # arg 0 donated but the caller claims nothing should be
    with pytest.raises(ValueError, match="donated"):
        reg.wrap("train|don|mesh1x1|b4", jitted, avals, donated_args=())
    # correct declaration passes
    program, _ = reg.wrap("train|don2|mesh1x1|b4", jitted, avals,
                          donated_args=(0,))
    assert program is not None


# ------------------------------------------------------------- integration
def test_train_loop_warm_restart_hits_cache_value_identically(tmp_path):
    """Two fresh train() runs sharing one cache dir: the second must
    LOAD its program (cache_load span with cache_hit) and produce a
    bit-identical loss stream — the executable cache is an identity
    transform on results."""
    from tpu_resnet.obs.spans import load_jsonl, load_spans
    from tpu_resnet.train.loop import train

    losses = {}
    for run in ("cold", "warm"):
        cfg = load_config("smoke")
        cfg.programs.cache = "on"
        cfg.programs.cache_dir = str(tmp_path / "progcache")
        cfg.model.name = "mlp"
        cfg.data.device_resident = "off"
        cfg.data.transfer_stage = 1
        cfg.train.train_dir = str(tmp_path / run)
        cfg.train.train_steps = 6
        cfg.train.log_every = 3
        cfg.train.summary_every = 3
        cfg.train.checkpoint_every = 6
        cfg.train.image_summary_every = 0
        cfg.train.memory_ledger = False
        _fresh_process()  # each run simulates its own process
        train(cfg)
        losses[run] = [r["loss"] for r in load_jsonl(
            os.path.join(cfg.train.train_dir, "metrics.jsonl"), "step")
            if "loss" in r]
        cache_spans = [s for s in load_spans(
            os.path.join(cfg.train.train_dir, "events.jsonl"))
            if s["span"] == "cache_load"]
        assert cache_spans, "registry must record cache_load spans"
        expect_hit = run == "warm"
        assert all(s["cache_hit"] is expect_hit for s in cache_spans), \
            (run, cache_spans)
    assert losses["cold"] == losses["warm"] and losses["cold"]


def test_serve_warmup_smallest_first_with_cache_hit_spans(tmp_path):
    """PredictServer warms buckets smallest-first through
    backend.warmup_bucket and emits one serve_warmup_bucket span per
    bucket carrying cache_hit, plus the serve_ready summary event."""
    from tpu_resnet.obs.spans import SpanTracer, load_spans
    from tpu_resnet.serve.server import PredictServer

    order = []

    class RecordingBackend:
        image_size = 8
        num_classes = 3
        fixed_batch = 0
        model_step = 1
        reloads = 0

        def constrain_buckets(self, buckets):
            return tuple(buckets)

        def warmup_bucket(self, b):
            order.append(b)
            return {"bucket": b, "cache_hit": b != 8, "seconds": 0.0}

        def infer(self, images):
            return np.zeros((images.shape[0], 3), np.float32)

        def maybe_reload(self):
            return False

        def close(self):
            pass

    cfg = load_config("smoke")
    cfg.train.train_dir = str(tmp_path)
    cfg.serve.port = 0
    cfg.serve.host = "127.0.0.1"
    cfg.serve.batch_buckets = (8, 2, 4)  # deliberately unsorted
    spans = SpanTracer(str(tmp_path), filename="serve_events.jsonl")
    server = PredictServer(cfg, backend=RecordingBackend(), spans=spans)
    try:
        server.start()
    finally:
        server.drain(timeout=2)
        server.close()
        spans.close()
    assert order == [2, 4, 8], "warmup must be smallest-first"
    recorded = load_spans(os.path.join(str(tmp_path),
                                       "serve_events.jsonl"))
    per_bucket = [s for s in recorded if s["span"] == "serve_warmup_bucket"]
    assert [s["bucket"] for s in per_bucket] == [2, 4, 8]
    assert [s["cache_hit"] for s in per_bucket] == [True, True, False]
    ready = [s for s in recorded if s["span"] == "serve_ready"]
    assert ready and ready[0]["cache_hits_total"] == 2
    assert server.registry._gauges["serve_buckets_warm"] == 3.0
