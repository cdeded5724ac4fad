"""Smoke the bench measurement functions at tiny config on the CPU mesh —
so the driver's unattended TPU bench can't be the first-ever execution of
any measurement path (round-1 failure mode)."""

import jax
import pytest

import bench
from tpu_resnet.parallel import create_mesh


@pytest.fixture(scope="module")
def mesh():
    return create_mesh(None, devices=jax.devices()[:8])


@pytest.mark.slow
def test_measure_cifar_multiplan_smoke(mesh):
    """Two fusion factors share one setup; each plan aligns to an epoch
    boundary and yields a positive rate. Two chunk-variant compiles —
    slow-tiered with the other bench-harness integration smokes; the
    single-plan resident path stays in the default tier via
    test_measure_cifar_wide_smoke + the streaming smoke."""
    by_k = bench._measure_cifar(mesh, [(2, 1, 2), (4, 1, 2)],
                                resnet_size=8, batch=16, dtype="float32",
                                split=256)
    assert set(by_k) == {2, 4}
    assert all(v > 0 for v in by_k.values())


def test_measure_cifar_rejects_zero_warmup(mesh):
    """warmup_chunks=0 must fail loudly at validation, not NameError in
    the timed loop (advisor round-2 finding)."""
    with pytest.raises(ValueError, match="warmup_chunks"):
        bench._measure_cifar(mesh, [(2, 0, 2)], resnet_size=8, batch=16,
                             dtype="float32", split=256)


@pytest.mark.slow  # 19s: bench-harness WRN-path smoke; the streaming and
# pallas A/B smokes keep the harness covered in tier-1. Joined the slow
# tier to keep the default tier inside the 870s verify budget (precedent:
# its imagenet/multiplan siblings above).
def test_measure_cifar_wide_smoke(mesh):
    """The WRN entry's path: width multiplier + 100 classes."""
    by_k = bench._measure_cifar(mesh, [(2, 1, 1)], resnet_size=10,
                                batch=16, dtype="float32", split=64,
                                width=2, num_classes=100)
    assert by_k[2] > 0


def test_measure_pallas_ab_smoke(mesh):
    """The A/B harness's scan-fused timing loop runs end-to-end (interpret
    -mode Pallas on CPU; tiny iteration count)."""
    out = bench._measure_pallas_ab(iters=2)
    assert set(out) == {"b128x10", "b128x1000"}
    assert all(v["pallas_us"] > 0 and v["xla_us"] > 0
               for v in out.values())


def test_measure_cifar_streaming_smoke(mesh):
    sps, breakdown = bench._measure_cifar_streaming(
        mesh, warmup_super=1, measure_super=1, stage=2, resnet_size=8,
        batch=16, dtype="float32", split=256)
    assert sps > 0
    # The bench line carries the step-time decomposition of the measured
    # window (tpu_resnet/obs/breakdown.py).
    assert 0.0 <= breakdown["data_wait_frac"] <= 1.0
    assert breakdown["dispatch_sec"] >= 0.0


@pytest.mark.slow
def test_measure_imagenet_smoke(mesh):
    sps, flops, comms = bench._measure_imagenet(
        mesh, warmup_steps=1, measure_steps=2, resnet_size=18, batch=16,
        image=64, dtype="float32")
    assert sps > 0
    assert flops is None or flops > 0
    # single-device mesh: the compiled step is collective-free, and the
    # comms fields (when the backend reports HLO) must say exactly that.
    if comms:
        assert comms["comms_bytes_per_step"] == 0
        assert comms["comms_collective_count"] == 0


def test_peak_flops_table():
    assert bench._peak_flops("TPU v5 lite") == 197e12
    assert bench._peak_flops("TPU v4") == 275e12
    assert bench._peak_flops("TPU v5p") == 459e12
    assert bench._peak_flops("mystery chip") is None


def test_measure_host_decode():
    # engine_curve=False: the worker-scaling probe is covered by
    # test_doctor's data-bench test (same probe function); spawning
    # processes twice per suite buys nothing.
    out = bench._measure_host_decode(n_images=20, size=(320, 240),
                                     engine_curve=False)
    assert out["native_images_per_sec"] > 0
    assert out["pil_images_per_sec"] > 0
    assert "engine_scaling" not in out


def test_measure_host_decode_engine_curve_key(monkeypatch):
    """With the curve enabled the section carries the probe result; a
    probe that raises fails the section (and so the run)."""
    import tpu_resnet.data.engine as engine_mod

    monkeypatch.setattr(engine_mod, "decode_scaling_probe",
                        lambda **kw: {"engine_images_per_sec_by_procs":
                                      {"1": 10.0}})
    out = bench._measure_host_decode(n_images=5, size=(320, 240),
                                     engine_curve=True)
    assert out["engine_scaling"]["engine_images_per_sec_by_procs"] == \
        {"1": 10.0}

    def boom(**kw):
        raise RuntimeError("no procs here")

    monkeypatch.setattr(engine_mod, "decode_scaling_probe", boom)
    with pytest.raises(RuntimeError, match="no procs here"):
        bench._measure_host_decode(n_images=5, size=(320, 240),
                                   engine_curve=True)


def test_measure_record_split():
    out = bench._measure_record_split(n_records=40)
    assert out["native_crc_mb_per_sec"] > 0
    assert out["python_crc_mb_per_sec"] > 0


def test_fetch_sync_returns_scalar():
    """_fetch_sync is the timing barrier every timed loop closes over —
    it must force a host value out of any scalar-shaped JAX array."""
    import jax.numpy as jnp

    v = bench._fetch_sync(jnp.float32(3.5))
    assert isinstance(v, float) and v == 3.5


def test_without_a_tpu_bench_measures_nothing():
    """`python bench.py` on a CPU: non-zero exit, the platform named, no
    JSON line and so no rate — there is no CPU fallback."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.abspath(bench.__file__))
    proc = subprocess.run([sys.executable, "bench.py"], cwd=repo,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                          text=True, timeout=300)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr and "platform=cpu" in proc.stderr
    assert proc.stdout.strip() == ""


def test_a_section_that_raises_is_filed_and_fails_the_run(monkeypatch,
                                                          capsys):
    """Every section runs; one that raised lands under `errors` in the
    final line (still printed) and the exit code is 1."""
    import json
    import types

    def boom(*a, **kw):
        raise RuntimeError("mosaic refused")

    monkeypatch.setattr(bench, "_measure_cifar",
                        lambda *a, **kw: {10: 5.0, 50: 6.0})
    monkeypatch.setattr(bench, "_measure_cifar_streaming",
                        lambda *a, **kw: (4.0, {"data_wait_frac": 0.1}))
    monkeypatch.setattr(bench, "_measure_imagenet",
                        lambda *a, **kw: (2.0, 1e12, {}))
    monkeypatch.setattr(bench, "_measure_pallas_ab", boom)
    monkeypatch.setattr(bench, "_measure_host_decode", lambda: {"n": 1})
    monkeypatch.setattr(bench, "_measure_record_split", lambda: {"n": 2})
    tpu = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a: [tpu])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    from tpu_resnet import hostenv, parallel
    monkeypatch.setattr(hostenv, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(parallel, "create_mesh", lambda cfg: None)

    assert bench.main() == 1
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["errors"] == {"pallas_xent_ab": "RuntimeError: mosaic "
                                                "refused"}
    assert line["value"] == 5.0 and line["device_kind"] == "TPU v5 lite"
    assert line["imagenet"]["mfu"] == round(1e12 * 2.0 / 197e12, 4)
    assert line["record_split"] == {"n": 2}  # later sections still ran

    monkeypatch.setattr(bench, "_measure_pallas_ab", lambda: {"ok": 1})
    assert bench.main() == 0
    assert "errors" not in json.loads(
        capsys.readouterr().out.strip().splitlines()[-1])
