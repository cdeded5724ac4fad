"""The family ``sdar_moe`` in the harness, on the CPU at a tiny size: its
configuration's and its cell's entries, its counts against a brute-force
count of the dense mask, its three readers, and a fixture cell
(``fixtures/tiny_sdar``: d 64, 16 experts of which 4 are held, sequences of
32 clean ids fed as 64 positions) through ``run_cell`` plain and traced,
with the fp8 control and each planted fault read as not correct. The
family, its reference and the readers are the benchmark's own files; only
the cell is the fixture's (its traced run lists no roofline: the CPU takes
the scan, and its trace holds no kernel)."""

import io
import json
import os
import shutil
import time
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks.data_kinds import packed_tokens
from benchmarks.families import sdar_moe as family
from benchmarks.lib import faults, harness, lastline, peaks
from benchmarks.lib.manifest import Manifest
from benchmarks.reference import sdar_moe as ref

TINY = os.path.join(os.path.dirname(__file__), "fixtures", "tiny_sdar")
CELL = "tiny_sdar.blockdiff_b4_s32"
REAL = "sdar_30b_a3b_chat.blockdiff_b1_s4096"
READERS = ("diffusion_masked_pct", "blockdiff_attn_fwd_roofline_pct",
           "blockdiff_attn_bwd_roofline_pct")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(autouse=True)
def exact_products(monkeypatch):
    """The reference in float32 to the last bits (a chip run's carries 16
    bits a product, ``HIGH``, for its time limit's sake), so that the
    fixture's limits can stand under the bf16 stand-in's readings."""
    from benchmarks.reference import afmoe as numerics

    monkeypatch.setattr(numerics, "TERMS", numerics.HIGHEST)


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tiny_sdar"))
    shutil.copytree(TINY, root, dirs_exist_ok=True)
    os.rename(os.path.join(root, "tiny_manifest.json"),
              os.path.join(root, "BENCHMARK.json"))
    m = Manifest(root=root, bench_dir=root)
    assert m.problems() == []
    return m


def run(manifest, seed, trace, **kw):
    out = io.StringIO()
    rc = harness.run_cell(CELL, seed, 0.3, trace,
                          started=time.perf_counter(), manifest=manifest,
                          require_tpu=False, out=out, **kw)
    assert rc == 0
    lines = out.getvalue().strip().splitlines()
    assert len(lines) == 1
    line = json.loads(lines[0])
    body = {k: v for k, v in line.items() if k != "compared"}
    lastline.validate(body, manifest.metrics_for(CELL, trace), trace)
    return line


# ------------------------------------------------------ entries and files
def test_the_benchmark_itself_is_sound_with_the_new_entries():
    m = Manifest()
    assert m.problems() == []
    assert m.family_of(REAL) is family
    assert m.data_kind_of(REAL) is packed_tokens.generate
    assert m.workload(REAL)["chips"] == 1
    names = [p["name"] for p in m.per_layer(REAL)]
    assert tuple(names[-3:]) == READERS and len(names) == 11
    for cell in m.workloads:
        if cell != REAL:
            assert not set(READERS) & {p["name"] for p in m.per_layer(cell)}
    for entry in m.spec["per_layer"]:
        if entry["name"] in READERS:
            assert entry["workloads"] == [REAL]
            assert entry["moves"] == "train_images_per_s"
    limits = m.limits_of(REAL)
    assert limits["step_count"] == 0 and limits["moments0"] == 0
    assert {"loss_rel", "gnorm_rel", "head_cos", "mu_cos"} <= set(limits)


def test_the_configuration_states_its_cut_and_keeps_every_width():
    m = Manifest()
    config, entry = m.config_of(REAL), m.configs["sdar_30b_a3b_chat"]
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["source"] == config["source"]
    assert config["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 128, "vocab_size": 151936}
    assert "8 chips share each layer" in config["deployment"]
    assert "456,346,624 parameters" in config["deployment"]
    for key in ("block_length", "noise_schedule", "mask_id", "loss", "job"):
        assert key in config["assumed"], key
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"]) == (
        2048, 32, 4, 128)
    assert (config["moe_intermediate_size"],
            config["num_experts_per_tok"]) == (768, 8)
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (4, 16, 18992)
    model = config["model"]
    assert (model["hidden"], model["heads"], model["kv_heads"],
            model["head_dim"], model["expert_width"],
            model["experts_total"], model["top_k"]) == (
        2048, 32, 4, 128, 768, 128, 8)
    assert (model["layers"], model["experts_held"], model["vocab_rows"],
            model["mask_id"], model["block_length"]) == (4, 16, 18992,
                                                         18991, 4)
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "SDAR-30B-A3B-Chat")
        assert row["source_url"] == config["source"]
        for key, value in row["config"].items():
            assert key in config, key
            if key not in config["reduced"]:
                assert config[key] == value, key


def test_the_traffic_is_what_the_cell_says_and_the_program_runs_it():
    from tpu_resnet.train.step import check_step_config

    m = Manifest()
    traffic = m.traffic_of(REAL)
    assert traffic["data"] == {
        "kind": "packed_tokens", "sequences": 1000, "seq_len": 4096,
        "vocab": 18991, "median": 600, "sigma": 1.2, "min_len": 16,
        "max_len": 4096}
    assert traffic["overrides"] == ["train.global_batch_size=1"]
    assert (traffic["warmup_boundaries"], traffic["trace_seconds"]) == (1,
                                                                        15)
    cfg = harness.build_config(m.config_of(REAL), traffic, 1, "d", "t")
    check_step_config(cfg, 1)
    assert (cfg.model.name, cfg.data.seq_len, cfg.data.vocab_size,
            cfg.train.global_batch_size, cfg.train.steps_per_call) == (
        "sdar_moe", 4096, 18992, 1, 10)
    assert family.example_input(cfg).shape == (1, 8)
    assert family.example(m.config_of(REAL)["model"]) == {
        "what": "packed sequence", "tokens": 4096, "positions": 8192}


# -------------------------------------------------------------- the counts
def test_counts_equal_a_brute_force_count_of_the_dense_mask():
    arch = dict(layers=3, hidden=64, heads=4, kv_heads=2, head_dim=16,
                expert_width=32, experts_total=16, experts_held=4, top_k=4,
                vocab_rows=128, seq_len=48, block_length=4)
    dense = ref.mask(48, 4)
    assert dense.shape == (96, 96)
    assert family.live_entries(arch) == int(dense.sum())
    assert int(dense[:48, :48].sum()) == 48 * 4          # noisy diagonal
    assert int(dense[:48, 48:].sum()) == 48 * (48 - 4) // 2
    assert int(dense[48:, 48:].sum()) == 48 * (48 + 4) // 2
    # two products of head_dim over every live entry of every head
    assert family.attention_fwd_flops(arch) == \
        3 * 4 * int(dense.sum()) * 2 * 2 * 16
    assert family.attention_bwd_flops(arch) == \
        2.5 * family.attention_fwd_flops(arch)
    macs = 3 * (96 * (64 * (64 + 32 + 32) + 64 * 64 + 64 * 16
                      + 3 * 64 * 32 * 4 * 4 / 16)
                + 2 * 64 * int(dense.sum())) + 48 * 64 * 128
    assert family.train_flops_per_example(arch) == 6.0 * macs


def test_the_cells_counts_are_the_issues_and_the_programs():
    from tpu_resnet.config import load_config
    from tpu_resnet.models import family as program_family

    arch = Manifest().config_of(REAL)["model"]
    assert family.train_flops_per_example(arch) == pytest.approx(8.95e12,
                                                                 rel=2e-3)
    cfg = load_config("sdar_30b_a3b_chat")
    assert program_family(cfg).train_flops_per_example(cfg) == \
        family.train_flops_per_example(arch)
    # 4 layers x 32 heads x (L^2 + L B) x 2 products x 2 x 128
    assert family.attention_fwd_flops(arch) == \
        4 * 32 * (4096 ** 2 + 4096 * 4) * 2 * 2 * 128


# -------------------------------------------------------------- the readers
def test_readers_return_numbers_and_find_nothing_where_nothing_is():
    m = Manifest()
    masked, fwd, bwd = (m.reader(name) for name in READERS)
    assert masked(SimpleNamespace(records=[{"loss": 1.0}])) is None
    assert masked(SimpleNamespace(records=[
        {"diffusion_masked_frac": 0.5}, {"diffusion_masked_frac": 0.52}])
    ) == pytest.approx(51.0)
    arch = m.config_of(REAL)["model"]
    peak = peaks.peaks_for("TPU v5 lite")
    rows = [["fusion", 9.0], ["splash_mqa_dkv_segmented_no_residuals", 3.0],
            ["splash_mqa_fwd_segmented_residuals", 1.5]]
    on = SimpleNamespace(trace={"device_ops": rows}, peaks=peak, images=100,
                         chips=1, arch=arch)
    assert fwd(on) == pytest.approx(
        100 * family.attention_fwd_flops(arch) * 100 / 1.5 / 197e12)
    assert bwd(on) == pytest.approx(fwd(on) * 2.5 / 2)
    assert 0 < fwd(on) < 100 and 0 < bwd(on) < 100
    # the parent's program has no such cell; a trace without the kernels,
    # no trace, or another family's configuration: nothing to read
    for other in (dict(trace={"device_ops": rows[:1]}), dict(trace=None),
                  dict(arch={"layers": ["moe_full"], "seq_len": 4096}),
                  dict(images=0)):
        off = SimpleNamespace(**{**vars(on), **other})
        assert fwd(off) is None and bwd(off) is None


# ---------------------------------------------------------- the rehearsal
def test_untraced_run_is_correct_and_its_fp8_control_is_not(manifest, capfd):
    line = run(manifest, 2 ** 31 + 21, False, control="fp8")
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == {"train_images_per_s", "setup_s"}
    assert line["attempted"] >= 4 and line["failed"] == 0
    said = capfd.readouterr().err
    assert "CONTROL fp8 correct=False" in said and "masked shares" in said


def test_traced_run_reports_every_per_layer_metric(manifest):
    line = run(manifest, 23, True)
    assert set(line["metrics"]) == {m["name"]
                                    for m in manifest.per_layer(CELL)}
    assert 25 < line["metrics"]["diffusion_masked_pct"]["value"] < 75
    assert line["correct"] is True, line["compared"]


@pytest.mark.parametrize("fault", [family.half_batch, faults.loss_altered,
                                   family.state_unchanged])
def test_planted_fault_is_not_correct(manifest, fault):
    line = run(manifest, 17, False, fault=fault)
    assert line["correct"] is False, (fault.__name__, line["compared"])


def test_half_batch_repeats_the_first_half_of_the_one_sequence():
    import jax.numpy as jnp

    seen = []
    gi = jnp.arange(2 * 1 * 8).reshape(2, 1, 8)
    family.half_batch(lambda s, gi, gl, off, c: seen.append((gi, gl)))(
        None, gi, gi + 1, 0, 2)
    np.testing.assert_array_equal(
        np.asarray(seen[0][0])[1, 0], [8, 9, 10, 11, 8, 9, 10, 11])
    np.testing.assert_array_equal(np.asarray(seen[0][1]),
                                  np.asarray(seen[0][0]) + 1)
    # the library's halves the batch axis and leaves nothing of one row
    kept = []
    faults.half_batch(lambda s, gi, gl, off, c: kept.append(gi))(
        None, gi, gi[..., 0], 0, 2)
    assert kept[0].shape == (2, 0, 8)
