"""The family ``smallthinker`` in the harness, on the CPU at a tiny size: its
configuration's and its cell's entries, its counts against a brute-force
count, its readers, and a fixture cell (``fixtures/tiny_smallthinker``: d
64, a global and a window layer of 7 query heads over 1 key/value
head, a window of 16, 16 ReGLU experts of which 4 are held, one sequence
of 64 a step, a vocabulary of 128) through ``run_cell`` plain and traced,
with the fp8 control and each planted fault read as not correct. The
family, its reference and the data kind are the benchmark's own files;
only the cell is the fixture's."""

import io
import json
import os
import shutil
import time
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks.data_kinds import packed_tokens
from benchmarks.families import smallthinker as family
from benchmarks.lib import faults, harness, lastline, peaks
from benchmarks.lib.manifest import Manifest

TINY = os.path.join(os.path.dirname(__file__), "fixtures",
                    "tiny_smallthinker")
CELL = "tiny_smallthinker.longdoc_b1_s64"
REAL = "smallthinker_21b_a3b_ep8.longdoc_b1_s16384_v18992"
READERS = ("window_cut_pct", "mixed_attn_fwd_roofline_pct",
           "mixed_attn_bwd_roofline_pct")


@pytest.fixture(autouse=True)
def exact_products(monkeypatch):
    """The reference in float32 to the last bits (a chip run's carries 16
    bits a product, ``HIGH``, for its time limit's sake): two float32
    implementations then choose the same experts."""
    from benchmarks.reference import afmoe as numerics

    monkeypatch.setattr(numerics, "TERMS", numerics.HIGHEST)


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tiny_smallthinker"))
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


# ------------------------------------------------------------- the entries
def test_the_benchmark_itself_is_sound_with_the_new_entries():
    m = Manifest()
    assert m.problems() == []
    assert m.family_of(REAL) is family
    assert m.data_kind_of(REAL) is packed_tokens.generate
    assert m.workload(REAL)["chips"] == 1
    names = [p["name"] for p in m.per_layer(REAL)]
    assert names[:8] == [p["name"] for p in m.per_layer(
        "wrn28_10_cifar100.resident_b1024")]
    # the routing's two counters list the cells a test of the benchmark
    # pins, this one not among them (PERF.md section 7)
    assert names[8:] == list(READERS)
    for cell in m.workloads:
        if cell != REAL:
            assert not set(READERS) & {p["name"] for p in m.per_layer(cell)}
    for entry in m.spec["per_layer"]:
        if entry["name"] in READERS:
            assert entry["workloads"] == [REAL]
            assert entry["moves"] == "train_images_per_s"
            assert entry["unit"] == "%"
        elif entry["name"].startswith("moe_"):
            assert REAL not in entry["workloads"]
    traffic = m.traffic_of(REAL)
    assert traffic["data"] == dict(
        kind="packed_tokens", sequences=256, seq_len=16384, vocab=18992,
        median=8192, sigma=1.0, min_len=256, max_len=16384)
    assert traffic["overrides"] == ["train.global_batch_size=1"]
    limits = m.limits_of(REAL)
    assert limits["step_count"] == 0 and limits["moments0"] == 0
    assert "bias_gap" not in limits


def test_the_configuration_states_the_rows_keys_and_what_it_cut():
    """Every key of the catalog row's ``config`` under its name, changed
    only where ``reduced`` says; widths as published."""
    m = Manifest()
    stated = m.config_of(REAL)
    entry = m.configs["smallthinker_21b_a3b_ep8"]
    assert entry["source"] == stated["source"] and entry["source"].endswith(
        "PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json")
    assert entry["reduced"] == stated["reduced"] == [
        "num_hidden_layers", "moe_num_primary_experts", "vocab_size"]
    layout = [0, 1, 1, 1] * 13
    published = dict(
        head_dim=128, hidden_size=2560, max_position_embeddings=16384,
        model_name="smallthinker_21b_instruct", moe_ffn_hidden_size=768,
        moe_num_active_primary_experts=6, moe_num_primary_experts=64,
        moe_primary_router_apply_softmax=True, norm_topk_prob=True,
        num_attention_heads=28, num_hidden_layers=52, num_key_value_heads=4,
        rms_norm_eps=1e-06, rope_layout=layout, rope_scaling=None,
        rope_theta=1500000, sliding_window_layout=layout,
        sliding_window_size=4096, tie_word_embeddings=False,
        vocab_size=151936)
    for key, value in published.items():
        if key in stated["reduced"]:
            assert stated["published"][key] == value
            assert stated[key] != value
        else:
            assert stated[key] == value, key
    model = stated["model"]
    assert model["layers"] == [
        "window" if w else "global" for w in layout[:4]]
    assert model["experts_held"] == stated["moe_num_primary_experts"] == 8
    assert model["experts_total"] == 64 and model["top_k"] == 6
    assert model["vocab_rows"] == stated["vocab_size"] == 151936 // 8
    assert model["seq_len"] == stated["max_position_embeddings"]
    assert model["window"] == stated["sliding_window_size"]
    for words in ("deployment", "assumed", "reduced_note"):
        assert stated[words]
    assert "8 chips share each layer" in stated["deployment"]
    assert all("from memory" in v for k, v in stated["assumed"].items()
               if k in ("router_before_attention", "experts", "attention",
                        "window_and_position", "norms"))


# -------------------------------------------------------------- the counts
def test_counts_equal_a_brute_force_count():
    """Every multiply-add of a forward pass at a small size, counted one
    product at a time over explicit shapes and explicit masks."""
    arch = dict(layers=["global", "window", "window"], hidden=64, heads=14,
                kv_heads=2, head_dim=16, window=10, expert_width=16,
                experts_total=16, experts_held=4, top_k=3, vocab_rows=128,
                seq_len=48)
    s, d = 48, 64
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    causal = int((j <= i).sum())
    window = int(((j <= i) & (i - j < 10)).sum())
    assert family.live_entries(s, 0) == causal
    assert family.live_entries(s, 10) == window
    proj = s * d * (224 + 32 + 32) + s * 224 * d       # q, k, v; out
    moe = s * d * 16 + 3 * d * 16 * (s * 3 * 4 / 16)   # router; rows here
    attn = 14 * 16 * 2
    macs = (3 * (proj + moe) + attn * (causal + 2 * window)
            + s * d * 128)
    assert family.train_flops_per_example(arch) == 6.0 * macs
    assert family.attention_flops(arch) == 14 * (causal + 2 * window) * 64
    assert family.attention_flops(arch, backward=True) == \
        2.5 * family.attention_flops(arch)
    # bytes: q, k, v and the output in bf16, the row's float32 log-sum;
    # backward the cotangents besides
    fwd = s * 16 * 2 * (14 + 2 + 2 + 14) + 14 * s * 4
    bwd = s * 16 * 2 * (2 * (14 + 2 + 2) + 14) + 14 * s * 4
    assert family.attention_bytes(arch) == 3 * fwd
    assert family.attention_bytes(arch, backward=True) == 3 * bwd


def test_the_cells_counts_are_the_programs_and_a_hand_count():
    from tpu_resnet.config import load_config
    from tpu_resnet.models import family as program_family

    arch = Manifest().config_of(REAL)["model"]
    # 48.62 M the head, 4 x 25.56 M the projections, router and experts,
    # attention's live entries 58.72 M on the global layer and 25.69 M on
    # each window layer
    assert family.forward_macs_per_token(arch) == 286_652_544
    assert family.live_entries(16384, 0) == 134_225_920
    assert family.live_entries(16384, 4096) == 58_722_304
    assert family.train_flops_per_example(arch) == pytest.approx(
        28.18e12, rel=1e-3)
    cfg = load_config("smallthinker_21b_a3b_ep8")
    assert program_family(cfg).train_flops_per_example(cfg) == \
        family.train_flops_per_example(arch)
    # attention is about half the step's counted work
    share = (58_723_840 + 3 * 25_691_008) / 286_652_544
    assert share == pytest.approx(0.474, abs=5e-4)
    # compute-bound both ways on a v5e (197 TFLOP/s, 819 GB/s)
    for backward in (False, True):
        assert family.attention_flops(arch, backward) / 197e12 > \
            10 * family.attention_bytes(arch, backward) / 819e9


# -------------------------------------------------------------- the readers
def test_readers_return_numbers_and_find_nothing_where_nothing_is():
    m = Manifest()
    cut = m.reader("window_cut_pct")
    assert cut(SimpleNamespace(records=[{"loss": 1.0}])) is None
    assert cut(SimpleNamespace(records=[])) is None
    assert cut(SimpleNamespace(records=[
        {"window_cut_frac": 0.3}, {"window_cut_frac": 0.4}])
    ) == pytest.approx(35.0)
    arch = m.config_of(REAL)["model"]
    peak = peaks.peaks_for("TPU v5 lite")
    rows = [["fusion", 9.0], ["splash_mqa_dkv_segmented_no_residuals", 6.0],
            ["splash_mqa_fwd_segmented_residuals", 3.0]]
    on = SimpleNamespace(trace={"device_ops": rows}, peaks=peak, images=30,
                         chips=1, arch=arch)
    fwd = m.reader("mixed_attn_fwd_roofline_pct")
    bwd = m.reader("mixed_attn_bwd_roofline_pct")
    assert fwd(on) == pytest.approx(
        100 * family.attention_flops(arch) * 30 / 197e12 / 3.0)
    assert bwd(on) == pytest.approx(
        100 * family.attention_flops(arch, True) * 30 / 197e12 / 6.0)
    assert 0 < fwd(on) < 100 and 0 < bwd(on) < 100
    # a trace without the kernels among its largest rows, no trace, or
    # another family's configuration (Trinity's has a window too)
    trinity = m.config_of("trinity_mini_ep16.packed_b2_s4096")["model"]
    for other in (dict(trace={"device_ops": rows[:1]}), dict(trace=None),
                  dict(arch=trinity), dict(images=0)):
        off = SimpleNamespace(**{**vars(on), **other})
        assert fwd(off) is None and bwd(off) is None


# ---------------------------------------------------------- the rehearsal
def test_untraced_run_is_correct_and_its_fp8_control_is_not(manifest, capfd):
    line = run(manifest, 2 ** 31 + 43, False, control="fp8")
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == {"train_images_per_s", "setup_s"}
    assert line["attempted"] >= 4 and line["failed"] == 0
    assert "bias_gap" not in line["compared"]
    assert "CONTROL fp8 correct=False" in capfd.readouterr().err


def test_traced_run_reports_every_per_layer_metric(manifest):
    line = run(manifest, 43, True)
    assert set(line["metrics"]) == {m["name"]
                                    for m in manifest.per_layer(CELL)}
    # documents of a median 32 ids against a window of 16: the window
    # drops some of a step's pairs, and never all
    assert 0 < line["metrics"]["window_cut_pct"]["value"] < 100
    assert line["correct"] is True, line["compared"]


@pytest.mark.parametrize("fault", [family.state_unchanged,
                                   faults.loss_altered])
def test_planted_fault_is_not_correct(manifest, fault):
    line = run(manifest, 17, False, fault=fault)
    assert line["correct"] is False, (fault.__name__, line["compared"])
