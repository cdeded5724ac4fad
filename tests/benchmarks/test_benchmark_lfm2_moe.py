"""The family ``lfm2_moe`` in the harness, on the CPU at a tiny size: its
configuration's and its cell's entries, its counts against a brute-force
count, its readers, and a fixture cell (``fixtures/tiny_lfm2``: d 64, a
conv layer with a dense MLP, an attention layer and three conv layers over
16 experts of which 4 are held, S 32, a tied vocabulary of 128) through
``run_cell`` plain and traced, with the fp8 control and each planted fault
read as not correct. The family, its reference and the data kind are the
benchmark's own files; only the cell is the fixture's."""

import io
import json
import os
import shutil
import time
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks.data_kinds import packed_tokens
from benchmarks.families import lfm2_moe as family
from benchmarks.lib import faults, harness, lastline, peaks
from benchmarks.lib.manifest import Manifest

TINY = os.path.join(os.path.dirname(__file__), "fixtures", "tiny_lfm2")
CELL = "tiny_lfm2.packed_b8_s32"
REAL = "lfm2_24b_a2b_ep8.packed_b2_s4096_v8192"


@pytest.fixture(autouse=True)
def exact_products(monkeypatch):
    """The reference in float32 to the last bits (a chip run's carries 16
    bits a product, ``HIGH``, for its time limit's sake): two float32
    implementations then choose the same experts."""
    from benchmarks.reference import afmoe as numerics

    monkeypatch.setattr(numerics, "TERMS", numerics.HIGHEST)


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tiny_lfm2"))
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
    mine = [p["name"] for p in m.spec["per_layer"]
            if p.get("workloads") == [REAL]]
    assert mine[0] == "conv_cut_taps_pct"
    names = [p["name"] for p in m.per_layer(REAL)]
    assert names[:8] == [p["name"] for p in m.per_layer(
        "wrn28_10_cifar100.resident_b1024")]
    assert names[8:] == mine
    # no accepted entry lists the new cell, and none of the new ones
    # lists an accepted cell
    for p in m.spec["per_layer"]:
        if p["name"] not in mine:
            assert REAL not in p.get("workloads", ())
    trinity = [p["name"] for p in
               m.per_layer("trinity_mini_ep16.packed_b2_s4096")]
    assert len(trinity) == 10 and not set(trinity) & set(mine)
    traffic = m.traffic_of(REAL)
    assert traffic["data"] == dict(
        m.traffic_of("trinity_mini_ep16.packed_b2_s4096")["data"],
        vocab=8192)
    assert traffic["overrides"] == ["train.global_batch_size=2"]
    limits = m.limits_of(REAL)
    assert limits["step_count"] == 0 and limits["moments0"] == 0


def test_the_configuration_states_the_rows_keys_and_what_it_cut():
    """Every key of the catalog row's ``config`` under its name, changed
    only where ``reduced`` says; widths as published."""
    m = Manifest()
    stated = m.config_of(REAL)
    entry = m.configs["lfm2_24b_a2b_ep8"]
    assert entry["source"] == stated["source"] and entry["source"].endswith(
        "LiquidAI/LFM2-24B-A2B/blob/main/config.json")
    assert entry["reduced"] == stated["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"]
    published = dict(
        conv_L_cache=3, conv_bias=False, hidden_size=2048,
        intermediate_size=11776, max_position_embeddings=128000,
        model_type="lfm2_moe", moe_intermediate_size=1536, norm_eps=1e-05,
        norm_topk_prob=True, num_attention_heads=32, num_dense_layers=2,
        num_experts=64, num_experts_per_tok=4, num_hidden_layers=40,
        num_key_value_heads=8, routed_scaling_factor=1,
        use_expert_bias=True, vocab_size=65536)
    for key, value in published.items():
        if key in stated["reduced"]:
            assert stated["published"][key] == value
            assert stated[key] != value
        else:
            assert stated[key] == value, key
    assert stated["rope_parameters"] == {"rope_theta": 1000000,
                                         "rope_type": "default"}
    assert len(stated["layer_types"]) == 40
    assert stated["layer_types"][1:6] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert [k.split("_")[1] for k in stated["model"]["layers"]] == [
        "conv", "full", "conv", "conv", "conv"]
    for words in ("deployment", "assumed", "reduced_note"):
        assert stated[words]
    assert "float32" in stated["assumed"]["compute"]


# -------------------------------------------------------------- the counts
def test_counts_equal_a_brute_force_count():
    """Every multiply-add of a forward pass at a small size, counted one
    product at a time over explicit shapes and an explicit causal mask."""
    arch = dict(layers=["dense_conv", "moe_full", "dense_full", "moe_conv"],
                hidden=64, heads=4, kv_heads=2, head_dim=16, conv_taps=3,
                dense_width=96, expert_width=32, experts_total=16,
                experts_held=4, top_k=4, vocab_rows=128, seq_len=48)
    s, d = 48, 64
    causal = np.tril(np.ones((s, s), np.int64))
    assert causal.sum() == s * (s + 1) // 2
    conv = s * (d * 3 * d) + s * d * 3 + s * (d * d)
    attn = (s * d * (64 + 32 + 32) + s * 64 * d        # q, k, v; out
            + 4 * int(causal.sum()) * 16 * 2)          # scores, values
    dense = 3 * s * d * 96
    moe = s * d * 16 + 3 * d * 32 * (s * 4 * 4 / 16)   # router; rows here
    macs = (conv + dense) + (attn + moe) + (attn + dense) + (conv + moe) \
        + s * d * 128
    assert family.train_flops_per_example(arch) == 6.0 * macs
    assert family.attention_fwd_flops(arch) == \
        2 * 4 * int(causal.sum()) * 2 * 2 * 16
    assert family.attention_bwd_flops(arch) == \
        2.5 * family.attention_fwd_flops(arch)
    # forward: B, X, C in and y out; backward: those three and dy in,
    # three gradients out; two bytes an element
    assert family.conv_bytes(arch, 100) == (4 + 7) * 100 * 64 * 2


def test_the_cells_counts_are_the_issues_and_the_programs():
    from tpu_resnet.config import load_config
    from tpu_resnet.models import family as program_family

    arch = Manifest().config_of(REAL)["model"]
    assert family.forward_macs_per_token(arch) == 194_537_472
    assert family.train_flops_per_example(arch) == pytest.approx(4.78e12,
                                                                 rel=2e-3)
    cfg = load_config("lfm2_24b_a2b_ep8")
    assert program_family(cfg).train_flops_per_example(cfg) == \
        family.train_flops_per_example(arch)
    # 1 layer x 32 heads x S (S + 1) / 2 x 2 products x 2 x 64
    assert family.attention_fwd_flops(arch) == \
        32 * (4096 * 4097 // 2) * 2 * 2 * 64
    # one layer's forward at 8,192 tokens: 134 MB
    assert 4 * 8192 * 2048 * 2 == 134_217_728
    assert family.conv_bytes(arch, 8192) == 11 * 8192 * 2048 * 2


# -------------------------------------------------------------- the readers
def test_readers_return_numbers_and_find_nothing_where_nothing_is():
    m = Manifest()
    cut = m.reader("conv_cut_taps_pct")
    assert cut(SimpleNamespace(records=[{"loss": 1.0}])) is None
    assert cut(SimpleNamespace(records=[])) is None
    assert cut(SimpleNamespace(records=[
        {"conv_cut_taps_frac": 0.001}, {"conv_cut_taps_frac": 0.002}])
    ) == pytest.approx(0.15)
    arch = m.config_of(REAL)["model"]
    peak = peaks.peaks_for("TPU v5 lite")
    rows = [["fusion", 9.0], ["splash_mqa_dkv_segmented_no_residuals", 0.6],
            ["splash_mqa_fwd_segmented_residuals", 0.3]]
    on = SimpleNamespace(trace={"device_ops": rows}, peaks=peak, images=200,
                         chips=1, arch=arch)
    fwd = lambda run: family.kernel_share(
        run, "splash_mqa_fwd", family.attention_fwd_flops)
    bwd = lambda run: family.kernel_share(
        run, "splash_mqa_dkv", family.attention_bwd_flops)
    assert fwd(on) == pytest.approx(
        100 * family.attention_fwd_flops(arch) * 200 / 0.3 / 197e12)
    assert bwd(on) == pytest.approx(fwd(on) * 2.5 / 2)
    assert 0 < fwd(on) < 100 and 0 < bwd(on) < 100
    # a trace without the kernels among its largest rows, no trace, or
    # another family's configuration: nothing to read
    trinity = m.config_of("trinity_mini_ep16.packed_b2_s4096")["model"]
    for other in (dict(trace={"device_ops": rows[:1]}), dict(trace=None),
                  dict(arch=trinity), dict(images=0)):
        off = SimpleNamespace(**{**vars(on), **other})
        assert fwd(off) is None and bwd(off) is None


# ---------------------------------------------------------- the rehearsal
def test_untraced_run_is_correct_and_its_fp8_control_is_not(manifest, capfd):
    line = run(manifest, 2 ** 31 + 21, False, control="fp8")
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == {"train_images_per_s", "setup_s"}
    assert line["attempted"] >= 4 and line["failed"] == 0
    assert "bias_gap" in line["compared"]
    assert "CONTROL fp8 correct=False" in capfd.readouterr().err


def test_traced_run_reports_every_per_layer_metric(manifest):
    line = run(manifest, 23, True)
    assert set(line["metrics"]) == {m["name"]
                                    for m in manifest.per_layer(CELL)}
    # documents of a median 12 ids: a cut tap in every dozen or so
    assert 1 < line["metrics"]["conv_cut_taps_pct"]["value"] < 20
    assert line["correct"] is True, line["compared"]


@pytest.mark.parametrize("fault", [
    faults.state_unchanged, family.state_unchanged, family.half_batch,
    faults.loss_altered])
def test_planted_fault_is_not_correct(manifest, fault):
    line = run(manifest, 17, False, fault=fault)
    assert line["correct"] is False, (fault.__name__, line["compared"])
