"""The family ``qwen3_next`` in the harness, on the CPU at a tiny size: its
configuration's and its cell's entries, its counts against a brute-force
count, its readers, and a fixture cell (``fixtures/tiny_qwen3_next``: d
64, three Gated DeltaNet layers and an attention layer over 16 experts of
which 4 are held beside a gated shared one, S 32 as one chunk, a
vocabulary of 128) through ``run_cell`` plain and traced, with the fp8
control and each planted fault read as not correct. The family, its
reference and the data kind are the benchmark's own files; only the cell
is the fixture's."""

import io
import json
import math
import os
import shutil
import time
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks.data_kinds import packed_tokens
from benchmarks.families import qwen3_next as family
from benchmarks.lib import faults, harness, lastline, peaks
from benchmarks.lib.manifest import Manifest

TINY = os.path.join(os.path.dirname(__file__), "fixtures", "tiny_qwen3_next")
CELL = "tiny_qwen3_next.packed_b8_s32"
REAL = "qwen3_next_80b_a3b_ep16.packed_b2_s4096_v18992"
READERS = ("gdn_fwd_roofline_pct", "gdn_bwd_roofline_pct",
           "gdn_doc_chunks_pct")


@pytest.fixture(autouse=True)
def exact_products(monkeypatch):
    """The reference in float32 to the last bits (a chip run's carries 16
    bits a product, ``HIGH``, for its time limit's sake): two float32
    implementations then choose the same experts."""
    from benchmarks.reference import afmoe as numerics

    monkeypatch.setattr(numerics, "TERMS", numerics.HIGHEST)


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tiny_qwen3_next"))
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
    assert names[8:] == ["moe_dropped_pct", "moe_load_max_over_mean",
                         *READERS]
    for cell in m.workloads:
        if cell != REAL:
            assert not set(READERS) & {p["name"] for p in m.per_layer(cell)}
    for entry in m.spec["per_layer"]:
        if entry["name"] in READERS:
            assert entry["workloads"] == [REAL]
            assert entry["moves"] == "train_images_per_s"
            assert entry["unit"] == "%"
        elif entry["name"].startswith("moe_"):
            assert entry["workloads"] == [
                "trinity_mini_ep16.packed_b2_s4096", REAL]
    traffic = m.traffic_of(REAL)
    assert traffic["data"] == dict(
        m.traffic_of("trinity_mini_ep16.packed_b2_s4096")["data"],
        vocab=18992)
    assert traffic["overrides"] == ["train.global_batch_size=2"]
    limits = m.limits_of(REAL)
    assert limits["step_count"] == 0 and limits["moments0"] == 0
    assert "bias_gap" not in limits


def test_the_configuration_states_the_rows_keys_and_what_it_cut():
    """Every key of the catalog row's ``config`` under its name, changed
    only where ``reduced`` says; widths as published."""
    m = Manifest()
    stated = m.config_of(REAL)
    entry = m.configs["qwen3_next_80b_a3b_ep16"]
    assert entry["source"] == stated["source"] and entry["source"].endswith(
        "Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json")
    assert entry["reduced"] == stated["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    published = dict(
        decoder_sparse_step=1, full_attention_interval=4, head_dim=256,
        hidden_act="silu", hidden_size=2048, intermediate_size=5120,
        linear_conv_kernel_dim=4, linear_key_head_dim=128,
        linear_num_key_heads=16, linear_num_value_heads=32,
        linear_value_head_dim=128, max_position_embeddings=262144,
        mlp_only_layers=[], model_type="qwen3_next",
        moe_intermediate_size=512, norm_topk_prob=True,
        num_attention_heads=16, num_experts=512, num_experts_per_tok=10,
        num_hidden_layers=48, num_key_value_heads=2,
        partial_rotary_factor=0.25, rms_norm_eps=1e-06, rope_scaling=None,
        rope_theta=10000000, shared_expert_intermediate_size=512,
        tie_word_embeddings=False, use_sliding_window=False,
        vocab_size=151936)
    for key, value in published.items():
        if key in stated["reduced"]:
            assert stated["published"][key] == value
            assert stated[key] != value
        else:
            assert stated[key] == value, key
    model = stated["model"]
    assert model["layers"] == ["linear", "linear", "linear", "full"]
    assert model["rotary_dim"] == 256 * 0.25
    assert model["experts_held"] == stated["num_experts"] == 32
    assert model["vocab_rows"] == stated["vocab_size"] == 151936 // 8
    for words in ("deployment", "assumed", "reduced_note"):
        assert stated[words]
    assert "16 chips share each layer" in stated["deployment"]
    assert all("from memory" in v for k, v in stated["assumed"].items()
               if k.startswith(("gated_deltanet", "attention", "router",
                                "norms", "layer_order")))


# -------------------------------------------------------------- the counts
def test_counts_equal_a_brute_force_count():
    """Every multiply-add of a forward pass at a small size, counted one
    product at a time over explicit shapes and an explicit causal mask."""
    arch = dict(layers=["linear", "full", "linear"], hidden=64, heads=4,
                kv_heads=2, head_dim=32, rotary_dim=8, key_heads=2,
                value_heads=4, key_dim=16, value_dim=16, conv_taps=4,
                expert_width=16, shared_width=24, experts_total=16,
                experts_held=4, top_k=4, vocab_rows=128, seq_len=48,
                chunk=16)
    s, d = 48, 64
    causal = np.tril(np.ones((s, s), np.int64))
    gdn = (s * d * (32 + 32 + 64 + 64) + s * d * 8     # qkvz; b, a
           + s * 128 * 4                               # the filter
           + s * 4 * 3 * 16 * 16                       # the recurrence
           + s * 64 * d)                               # out
    attn = (s * d * (256 + 64 + 64) + s * 128 * d      # q and gate, k, v
            + 4 * int(causal.sum()) * 32 * 2)          # scores, values
    moe = (s * d * 16 + s * d                          # router, shared gate
           + 3 * d * 16 * (s * 4 * 4 / 16)             # rows here
           + 3 * s * d * 24)                           # the shared expert
    macs = 2 * (gdn + moe) + (attn + moe) + s * d * 128
    assert family.train_flops_per_example(arch) == 6.0 * macs
    # the chunked form, chunk by chunk and value head by value head: 2
    # layers x 4 heads x 3 chunks of 16; the inverse's 3 squarings
    units, c, dk, dv = 2 * 4 * 3, 16, 16, 16
    assert math.ceil(math.log2(c)) - 1 == 3
    fwd = 2 * c * c * dk + 2 * c * c * dv + 3 * c * dk * dv + 2 * 3 * c ** 3
    bwd = 6 * c * c * dk + 5 * c * c * dv + 8 * c * dk * dv + 2 * 3 * c ** 3
    assert family.gdn_ops(arch) == 2 * units * fwd
    assert family.gdn_ops(arch, backward=True) == 2 * units * bwd
    # bytes: q, k, v in bf16, beta G R and two rows in float32, the output
    # in bf16, the three chunks' states in float32 a value head
    inputs = s * (2 * 32 + 64) * 2 + s * 4 * 5 * 4
    states = 4 * 3 * 16 * 16 * 4
    assert family.gdn_bytes(arch) == 2 * (inputs + s * 64 * 2 + states)
    grads = s * 4 * (16 + 16 + 16) * 4 + s * 4 * 3 * 4
    assert family.gdn_bytes(arch, backward=True) == 2 * (
        inputs + states + s * 64 * 2 + grads)


def test_the_cells_counts_are_the_programs_and_a_hand_count():
    from tpu_resnet.config import load_config
    from tpu_resnet.models import family as program_family

    arch = Manifest().config_of(REAL)["model"]
    assert family.forward_macs_per_token(arch) == 213_463_040
    assert family.train_flops_per_example(arch) == pytest.approx(5.246e12,
                                                                 rel=1e-3)
    cfg = load_config("qwen3_next_80b_a3b_ep16")
    assert program_family(cfg).train_flops_per_example(cfg) == \
        family.train_flops_per_example(arch)
    # the roofline sets the forward kernel's rows against two runs a step
    assert arch["remat"] is cfg.model.remat is True
    # three DeltaNet mixers are half the work: 49.6 %
    share = 3 * (2048 * 12288 + 2048 * 64 + 8192 * 4 + 3 * 128 * 4096
                 + 4096 * 2048) / 213_463_040
    assert share == pytest.approx(0.496, abs=5e-4)
    # the forward kernel at chunks of 128: 19 x 2^21 multiply-adds a chunk
    # and value head, 3 x 32 x 32 of them a sequence
    assert family.gdn_ops(arch) == 2 * 3 * 32 * 32 * 19 * 2 ** 21
    assert family.gdn_ops(arch, backward=True) == \
        2 * 3 * 32 * 32 * 31 * 2 ** 21
    # compute-bound both ways on a v5e (197 TFLOP/s, 819 GB/s)
    for backward in (False, True):
        assert family.gdn_ops(arch, backward) / 197e12 > \
            family.gdn_bytes(arch, backward) / 819e9


# -------------------------------------------------------------- the readers
def test_readers_return_numbers_and_find_nothing_where_nothing_is():
    m = Manifest()
    chunks = m.reader("gdn_doc_chunks_pct")
    assert chunks(SimpleNamespace(records=[{"loss": 1.0}])) is None
    assert chunks(SimpleNamespace(records=[])) is None
    assert chunks(SimpleNamespace(records=[
        {"gdn_doc_chunks_frac": 0.1}, {"gdn_doc_chunks_frac": 0.2}])
    ) == pytest.approx(15.0)
    arch = m.config_of(REAL)["model"]
    peak = peaks.peaks_for("TPU v5 lite")
    rows = [["fusion", 9.0], ["gated_delta_bwd", 0.6],
            ["gated_delta_fwd", 0.6]]
    on = SimpleNamespace(trace={"device_ops": rows}, peaks=peak, images=200,
                         chips=1, arch=arch)
    fwd, bwd = m.reader("gdn_fwd_roofline_pct"), m.reader(
        "gdn_bwd_roofline_pct")
    assert fwd(on) == pytest.approx(
        2 * 100 * family.gdn_ops(arch) * 200 / 197e12 / 0.6)
    assert bwd(on) == pytest.approx(
        100 * family.gdn_ops(arch, True) * 200 / 197e12 / 0.6)
    assert 0 < fwd(on) < 100 and 0 < bwd(on) < 100
    once = SimpleNamespace(**{**vars(on), "arch": {**arch, "remat": False}})
    assert fwd(once) == pytest.approx(fwd(on) / 2)
    assert bwd(once) == pytest.approx(bwd(on))
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
    assert "bias_gap" not in line["compared"]
    assert "CONTROL fp8 correct=False" in capfd.readouterr().err


def test_traced_run_reports_every_per_layer_metric(manifest):
    line = run(manifest, 23, True)
    assert set(line["metrics"]) == {m["name"]
                                    for m in manifest.per_layer(CELL)}
    # documents of a median 12 ids in sequences of 32, each one chunk:
    # nearly every chunk holds a start after its first position
    assert 80 < line["metrics"]["gdn_doc_chunks_pct"]["value"] <= 100
    assert line["correct"] is True, line["compared"]


@pytest.mark.parametrize("fault", [
    faults.state_unchanged, family.state_unchanged, family.half_batch,
    faults.loss_altered])
def test_planted_fault_is_not_correct(manifest, fault):
    line = run(manifest, 17, False, fault=fault)
    assert line["correct"] is False, (fault.__name__, line["compared"])
