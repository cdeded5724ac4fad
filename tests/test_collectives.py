"""Check engine 5 (analysis/collectives.py + obs/comms.py): the HLO
collective parser (both replica-group spellings, the CPU reduce-scatter
re-derivation), the ring cost model, the golden workflow
(update/drift/missing/prune), the named semantic rules, and the
acceptance drills — the checked-in golden's zero1 twin bytes-ratio, the
collective-free serve bucket, and the no-wsc mutant that must fail
loudly."""

import json
import os
import subprocess
import sys

import pytest

from tpu_resnet.analysis import collectives, configmatrix, memorybudget
from tpu_resnet.analysis.configmatrix import MATRIX
from tpu_resnet.obs import comms

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BY_NAME = {e.name: e for e in MATRIX}
RN8 = BY_NAME["cifar10_rn8_f32"]
MESH8 = BY_NAME["cifar10_rn8_f32_mesh8"]
ZERO1 = BY_NAME["cifar10_rn8_f32_mesh8_zero1"]
ZERO1_MESH1 = BY_NAME["cifar10_rn8_f32_zero1_mesh1"]
MESH4X2 = BY_NAME["cifar10_rn8_f32_mesh4x2"]
SERVE = next(e for e in MATRIX if e.builder == "serve")


def _summary(**over):
    """A minimal clean comms summary; override per test."""
    base = {"mesh": "1x1", "collective_count": 0, "ops": {},
            "structure": {}, "bytes_by_axis": {},
            "wire_bytes_per_device": 0, "all_gather_bytes": 0,
            "reduce_scatter_bytes": 0, "plain_all_reduce_bytes": 0,
            "partition": "replicated", "params_argument_bytes": 312424}
    base.update(over)
    return base


# ------------------------------------------------------------ HLO parser

# Handcrafted post-SPMD HLO exercising every parser path at once: an
# all-reduce whose single consumer keeps 1/8 of the payload (the CPU
# reduce-scatter decomposition), an iota-form all-gather, a tuple
# all-reduce over the implicit full mesh, and a collective-permute.
HLO = """\
HloModule test

%sum (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add = f32[] add(%a, %b)
}

ENTRY %main (p0: f32[64]) -> f32[8] {
  %p0 = f32[64]{0} parameter(0)
  %ar.1 = f32[64]{0} all-reduce(%p0), replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%sum
  %ds.1 = f32[8]{0} dynamic-slice(%ar.1, %c0), dynamic_slice_sizes={8}
  %ag.1 = f32[64]{0} all-gather(%ds.1), replica_groups=[1,8]<=[8], dimensions={0}
  %ar.2 = (f32[16]{0}, f32[16]{0}) all-reduce(%ag.1, %ag.1), replica_groups={}, to_apply=%sum
  ROOT %cp.1 = f32[8]{0} collective-permute(%ds.1), source_target_pairs={{0,1},{1,0}}
}
"""


def test_iota_groups_expansion():
    # the [2,4]<=[4,2]T(1,0) spelling of a 4x2 mesh's data-axis groups
    assert comms._iota_groups(2, 4, [4, 2], [1, 0]) == \
        [(0, 2, 4, 6), (1, 3, 5, 7)]
    assert comms._iota_groups(2, 4, [8], None) == \
        [(0, 1, 2, 3), (4, 5, 6, 7)]


def test_parse_groups_every_spelling():
    assert comms._parse_groups("replica_groups={{0,2},{1,3}}", 4) == \
        [(0, 2), (1, 3)]
    assert comms._parse_groups("replica_groups=[2,4]<=[4,2]T(1,0)", 8) \
        == [(0, 2, 4, 6), (1, 3, 5, 7)]
    # empty groups = one group of every device
    assert comms._parse_groups("replica_groups={}", 4) == [(0, 1, 2, 3)]
    assert comms._parse_groups("source_target_pairs={{0,1},{1,0}}", 4) \
        == [(0, 1), (1, 0)]
    # no annotation at all: same full-mesh default
    assert comms._parse_groups("channel_id=1", 2) == [(0, 1)]


def test_classify_groups_buckets():
    # 4x2 mesh, row-major ("data","model") device order
    assert comms.classify_groups([(0, 2, 4, 6), (1, 3, 5, 7)], 4, 2) \
        == "data"
    assert comms.classify_groups([(0, 1), (2, 3), (4, 5), (6, 7)], 4, 2) \
        == "model"
    assert comms.classify_groups([tuple(range(8))], 4, 2) == "all"
    # both coordinates vary without covering the mesh: the violation
    assert comms.classify_groups([(0, 3)], 4, 2) == "mixed"
    assert comms.classify_groups([(0,)], 4, 2) == "self"
    # 1-D mesh: the full mesh is the data axis, never "all"
    assert comms.classify_groups([tuple(range(8))], 8, 1) == "data"


def test_type_bytes_and_dtype():
    assert comms._type_bytes("f32[3,3,16,16]{3,2,1,0}") == 9216
    assert comms._type_bytes("(f32[16]{0}, u8[4]{0})") == 68
    assert comms._type_bytes("f32[]") == 4
    assert comms._type_dtype("bf16[8,8]{1,0}") == "bf16"


def test_ring_wire_bytes():
    assert comms._ring_wire_bytes("all-reduce", 800, 8) == 1400.0
    assert comms._ring_wire_bytes("all-gather", 800, 8) == 700.0
    assert comms._ring_wire_bytes("reduce-scatter", 800, 8) == 700.0
    assert comms._ring_wire_bytes("collective-permute", 800, 2) == 800.0
    assert comms._ring_wire_bytes("all-reduce", 800, 1) == 0.0


def test_extract_collectives_handcrafted_hlo():
    cols = {c.name: c for c in comms.extract_collectives(HLO, 8, 1)}
    assert set(cols) == {"ar.1", "ag.1", "ar.2", "cp.1"}
    # ar.1's only consumer keeps 32 <= ceil(256/8)+4 bytes: the CPU
    # decomposer's all-reduce is re-derived as the logical reduce-scatter
    assert cols["ar.1"].op == "reduce-scatter"
    assert cols["ar.1"].raw_op == "all-reduce"
    assert cols["ar.1"].payload_bytes == 256
    assert cols["ar.1"].wire_bytes == 224.0
    assert cols["ag.1"].op == "all-gather"
    assert cols["ag.1"].group_size == 8 and cols["ag.1"].bucket == "data"
    # tuple all-reduce (combined small reductions) stays plain
    assert cols["ar.2"].op == "all-reduce"
    assert cols["ar.2"].payload_bytes == 128
    assert cols["cp.1"].op == "collective-permute"
    assert cols["cp.1"].wire_bytes == 32.0
    assert cols["ar.1"].signature() == "reduce-scatter|f32:256b|data|g8"


def test_extract_literal_reduce_scatter_payload_from_operand():
    """On TPU the literal op appears: the logical payload is the full
    OPERAND, not the sharded result."""
    hlo = ("ENTRY %main (p0: f32[64]) -> f32[8] {\n"
           "  %p0 = f32[64]{0} parameter(0)\n"
           "  ROOT %rs.1 = f32[8]{0} reduce-scatter(f32[64]{0} %p0), "
           "replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}, "
           "to_apply=%sum\n}\n")
    (c,) = comms.extract_collectives(hlo, 8, 1)
    assert c.op == c.raw_op == "reduce-scatter"
    assert c.payload_bytes == 256 and c.wire_bytes == 224.0


def test_tpu_tiled_layouts_do_not_hide_tuple_collectives():
    """TPU HLO prints tiling inside layout braces (``T(8,128)``): a tuple
    type then holds parentheses, and a parser that cut it at the first
    ")" saw no collective at all in a four-chip program (comms.json read
    collective_count 0 on the chip)."""
    hlo = ("ENTRY %main (p0: f32[512,128]) -> f32[512,128] {\n"
           "  %p0 = f32[512,128]{1,0:T(8,128)} parameter(0)\n"
           "  %p1 = bf16[128]{0:T(256)(128)(2,1)} parameter(1)\n"
           "  %ar.9 = (f32[512,128]{1,0:T(8,128)}, "
           "bf16[128]{0:T(256)(128)(2,1)S(1)}) all-reduce(%p0, %p1), "
           "channel_id=3, replica_groups=[1,4]<=[4], "
           "use_global_device_ids=true, to_apply=%sum\n"
           "  ROOT %g = f32[512,128]{1,0:T(8,128)} "
           "get-tuple-element(%ar.9), index=0\n}\n")
    (c,) = comms.extract_collectives(hlo, 4, 1)
    assert c.op == "all-reduce" and c.group_size == 4
    assert c.payload_bytes == 512 * 128 * 4 + 128 * 2
    assert c.bucket == "data"


def test_combined_all_reduce_splits_into_scattered_and_plain_parts():
    """XLA's all-reduce combiner merges many gradient reductions into ONE
    tuple op; under ZeRO-1 on a CPU compile the elements whose consumers
    keep only a shard are the decomposed reduce-scatter, the others (BN
    moments) stay plain — judged per element, through its
    get-tuple-element."""
    hlo = ("ENTRY %main (p0: f32[64], p1: f32[16]) -> f32[8] {\n"
           "  %p0 = f32[64]{0} parameter(0)\n"
           "  %p1 = f32[16]{0} parameter(1)\n"
           "  %ar.7 = (f32[64]{0}, f32[16]{0}) all-reduce(%p0, %p1), "
           "replica_groups=[1,8]<=[8], to_apply=%sum\n"
           "  %gte.0 = f32[64]{0} get-tuple-element(%ar.7), index=0\n"
           "  %gte.1 = f32[16]{0} get-tuple-element(%ar.7), index=1\n"
           "  %ds.0 = f32[8]{0} dynamic-slice(%gte.0, %c0), "
           "dynamic_slice_sizes={8}\n"
           "  %full = f32[16]{0} multiply(%gte.1, %gte.1)\n"
           "  ROOT %out = f32[8]{0} add(%ds.0, %ds.0)\n}\n")
    got = sorted((c.op, c.payload_bytes)
                 for c in comms.extract_collectives(hlo, 8, 1))
    assert got == [("all-reduce", 64), ("reduce-scatter", 256)]
    s = comms.summarize_collectives(hlo, 8, 1)
    assert s["reduce_scatter_bytes"] == 256
    assert s["plain_all_reduce_bytes"] == 64


def test_summarize_collectives_budget():
    s = comms.summarize_collectives(HLO, 8, 1)
    assert s["mesh"] == "8x1" and s["collective_count"] == 4
    assert s["ops"] == {"all-gather": 1, "all-reduce": 1,
                        "collective-permute": 1, "reduce-scatter": 1}
    assert s["reduce_scatter_bytes"] == 256
    assert s["all_gather_bytes"] == 256
    assert s["plain_all_reduce_bytes"] == 128
    assert s["wire_bytes_per_device"] == 704
    assert s["bytes_by_axis"] == {"data": 704}
    assert sum(s["structure"].values()) == 4


def test_ici_table_and_override(monkeypatch):
    monkeypatch.delenv("TPU_RESNET_ICI_BYTES", raising=False)
    assert comms.ici_bytes_per_chip("TPU v5 lite") == 1600 * 1e9 / 8
    assert comms.ici_bytes_per_chip("TPU v5p chip") == 4800 * 1e9 / 8
    assert comms.ici_bytes_per_chip("cpu") is None
    assert comms.ici_bytes_per_chip("") is None
    monkeypatch.setenv("TPU_RESNET_ICI_BYTES", "1e9")
    assert comms.ici_bytes_per_chip("cpu") == 1e9
    monkeypatch.setenv("TPU_RESNET_ICI_BYTES", "not-a-number")
    assert comms.ici_bytes_per_chip("TPU v4") == 2400 * 1e9 / 8


def test_predicted_time_on_wire(monkeypatch):
    monkeypatch.setenv("TPU_RESNET_ICI_BYTES", "1000000.0")
    s = _summary(wire_bytes_per_device=500000)
    assert comms.predicted_time_on_wire(s, "cpu") == 0.5
    monkeypatch.delenv("TPU_RESNET_ICI_BYTES")
    assert comms.predicted_time_on_wire(s, "cpu") is None
    assert comms.predicted_time_on_wire(None, "TPU v4") is None


def test_comms_ledger_roundtrip(tmp_path):
    led = comms.CommsLedger()
    led.register("train/cifar10/rn8", _summary(), program="single-step")
    led.register("no-hlo-key", None)
    path = led.save(str(tmp_path))
    assert path and os.path.exists(path)
    back = comms.CommsLedger.load(str(tmp_path))
    assert back.keys() == ["no-hlo-key", "train/cifar10/rn8"]
    entry = back.get("train/cifar10/rn8")
    assert entry["comms_source"] == "compiled_hlo"
    assert entry["program"] == "single-step"
    assert back.get("no-hlo-key")["comms_source"] == "none"


# -------------------------------------------------------- golden compare

def test_compare_structure_exact_and_bytes_banded():
    want = _summary(ops={"all-reduce": 2},
                    structure={"all-reduce|f32:256b|data|g8": 2},
                    bytes_by_axis={"data": 1_000_000},
                    wire_bytes_per_device=1_000_000,
                    plain_all_reduce_bytes=500_000)
    assert collectives._compare("e", want, dict(want), 0.10) == []
    # inside the band / absolute slack: clean
    near = dict(want, wire_bytes_per_device=1_050_000,
                reduce_scatter_bytes=4000)
    assert collectives._compare("e", want, near, 0.10) == []
    # structure compares EXACTLY — one recount is a drift
    moved = dict(want, structure={"all-reduce|f32:256b|data|g8": 1,
                                  "all-gather|f32:256b|data|g8": 1})
    findings = collectives._compare("e", want, moved, 0.10)
    assert any(f.rule == "golden-collectives-drift"
               and "structure" in f.message and "added" in f.message
               for f in findings)
    # byte totals band: a doubled wire budget is a drift with the hint
    doubled = dict(want, wire_bytes_per_device=2_000_000)
    findings = collectives._compare("e", want, doubled, 0.10)
    assert len(findings) == 1
    assert "--update-golden" in findings[0].message
    # traffic moving BETWEEN axes is its own story
    shifted = dict(want, bytes_by_axis={"model": 1_000_000})
    findings = collectives._compare("e", want, shifted, 0.10)
    assert any("mesh axis" in f.message for f in findings)


# ---------------------------------------------------------- named rules

def test_rule_collective_free_serve():
    assert collectives._rule_collective_free_serve(SERVE, _summary()) == []
    findings = collectives._rule_collective_free_serve(
        SERVE, _summary(collective_count=2, ops={"all-reduce": 2}))
    assert [f.rule for f in findings] == ["collective-free-serve"]
    assert "fleet-wide hang" in findings[0].message
    # train rows are exempt whatever they contain
    assert collectives._rule_collective_free_serve(
        MESH8, _summary(collective_count=2)) == []


def test_rule_stray_gather():
    params = 312424
    ok = _summary(params_argument_bytes=params, all_gather_bytes=1000)
    assert collectives._rule_stray_gather(MESH8, ok) == []
    bad = _summary(params_argument_bytes=params,
                   all_gather_bytes=int(0.5 * params))
    findings = collectives._rule_stray_gather(MESH8, bad)
    assert [f.rule for f in findings] == ["stray-gather"]
    assert "ZeRO-bloat" in findings[0].message
    # zero1 rows legitimately gather the param footprint; serve rows
    # are owned by collective-free-serve
    assert collectives._rule_stray_gather(ZERO1, bad) == []
    assert collectives._rule_stray_gather(SERVE, bad) == []


def test_rule_axis_confinement():
    clean = _summary(bytes_by_axis={"data": 9999999, "model": 5000})
    assert collectives._rule_axis_confinement(MESH4X2, clean) == []
    bad = _summary(bytes_by_axis={"data": 10, "mixed": 8192})
    findings = collectives._rule_axis_confinement(MESH4X2, bad)
    assert [f.rule for f in findings] == ["axis-confinement"]
    # 1-D meshes have no second axis to violate
    assert collectives._rule_axis_confinement(MESH8, bad) == []


def test_rule_zero1_exchange():
    params = 312424
    good = _summary(partition="zero1", params_argument_bytes=params,
                    reduce_scatter_bytes=315372, all_gather_bytes=317184,
                    plain_all_reduce_bytes=40)
    twin = _summary(plain_all_reduce_bytes=315304)
    assert collectives._rule_zero1_exchange(ZERO1, good, twin) == []
    # missing exchange (the gradient all-reduce stayed plain): both
    # floors fire AND the twin ceiling catches the un-replaced traffic
    missing = _summary(partition="zero1", params_argument_bytes=params,
                       plain_all_reduce_bytes=315304)
    findings = collectives._rule_zero1_exchange(ZERO1, missing, twin)
    assert len(findings) == 3  # rs floor, ag floor, plain not replaced
    assert all(f.rule == "zero1-exchange" for f in findings)
    # ...the plain ceiling needs the twin; floors alone without it
    assert len(collectives._rule_zero1_exchange(ZERO1, missing, None)) == 2
    # plain all-reduce riding ALONGSIDE the exchange
    riding = dict(good, plain_all_reduce_bytes=315304)
    findings = collectives._rule_zero1_exchange(ZERO1, riding, twin)
    assert len(findings) == 1 and "REPLACE" in findings[0].message
    # zero1 on a 1-way data axis is the replicated identity: exempt
    assert collectives._rule_zero1_exchange(ZERO1_MESH1, missing,
                                            twin) == []


# ------------------------------------------------- verify flow (stubbed)

def test_verify_collectives_update_drift_missing_prune(tmp_path,
                                                       monkeypatch):
    """Engine flow with a stubbed compiler: update writes the golden
    (tolerance + jax version recorded, stale entries pruned), a verify
    round-trips clean, a mutated structure drifts, a missing entry is
    reported."""
    import jax

    monkeypatch.setattr(collectives, "entry_comms_summary",
                        lambda entry: _summary())
    golden_path = str(tmp_path / "golden_collectives.json")
    collectives.save_golden(
        {"format": 1, "entries": {"renamed_entry": _summary()}},
        golden_path)
    findings, stats = collectives.verify_collectives(
        entries=(RN8,), update_golden=True, golden_path=golden_path)
    assert findings == [] and stats["updated"] == [RN8.name]
    golden = collectives.load_golden(golden_path)
    assert set(golden["entries"]) == {RN8.name}
    assert golden["tolerance"] == collectives.DEFAULT_TOLERANCE
    assert golden["jax"] == jax.__version__

    findings, stats = collectives.verify_collectives(
        entries=(RN8,), golden_path=golden_path)
    assert findings == [] and stats["compared"] == 1

    monkeypatch.setattr(
        collectives, "entry_comms_summary",
        lambda entry: _summary(collective_count=1, ops={"all-gather": 1},
                               structure={"all-gather|f32:256b|data|g8": 1}))
    findings, _ = collectives.verify_collectives(entries=(RN8,),
                                                 golden_path=golden_path)
    assert findings and all(f.rule == "golden-collectives-drift"
                            for f in findings)

    findings, _ = collectives.verify_collectives(
        entries=(RN8,), golden_path=str(tmp_path / "empty.json"))
    assert any("no golden collectives summary" in f.message
               for f in findings)


def test_verify_collectives_rules_run_under_update(tmp_path, monkeypatch):
    """--update-golden can never bake a violation into the golden: the
    semantic rules run in update mode too."""
    monkeypatch.setattr(
        collectives, "entry_comms_summary",
        lambda entry: _summary(collective_count=1, ops={"all-reduce": 1}))
    findings, _ = collectives.verify_collectives(
        entries=(SERVE,), update_golden=True,
        golden_path=str(tmp_path / "g.json"))
    assert [f.rule for f in findings] == ["collective-free-serve"]


def test_verify_collectives_compile_failure_is_per_entry(tmp_path,
                                                         monkeypatch):
    def boom(entry):
        raise RuntimeError("lowering exploded")

    monkeypatch.setattr(collectives, "entry_comms_summary", boom)
    findings, stats = collectives.verify_collectives(
        entries=(RN8,), golden_path=str(tmp_path / "g.json"))
    assert stats["failed"] == 1
    assert [f.rule for f in findings] == ["collectives-budget"]


def test_verify_collectives_zero1_sees_twin(tmp_path, monkeypatch):
    """The two-pass flow: the zero1 row's plain-ceiling gate reads the
    replicated twin's summary compiled in the same run."""
    def fake(entry):
        if entry.partition == "zero1":
            return _summary(partition="zero1",
                            reduce_scatter_bytes=315372,
                            all_gather_bytes=317184,
                            plain_all_reduce_bytes=200_000)  # riding
        return _summary(plain_all_reduce_bytes=315304)

    monkeypatch.setattr(collectives, "entry_comms_summary", fake)
    findings, _ = collectives.verify_collectives(
        entries=(MESH8, ZERO1), update_golden=True,
        golden_path=str(tmp_path / "g.json"))
    assert any(f.rule == "zero1-exchange" and "REPLACE" in f.message
               for f in findings)


# ------------------------------------- checked-in golden acceptance gates

def _checked_in():
    golden = collectives.load_golden()
    assert golden["entries"], "analysis/golden_collectives.json missing"
    return golden["entries"]


def test_checked_in_golden_mirrors_matrix():
    entries = _checked_in()
    live = {e.name for e in MATRIX
            if e.expect_error is None and e.builder != "ctor-bn-axis"}
    assert set(entries) == live
    golden = collectives.load_golden()
    assert golden["format"] == collectives.GOLDEN_FORMAT
    assert "tolerance" in golden and "jax" in golden


def test_checked_in_golden_zero1_twin_bytes_ratio():
    """THE acceptance artifact of the zero1 comms story, gated on the
    committed goldens (no compile): the scattered/gathered bytes each
    cover >= 75% of the param footprint and the plain all-reduce bytes
    collapsed below 50% of the replicated twin's."""
    entries = _checked_in()
    gated = 0
    for e in MATRIX:
        if e.partition != "zero1" or e.data_axis <= 1 \
                or e.name not in entries:
            continue
        z = entries[e.name]
        twin = entries[e.name.replace("_zero1", "")]
        params = z["params_argument_bytes"]
        assert params > 0, e.name
        assert z["reduce_scatter_bytes"] >= \
            collectives.ZERO1_MIN_EXCHANGE_FRACTION * params, e.name
        assert z["all_gather_bytes"] >= \
            collectives.ZERO1_MIN_EXCHANGE_FRACTION * params, e.name
        assert z["plain_all_reduce_bytes"] < \
            collectives.ZERO1_MAX_PLAIN_FRACTION * \
            twin["plain_all_reduce_bytes"], e.name
        gated += 1
    assert gated >= 2  # mesh8 + mesh4x2 zero1 rows at minimum


def test_checked_in_golden_serve_rows_collective_free():
    entries = _checked_in()
    serve = [e for e in MATRIX if e.builder == "serve"
             and e.name in entries]
    assert serve and any(e.name.endswith("_q8") for e in serve)
    for e in serve:
        assert entries[e.name]["collective_count"] == 0, e.name
        assert entries[e.name]["wire_bytes_per_device"] == 0, e.name


def test_checked_in_golden_single_device_rows_are_silent():
    """1x1 rows (RN8 and friends) put nothing on the wire — a
    collective appearing there would be a partitioner leak."""
    entries = _checked_in()
    for e in MATRIX:
        if e.name in entries and e.data_axis * e.model_axis == 1 \
                and e.partition != "zero1":
            assert entries[e.name]["wire_bytes_per_device"] == 0, e.name


# -------------------------------------------- real-compile tier-1 drills

def test_golden_collectives_subset_matches_checked_in():
    """Fast tier-1 gate on the REAL golden: the cheapest matrix entry
    compiles to the committed summary (the full-matrix verify is the
    slow-tier twin; `tpu-resnet check` runs it for operators). Shares
    the per-process compile cache with test_memory's subset gate."""
    findings, stats = collectives.verify_collectives(entries=(RN8,))
    assert findings == [], "\n".join(f.format() for f in findings)
    assert stats["compiled"] == stats["compared"] == 1


def test_update_golden_reproduces_checked_in_entries(tmp_path):
    """The satellite-6 byte-stability contract at entry granularity:
    regenerating RN8's goldens through all three engines reproduces the
    committed entries EXACTLY — `check --update-golden` must never churn
    entries whose programs did not change."""
    cases = (
        (configmatrix.verify_matrix, configmatrix, "golden_jaxprs.json"),
        (memorybudget.verify_memory, memorybudget, "golden_memory.json"),
        (collectives.verify_collectives, collectives,
         "golden_collectives.json"),
    )
    for verify, mod, fname in cases:
        path = str(tmp_path / fname)
        findings, _ = verify(entries=(RN8,), update_golden=True,
                             golden_path=path)
        assert findings == [], fname
        with open(path) as fh:
            fresh = json.load(fh)["entries"][RN8.name]
        with open(os.path.join(os.path.dirname(mod.__file__),
                               fname)) as fh:
            committed = json.load(fh)["entries"][RN8.name]
        assert fresh == committed, fname


def test_no_wsc_mutation_caught():
    """Acceptance drill: compile the mesh8 zero1 entry's REAL program
    with the partitioner's sharding constraints deliberately dropped
    (identity wsc hooks) — the zero1-exchange gate and the checked-in
    golden must both catch it loudly."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from tpu_resnet.data import augment as aug_lib
    from tpu_resnet.models import build_model
    from tpu_resnet.parallel.partition import StatePartitioner
    from tpu_resnet.train import schedule as sched_lib
    from tpu_resnet.train.state import init_state
    from tpu_resnet.train.step import make_train_step, shard_step

    class NoWsc(StatePartitioner):
        """zero1 mode whose constraints never reach the program — the
        regression the engine exists to catch."""

        def constrain_slots(self, tree):
            return tree

        def constrain_opt_state(self, opt_state):
            return opt_state

        def constrain_replicated(self, tree):
            return tree

    cfg = ZERO1.to_config()
    model = build_model(cfg)
    schedule = sched_lib.build_schedule(cfg.optim, cfg.train)
    size = cfg.data.resolved_image_size
    sample = jnp.zeros((1, size, size, 3), jnp.float32)
    state_sds = jax.eval_shape(
        lambda r: init_state(model, cfg.optim, schedule, r, sample),
        jax.random.PRNGKey(0))
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(8, 1),
                ("data", "model"))
    partitioner = NoWsc(mesh, "zero1")
    augment_fn, _ = aug_lib.get_augment_fns(cfg.data.dataset)
    base = make_train_step(model, cfg.optim, schedule,
                           cfg.data.num_classes, augment_fn,
                           base_rng=jax.random.PRNGKey(0), mesh=mesh,
                           partitioner=partitioner)
    imgs = jax.ShapeDtypeStruct((ZERO1.batch, size, size, 3), jnp.uint8)
    labels = jax.ShapeDtypeStruct((ZERO1.batch,), jnp.int32)
    # replicated inputs: exactly what "constraints not reaching the
    # compiled program" produces end to end
    compiled = shard_step(base, mesh).lower(state_sds, imgs,
                                            labels).compile()
    text = comms.hlo_text_of(compiled)
    assert text is not None
    summary = comms.summarize_collectives(text, 8, 1)
    summary["partition"] = "zero1"
    golden = collectives.load_golden()["entries"]
    summary["params_argument_bytes"] = \
        golden[ZERO1.name]["params_argument_bytes"]
    twin = golden[ZERO1.name.replace("_zero1", "")]
    findings = collectives._rule_zero1_exchange(ZERO1, summary, twin)
    assert any(f.rule == "zero1-exchange" for f in findings), \
        "the dropped-wsc mutant must fail the exchange gate"
    drift = collectives._compare(ZERO1.name, golden[ZERO1.name], summary,
                                 collectives.DEFAULT_TOLERANCE)
    assert any(f.rule == "golden-collectives-drift"
               and "structure" in f.message for f in drift), \
        "\n".join(f.format() for f in drift)


@pytest.mark.slow
def test_golden_collectives_full_matrix_matches_checked_in():
    """The full verify `tpu-resnet check` runs: every traced matrix
    entry's collective summary matches its committed golden (shares the
    memory engine's compile cache when both slow tests run in one
    process)."""
    findings, stats = collectives.verify_collectives()
    errors = [f for f in findings if f.severity == "error"]
    assert errors == [], "\n".join(f.format() for f in errors)
    assert stats["compared"] == stats["compiled"] >= 25


# ------------------------------------------------------------ CLI contract

def test_cli_list_rules_names_engine5():
    proc = subprocess.run(
        [sys.executable, "-m", "tpu_resnet", "check", "--list-rules"],
        cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout
    for rule in ("golden-collectives-drift", "stray-gather",
                 "axis-confinement", "collective-free-serve",
                 "zero1-exchange", "collectives-budget",
                 "sharding-scope"):
        assert rule in proc.stdout, rule


def test_cli_unknown_rule_exits_2():
    proc = subprocess.run(
        [sys.executable, "-m", "tpu_resnet", "check", "--skip-matrix",
         "--rules", "no-such-rule"],
        cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, timeout=120)
    assert proc.returncode == 2
    assert "unknown rule" in proc.stdout


def test_cli_skip_collectives_preserves_baseline_entries(tmp_path):
    """A partial run (--skip-matrix implies the collectives engine did
    not run) must MERGE on --write-baseline: accepted engine-5 entries
    survive verbatim instead of being silently deleted."""
    fixtures = os.path.join(REPO, "tests", "fixtures", "analysis")
    bl = str(tmp_path / "bl.json")
    with open(bl, "w") as fh:
        json.dump([{"fingerprint": "c" * 16, "rule": "zero1-exchange",
                    "path": "<golden-collectives>/x", "message": "m"},
                   {"fingerprint": "d" * 16,
                    "rule": "golden-collectives-drift",
                    "path": "<golden-collectives>/y", "message": "m"}],
                  fh)
    proc = subprocess.run(
        [sys.executable, "-m", "tpu_resnet", "check", "--skip-matrix",
         "--root", os.path.join(fixtures, "signal_bad"),
         "--baseline", bl, "--write-baseline"],
        cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout
    assert "preserved" in proc.stdout
    with open(bl) as fh:
        rules = {e["rule"] for e in json.load(fh)}
    assert {"zero1-exchange", "golden-collectives-drift",
            "signal-safety"} <= rules
