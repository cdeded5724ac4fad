"""Memory observability plane (tpu_resnet/obs/memory.py + the golden
memory-budget engine analysis/memorybudget.py): compiled-program HBM
ledger, live device-memory gauges, OOM forensics, and the trace-export
device/memory lanes.

Layout mirrors test_mfu.py (the time twin): unit coverage on the
extraction/gauge/report primitives, a fast golden-subset gate against
the checked-in analysis/golden_memory.json (one cheap rn8 compile; the
full 31-entry verify lives in the slow tier), and an in-process
loop drill proving gauges → metrics.jsonl and the RESOURCE_EXHAUSTED →
oom_report.json closer chain.
"""

import gzip
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_resnet.analysis import memorybudget
from tpu_resnet.analysis.configmatrix import MATRIX
from tpu_resnet.config import load_config
from tpu_resnet.obs import memory
from tpu_resnet.obs.trace import (build_trace, export_trace,
                                  find_device_trace_files, validate_trace)
from tpu_resnet.resilience import faultinject

RN8 = next(e for e in MATRIX if e.name == "cifar10_rn8_f32")


# ----------------------------------------------------- budget extraction

def test_budget_from_compiled_donation_credited():
    """The ledger's core contract: a donated input shows up as
    alias_bytes (the donation credit) and peak_bytes counts each aliased
    byte ONCE — broken donation would collapse alias to ~0 and
    double-buffer the state."""
    state = jnp.zeros((256, 256), jnp.float32)  # 256 KiB
    x = jnp.ones((256, 256), jnp.float32)

    def step(s, v):
        return s + v, (s * v).mean()

    donated = jax.jit(step, donate_argnums=(0,)).lower(state, x).compile()
    plain = jax.jit(step).lower(state, x).compile()
    b_don = memory.budget_from_compiled(donated)
    b_plain = memory.budget_from_compiled(plain)
    nbytes = 256 * 256 * 4
    assert b_don["argument_bytes"] >= 2 * nbytes
    assert b_don["alias_bytes"] >= nbytes  # the donated state buffer
    assert b_plain["alias_bytes"] < nbytes  # no donation, no credit
    for b in (b_don, b_plain):
        assert b["peak_bytes"] == (b["argument_bytes"] + b["output_bytes"]
                                   - b["alias_bytes"] + b["temp_bytes"]
                                   + b["generated_code_bytes"])
    # donated-in bytes not double-counted: the donated program's peak is
    # smaller by (about) the aliased state buffer
    assert b_don["peak_bytes"] <= b_plain["peak_bytes"]


def test_budget_from_compiled_degrades_to_none():
    class NoAnalysis:
        def memory_analysis(self):
            raise NotImplementedError("backend has no memory analysis")

    class NoneAnalysis:
        def memory_analysis(self):
            return None

    assert memory.budget_from_compiled(NoAnalysis()) is None
    assert memory.budget_from_compiled(NoneAnalysis()) is None


def test_ledger_save_load_roundtrip(tmp_path):
    ledger = memory.MemoryLedger()
    entry = ledger.register("train|x|mesh1x1|b8",
                            {"argument_bytes": 10, "temp_bytes": 5},
                            global_batch=8)
    assert entry["budget_source"] == "xla_memory_analysis"
    assert ledger.register("none|key", None)["budget_source"] == "none"
    path = ledger.save(str(tmp_path))
    assert os.path.basename(path) == "memory.json"
    loaded = memory.MemoryLedger.load(str(tmp_path))
    assert loaded.keys() == ["none|key", "train|x|mesh1x1|b8"]
    assert loaded.get("train|x|mesh1x1|b8")["temp_bytes"] == 5
    assert memory.MemoryLedger.load(str(tmp_path / "nope")).keys() == []


# ------------------------------------------------------- capacity table

def test_hbm_bytes_per_chip_table_and_override(monkeypatch):
    gib = 1024 ** 3
    assert memory.hbm_bytes_per_chip("TPU v5e") == 16 * gib
    assert memory.hbm_bytes_per_chip("TPU v5 lite") == 16 * gib
    assert memory.hbm_bytes_per_chip("TPU v5p chip") == 95 * gib
    assert memory.hbm_bytes_per_chip("TPU v4") == 32 * gib
    assert memory.hbm_bytes_per_chip("cpu") is None
    assert memory.hbm_bytes_per_chip("") is None
    # no environment variable may assume a capacity for an unknown chip
    monkeypatch.setenv("TPU_RESNET_HBM_BYTES", "1e9")
    assert memory.hbm_bytes_per_chip("cpu") is None


# ----------------------------------------------------------- live gauges

class FakeDev:
    def __init__(self, stats, kind="TPU v5e", id=0):
        self._stats = stats
        self.device_kind = kind
        self.id = id

    def memory_stats(self):
        if isinstance(self._stats, Exception):
            raise self._stats
        return self._stats


def test_sample_device_memory_max_in_use_min_limit():
    devs = [FakeDev({"bytes_in_use": 100, "peak_bytes_in_use": 700,
                     "bytes_limit": 1000}),
            FakeDev({"bytes_in_use": 400, "peak_bytes_in_use": 500,
                     "bytes_limit": 800})]
    out = memory.sample_device_memory(devs)
    assert out["hbm_bytes_in_use"] == 400.0   # max across devices
    assert out["hbm_bytes_peak"] == 700.0
    assert out["hbm_bytes_limit"] == 800.0    # min reported limit
    assert out["hbm_utilization"] == 0.5


def test_sample_device_memory_degrades_to_absent():
    assert memory.sample_device_memory([FakeDev(None)]) == {}
    assert memory.sample_device_memory(
        [FakeDev(RuntimeError("no stats"))]) == {}
    assert memory.sample_device_memory([]) == {}
    # real CPU backend: memory_stats unsupported → {}
    assert memory.sample_device_memory() == {}


def test_sample_device_memory_limit_falls_back_to_table():
    devs = [FakeDev({"bytes_in_use": 8 * 1024 ** 3}, kind="TPU v5e")]
    out = memory.sample_device_memory(devs)
    assert out["hbm_bytes_limit"] == float(16 * 1024 ** 3)
    assert out["hbm_utilization"] == 0.5
    out = memory.sample_device_memory([FakeDev({"bytes_in_use": 5},
                                               kind="weird-chip")])
    assert "hbm_bytes_limit" not in out and "hbm_utilization" not in out


def test_device_memory_detail_and_sample_ring():
    detail = memory.device_memory_detail(
        [FakeDev({"bytes_in_use": 7, "ignored": "str"}, id=3),
         FakeDev(None, kind="cpu", id=4)])
    assert detail[0] == {"id": 3, "device_kind": "TPU v5e",
                         "stats": {"bytes_in_use": 7}}
    assert detail[1]["stats"] is None
    ring = memory.MemorySampleRing(capacity=2)
    ring.add(1, {"hbm_bytes_in_use": 1.0})
    ring.add(2, {})  # empty sample never recorded
    ring.add(3, {"hbm_bytes_in_use": 3.0})
    ring.add(4, {"hbm_bytes_in_use": 4.0})
    snap = ring.snapshot()
    assert [s["step"] for s in snap] == [3, 4]  # capacity evicts oldest
    assert all("wall" in s for s in snap)


# -------------------------------------------------------- OOM forensics

def test_is_oom_error_duck_typing():
    assert memory.is_oom_error(
        RuntimeError("RESOURCE_EXHAUSTED: out of memory"))
    assert not memory.is_oom_error(RuntimeError("some other failure"))
    assert not memory.is_oom_error(ValueError("RESOURCE_EXHAUSTED"))
    assert not memory.is_oom_error(None)

    class XlaRuntimeError(Exception):  # the real class name, any module
        pass

    assert memory.is_oom_error(XlaRuntimeError("RESOURCE_EXHAUSTED: oom"))
    assert not memory.is_oom_error(XlaRuntimeError("INVALID_ARGUMENT"))


def test_live_array_census_buckets_and_cap():
    keep = [jnp.zeros((17, 5), jnp.float32) for _ in range(3)]
    keep.append(jnp.ones((3,), jnp.int32))  # a second, smaller bucket
    census = memory.live_array_census()
    assert census["total_arrays"] >= 3
    assert census["total_bytes"] > 0
    mine = [b for b in census["buckets"]
            if b["shape"] == [17, 5] and b["dtype"] == "float32"]
    assert mine and mine[0]["count"] >= 3
    assert mine[0]["bytes"] >= 3 * 17 * 5 * 4
    # ranked largest-first, cap reported not silent
    sizes = [b["bytes"] for b in census["buckets"]]
    assert sizes == sorted(sizes, reverse=True)
    capped = memory.live_array_census(max_buckets=1)
    assert len(capped["buckets"]) == 1
    assert capped["dropped_buckets"] >= 1
    del keep


def test_write_oom_report_schema_roundtrip(tmp_path):
    ledger = memory.MemoryLedger()
    ledger.register("train|k|mesh1x1|b8", {"argument_bytes": 1})
    path = memory.write_oom_report(
        str(tmp_path), RuntimeError("RESOURCE_EXHAUSTED: injected"),
        context="train", step=12, program_key="train|k|mesh1x1|b8",
        ledger=ledger,
        samples=[{"wall": 1.0, "step": 10, "hbm_bytes_in_use": 5.0}],
        run_id="r-1")
    with open(path) as f:
        report = json.load(f)
    assert memory.validate_oom_report(report) == []
    assert report["step"] == 12 and report["run_id"] == "r-1"
    assert report["ledger"]["train|k|mesh1x1|b8"]["argument_bytes"] == 1
    assert report["memory_samples"][0]["step"] == 10
    assert report["live_arrays"]["total_arrays"] >= 0
    assert isinstance(report["devices"], list) and report["devices"]


def test_validate_oom_report_catches_malformed():
    assert memory.validate_oom_report([]) == ["report is not a JSON object"]
    problems = memory.validate_oom_report({"format": "1"})
    assert any("wrong type" in p for p in problems)
    assert any("missing required key" in p for p in problems)
    good = {"format": 1, "written_at": 1.0, "context": "train",
            "error": {"type": "RuntimeError",
                      "message": "RESOURCE_EXHAUSTED"},
            "ledger": {}, "memory_samples": [], "devices": [],
            "live_arrays": {"buckets": [], "total_arrays": 0,
                            "total_bytes": 0}}
    assert memory.validate_oom_report(good) == []
    bad = dict(good, error={"type": "RuntimeError", "message": "other"})
    assert any("RESOURCE_EXHAUSTED" in p
               for p in memory.validate_oom_report(bad))
    bad = dict(good, memory_samples=[{"wall": 1.0}])
    assert any("memory_samples[0]" in p
               for p in memory.validate_oom_report(bad))
    bad = dict(good, live_arrays={"buckets": [{"shape": [1]}],
                                  "total_arrays": 1, "total_bytes": 4})
    assert any("malformed" in p for p in memory.validate_oom_report(bad))


# ------------------------------------------------------- fault injection

def test_fault_plan_oom_env_and_config():
    rcfg = load_config("smoke").resilience
    plan = faultinject.FaultPlan.from_config(
        rcfg, env={"TPU_RESNET_FAULT_OOM_STEP": "11"})
    assert plan.oom_at_step == 11 and plan.active
    rcfg.inject_oom_at_step = 4
    plan = faultinject.FaultPlan.from_config(rcfg, env={})
    assert plan.oom_at_step == 4 and plan.active


def test_fault_injector_oom_one_shot_and_recognized():
    inj = faultinject.FaultInjector(faultinject.FaultPlan(oom_at_step=5))
    inj.maybe_oom(4)  # before the planned step: nothing
    with pytest.raises(Exception) as exc_info:
        inj.maybe_oom(6)  # first boundary >= plan
    assert memory.is_oom_error(exc_info.value)
    inj.maybe_oom(7)  # one-shot: fired already


# ------------------------------------------- golden memory-budget engine

def test_compare_drift_donation_and_slack():
    want = {"argument_bytes": 10_000_000, "output_bytes": 9_000_000,
            "temp_bytes": 50_000_000, "alias_bytes": 9_000_000,
            "generated_code_bytes": 0}
    assert memorybudget._compare("e", want, dict(want), 0.10) == []
    # inside the band / inside absolute slack: clean
    near = dict(want, temp_bytes=int(50_000_000 * 1.05),
                generated_code_bytes=4096)
    assert memorybudget._compare("e", want, near, 0.10) == []
    # temp doubled: drift finding with the regen hint
    doubled = dict(want, temp_bytes=100_000_000)
    findings = memorybudget._compare("e", want, doubled, 0.10)
    assert len(findings) == 1
    assert findings[0].rule == "golden-memory-drift"
    assert "temp_bytes" in findings[0].message
    assert "--update-golden" in findings[0].message
    # donation collapse gets its own named story
    broken = dict(want, alias_bytes=0)
    findings = memorybudget._compare("e", want, broken, 0.10)
    assert any("donation" in f.message and "double-buffers" in f.message
               for f in findings)
    # alias GROWTH (more donation) is ordinary drift, not the collapse
    grown = dict(want, alias_bytes=18_000_000)
    findings = memorybudget._compare("e", want, grown, 0.10)
    assert findings and all("double-buffers" not in f.message
                            for f in findings)


def test_verify_memory_update_drift_missing_prune(tmp_path, monkeypatch):
    """Engine flow with a stubbed compiler (no XLA cost): update writes
    the golden (tolerance + jax version recorded, stale entries pruned),
    a verify round-trips clean, a mutated budget drifts, a missing entry
    is reported."""
    budget = {"argument_bytes": 1000_000, "output_bytes": 900_000,
              "temp_bytes": 5_000_000, "alias_bytes": 900_000,
              "generated_code_bytes": 0, "peak_bytes": 6_000_000}
    monkeypatch.setattr(memorybudget, "compile_entry_budget",
                        lambda entry: dict(budget))
    golden_path = str(tmp_path / "golden_memory.json")
    # pre-seed a stale entry: update must prune it (golden mirrors MATRIX)
    memorybudget.save_golden(
        {"format": 1, "entries": {"renamed_entry": dict(budget)}},
        golden_path)
    findings, stats = memorybudget.verify_memory(
        entries=(RN8,), update_golden=True, golden_path=golden_path)
    assert findings == [] and stats["updated"] == [RN8.name]
    golden = memorybudget.load_golden(golden_path)
    assert set(golden["entries"]) == {RN8.name}
    assert golden["tolerance"] == memorybudget.DEFAULT_TOLERANCE
    assert golden["jax"] == jax.__version__

    findings, stats = memorybudget.verify_memory(
        entries=(RN8,), golden_path=golden_path)
    assert findings == [] and stats["compared"] == 1

    monkeypatch.setattr(
        memorybudget, "compile_entry_budget",
        lambda entry: dict(budget, temp_bytes=3 * budget["temp_bytes"]))
    findings, _ = memorybudget.verify_memory(entries=(RN8,),
                                             golden_path=golden_path)
    assert [f.rule for f in findings] == ["golden-memory-drift"]

    findings, _ = memorybudget.verify_memory(
        entries=(RN8,), golden_path=str(tmp_path / "empty.json"))
    assert any("no golden memory budget" in f.message for f in findings)


def test_verify_memory_compile_failure_is_per_entry_finding(
        tmp_path, monkeypatch):
    def boom(entry):
        raise RuntimeError("lowering exploded")

    monkeypatch.setattr(memorybudget, "compile_entry_budget", boom)
    findings, stats = memorybudget.verify_memory(
        entries=(RN8,), golden_path=str(tmp_path / "g.json"))
    assert stats["failed"] == 1
    assert [f.rule for f in findings] == ["memory-budget"]


def test_golden_memory_subset_matches_checked_in():
    """Fast tier-1 gate on the REAL goldens: the cheapest matrix entry
    compiles to the committed budget (the full 31-entry verify is the
    slow-tier twin; `tpu-resnet check` runs it for operators)."""
    findings, stats = memorybudget.verify_memory(entries=(RN8,))
    assert findings == [], "\n".join(f.format() for f in findings)
    assert stats["compiled"] == stats["compared"] == 1


def test_donation_breaking_mutation_caught():
    """Acceptance drill: compile the rn8 entry's REAL program with the
    donation deliberately dropped — the checked-in golden must catch it
    as the alias-collapse finding (an undonated state double-buffers
    every parameter and optimizer slot)."""
    import jax.numpy as jnp

    from tpu_resnet.data import augment as aug_lib
    from tpu_resnet.models import build_model
    from tpu_resnet.train import schedule as sched_lib
    from tpu_resnet.train.state import init_state
    from tpu_resnet.train.step import make_train_step

    cfg = RN8.to_config()
    model = build_model(cfg)
    schedule = sched_lib.build_schedule(cfg.optim, cfg.train)
    size = cfg.data.resolved_image_size
    sample = jnp.zeros((1, size, size, 3), jnp.float32)
    state_sds = jax.eval_shape(
        lambda r: init_state(model, cfg.optim, schedule, r, sample),
        jax.random.PRNGKey(0))
    augment_fn, _ = aug_lib.get_augment_fns(cfg.data.dataset)
    base = make_train_step(model, cfg.optim, schedule,
                           cfg.data.num_classes, augment_fn,
                           base_rng=jax.random.PRNGKey(0))
    imgs = jax.ShapeDtypeStruct((RN8.batch, size, size, 3), jnp.uint8)
    labels = jax.ShapeDtypeStruct((RN8.batch,), jnp.int32)
    # The mutation: same program, donation dropped (no donate_argnums).
    mutant = memory.budget_from_compiled(
        jax.jit(base).lower(state_sds, imgs, labels).compile())
    golden = memorybudget.load_golden()["entries"][RN8.name]
    findings = memorybudget._compare(RN8.name, golden, mutant,
                                     memorybudget.DEFAULT_TOLERANCE)
    assert any("donation-credited" in f.message
               and "double-buffers" in f.message for f in findings), \
        "\n".join(f.format() for f in findings)


@pytest.mark.slow
def test_golden_memory_full_matrix_matches_checked_in():
    """The full verify `tpu-resnet check` runs: every traced matrix
    entry compiles to its committed budget (31 real XLA compiles —
    minutes; the default tier keeps the rn8 subset gate)."""
    findings, stats = memorybudget.verify_memory()
    errors = [f for f in findings if f.severity == "error"]
    assert errors == [], "\n".join(f.format() for f in errors)
    assert stats["compared"] == stats["compiled"] >= 25


@pytest.mark.slow  # two live train subprocesses (~90s); the ledger/
# gauge/report plumbing is covered in the default tier above
def test_doctor_mem_probe_contract():
    """doctor --mem-probe: hbm gauge series live in a mid-run scrape,
    memory.json certifies the same program keys as flops.json, and the
    injected RESOURCE_EXHAUSTED leaves a schema-valid oom_report.json
    with a nonempty live-array census."""
    from tpu_resnet.tools.doctor import _check_mem_probe

    out = _check_mem_probe()
    assert out["ok"], out
    assert out["ledger_keys"]
    assert out["oom_rc"] != 0
    assert out["oom_census_buckets"] > 0


# ---------------------------------------------------- loop + serve drill

def test_loop_ledger_gauges_and_oom_report(tmp_path, monkeypatch):
    """In-process loop drill: the memory ledger lands in memory.json
    keyed like flops.json, (monkeypatched) hbm gauges flow into
    metrics.jsonl and the sample ring, and an injected
    RESOURCE_EXHAUSTED leaves a schema-valid oom_report.json carrying
    the ring's history before the exception propagates."""
    from tpu_resnet.train import train

    fake = {"hbm_bytes_in_use": 2.5e9, "hbm_bytes_peak": 3.0e9,
            "hbm_bytes_limit": 16.0e9, "hbm_utilization": 0.1563}
    monkeypatch.setattr(memory, "sample_device_memory", lambda: dict(fake))
    cfg = load_config("smoke")
    cfg.model.name = "mlp"
    cfg.train.train_dir = str(tmp_path / "run")
    cfg.train.train_steps = 40
    cfg.train.global_batch_size = 16
    cfg.train.steps_per_call = 2
    cfg.train.log_every = 2
    cfg.train.summary_every = 2
    cfg.train.checkpoint_every = 50
    cfg.resilience.inject_oom_at_step = 8
    with pytest.raises(Exception) as exc_info:
        train(cfg)
    assert memory.is_oom_error(exc_info.value)  # forensics re-raise

    with open(os.path.join(cfg.train.train_dir, "memory.json")) as f:
        ledger = json.load(f)["entries"]
    with open(os.path.join(cfg.train.train_dir, "flops.json")) as f:
        flops = json.load(f)["entries"]
    with open(os.path.join(cfg.train.train_dir, "comms.json")) as f:
        comms_ledger = json.load(f)["entries"]
    # one key spelling, three times: flops / memory / comms certify the
    # same compiled programs
    assert sorted(ledger) == sorted(flops) == sorted(comms_ledger)
    (entry,) = ledger.values()
    assert entry["argument_bytes"] > 0 and entry["temp_bytes"] > 0
    assert entry["alias_bytes"] > 0  # loop step donates its state
    assert "program" in entry  # which program shape the budget describes
    (comms_entry,) = comms_ledger.values()
    assert comms_entry["comms_source"] == "compiled_hlo"
    # smoke runs on the virtual 8-way data mesh: the gradient sync is on
    # the wire and the prober sees it in the compiled HLO
    assert comms_entry["n_devices"] == 8
    assert comms_entry["collective_count"] > 0
    assert comms_entry["wire_bytes_per_device"] > 0
    assert comms_entry["program"] == entry["program"]

    hbm_records = [r for r in map(
        json.loads, open(os.path.join(cfg.train.train_dir,
                                      "metrics.jsonl")))
        if "hbm_bytes_in_use" in r]
    assert hbm_records, "hbm gauges never reached metrics.jsonl"
    assert hbm_records[0]["hbm_utilization"] == fake["hbm_utilization"]

    with open(os.path.join(cfg.train.train_dir, "oom_report.json")) as f:
        report = json.load(f)
    assert memory.validate_oom_report(report) == []
    assert report["context"] == "train"
    assert report["program_key"] in ledger
    assert report["memory_samples"]  # the ring's pre-OOM history
    assert report["memory_samples"][-1]["hbm_bytes_in_use"] == \
        fake["hbm_bytes_in_use"]


@pytest.mark.slow  # three MLP XLA compiles (~20s); the loop drill below
# covers single-step accounting + the program label in the default tier,
# and the full-matrix slow verify pins the staged-chunk budgets
def test_account_train_step_measures_dispatched_program(tmp_path):
    """The ledger measures the program the input edge actually
    dispatches: the staged-chunk jit (superbatch arguments + scan temps)
    on a stage>1 streaming run, not the single-step twin — and labels
    the variant on the entry."""
    import jax.numpy as jnp

    from tpu_resnet import parallel
    from tpu_resnet.models import build_model
    from tpu_resnet.train import build_schedule, init_state
    from tpu_resnet.train.step import make_train_step

    cfg = load_config("smoke")
    cfg.model.name = "mlp"
    cfg.train.global_batch_size = 16
    mesh = parallel.create_mesh(cfg.mesh)
    model = build_model(cfg)
    sched = build_schedule(cfg.optim, cfg.train)
    rng = jax.random.PRNGKey(0)
    state = init_state(model, cfg.optim, sched, rng,
                       jnp.zeros((1, 32, 32, 3)))
    state = jax.device_put(state, parallel.replicated(mesh))
    step = make_train_step(model, cfg.optim, sched, cfg.data.num_classes,
                           None, base_rng=rng, mesh=mesh)
    single = memory.account_train_step(
        cfg, mesh, state, step, train_dir=str(tmp_path / "single"))
    staged = memory.account_train_step(
        cfg, mesh, state, step, stage_rows=4, chunk_steps=2,
        train_dir=str(tmp_path / "staged"))
    assert single["program"] == "single-step"
    assert staged["program"] == "staged-chunk(steps=2,stage=4)"
    assert single["program_key"] == staged["program_key"]
    # the superbatch arguments are 4 stage rows vs 1 batch — budgets are
    # per-device (the per-shard SPMD module), so the growth is the
    # per-device batch slice times the extra rows
    per_dev_batch_bytes = (16 // mesh.size) * 32 * 32 * 3  # uint8
    assert (staged["argument_bytes"] - single["argument_bytes"]
            >= 3 * per_dev_batch_bytes)
    for entry in (single, staged):
        assert entry["alias_bytes"] > 0  # donation credited on both


def test_serve_note_oom_writes_report_once(tmp_path):
    """The serve closer hook: the FIRST RESOURCE_EXHAUSTED writes the
    forensics artifact (context serve-*, program key naming the bucket
    set and model step), non-OOM failures and repeats don't."""
    import types

    from tpu_resnet.serve.server import PredictServer

    events = []
    fake = types.SimpleNamespace(
        _oom_reported=False,
        cfg=types.SimpleNamespace(train=types.SimpleNamespace(
            train_dir=str(tmp_path))),
        buckets=(8, 16),
        backend=types.SimpleNamespace(model_step=42),
        run_id="r-serve",
        spans=types.SimpleNamespace(
            event=lambda name, **kw: events.append((name, kw))))
    PredictServer.note_oom(fake, ValueError("bad request"))
    assert not os.path.exists(tmp_path / "oom_report.json")
    PredictServer.note_oom(
        fake, RuntimeError("RESOURCE_EXHAUSTED: out of memory"),
        phase="warmup")
    with open(tmp_path / "oom_report.json") as f:
        report = json.load(f)
    assert memory.validate_oom_report(report) == []
    assert report["context"] == "serve-warmup"
    assert report["run_id"] == "r-serve"
    assert "buckets[8, 16]" in report["program_key"]
    assert "step42" in report["program_key"]
    assert events == [("oom", {"phase": "warmup"})]
    # once: a second OOM must not clobber the first report
    os.remove(tmp_path / "oom_report.json")
    PredictServer.note_oom(
        fake, RuntimeError("RESOURCE_EXHAUSTED: again"))
    assert not os.path.exists(tmp_path / "oom_report.json")


# ----------------------------------------------- trace-export lanes

def _synthetic_run_dir(tmp_path, with_hbm=True, with_profiler_span=True):
    d = tmp_path / "run"
    d.mkdir(exist_ok=True)
    t0 = 1700000000.0
    spans = [{"span": "run", "start": t0, "end": t0 + 50,
              "run_id": "r-mem", "pid": 77}]
    if with_profiler_span:
        spans.append({"span": "profiler_trace", "start": t0 + 10,
                      "end": t0 + 20, "run_id": "r-mem", "pid": 77})
    with open(d / "events.jsonl", "w") as f:
        for s in spans:
            f.write(json.dumps(s) + "\n")
    with open(d / "metrics.jsonl", "w") as f:
        for i in range(3):
            rec = {"step": 2 * i, "wall": t0 + 5 + i,
                   "data_wait_sec": 0.1, "steps_per_sec": 5.0}
            if with_hbm:
                rec.update(hbm_bytes_in_use=1e9 + i, hbm_bytes_peak=2e9,
                           hbm_utilization=0.125)
            f.write(json.dumps(rec) + "\n")
    return str(d), t0


def _synthetic_capture(train_dir, name="2026_01_01_00_00_00"):
    cap = os.path.join(train_dir, "profile", "plugins", "profile", name)
    os.makedirs(cap, exist_ok=True)
    payload = {"displayTimeUnit": "ns", "traceEvents": [
        {"ph": "M", "pid": 7, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 7, "tid": 1, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        {"ph": "X", "pid": 7, "tid": 1, "name": "fusion.1",
         "ts": 100.0, "dur": 50.0},
        {"ph": "X", "pid": 7, "tid": 1, "name": "$python_call",
         "ts": 10.0, "dur": 5.0},
        {"ph": "B", "pid": 7, "tid": 1, "name": "unsupported", "ts": 1.0},
    ]}
    path = os.path.join(cap, "host1.trace.json.gz")
    with gzip.open(path, "wt") as f:
        json.dump(payload, f)
    return path


def test_trace_export_device_memory_lane(tmp_path):
    d, t0 = _synthetic_run_dir(tmp_path)
    trace = build_trace(d)
    assert validate_trace(trace) == []
    counters = [e for e in trace["traceEvents"]
                if e["ph"] == "C" and e["name"].startswith("hbm_")]
    assert {e["name"] for e in counters} == {
        "hbm_bytes_in_use", "hbm_bytes_peak", "hbm_utilization"}
    lanes = [e for e in trace["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"]
    assert any(e["args"]["name"] == "device-memory" for e in lanes)
    # all hbm counters ride the dedicated thread
    assert {e["tid"] for e in counters} == {5}
    # interval slices carry the hbm args
    slices = [e for e in trace["traceEvents"]
              if e["name"].startswith("train_interval@")]
    assert slices and all("hbm_bytes_in_use" in s["args"] for s in slices)


def test_trace_export_no_hbm_no_lane(tmp_path):
    d, _ = _synthetic_run_dir(tmp_path, with_hbm=False)
    trace = build_trace(d)
    lanes = [e for e in trace["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"]
    assert not any(e["args"]["name"] == "device-memory" for e in lanes)


def test_trace_export_device_trace_merge(tmp_path):
    d, t0 = _synthetic_run_dir(tmp_path)
    _synthetic_capture(d)
    trace = build_trace(d, device_trace=True)
    assert validate_trace(trace) == []
    meta = trace["metadata"]["device_trace"]
    assert meta["anchored_by"] == "profiler_trace_span"
    assert meta["events"] == 1  # fusion.1 ($-event + B-phase dropped)
    assert meta["python_tracer_events_dropped"] == 1
    assert meta["events_dropped"] == 1
    procs = {e["args"]["name"] for e in trace["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert "device-trace: /device:TPU:0" in procs
    fusion = next(e for e in trace["traceEvents"]
                  if e["name"] == "fusion.1")
    # re-anchored on the profiler_trace span's wall clock: span starts
    # 10s after base, event 100us into the capture
    assert fusion["ts"] == pytest.approx(10e6 + 100.0)
    assert fusion["dur"] == 50.0
    assert fusion["cat"] == "device"
    assert fusion["pid"] >= 9000000  # remapped off the host lanes


def test_trace_export_device_trace_deterministic(tmp_path):
    d, _ = _synthetic_run_dir(tmp_path)
    _synthetic_capture(d)
    p1, _ = export_trace(d, out=str(tmp_path / "a.json"),
                         device_trace=True)
    p2, _ = export_trace(d, out=str(tmp_path / "b.json"),
                         device_trace=True)
    assert open(p1, "rb").read() == open(p2, "rb").read()


def test_trace_export_device_trace_missing_capture(tmp_path):
    d, _ = _synthetic_run_dir(tmp_path)
    with pytest.raises(FileNotFoundError, match="no profiler capture"):
        build_trace(d, device_trace=True)
    # the CLI maps it to exit 1, plain export still works
    assert validate_trace(build_trace(d)) == []


def test_trace_export_device_trace_mtime_anchor(tmp_path):
    """Without a profiler_trace span (out-of-band capture) the file
    mtime end-anchors the window — still deterministic, reported."""
    d, _ = _synthetic_run_dir(tmp_path, with_profiler_span=False)
    path = _synthetic_capture(d)
    os.utime(path, (1700000030.0, 1700000030.0))
    trace = build_trace(d, device_trace=True)
    assert trace["metadata"]["device_trace"]["anchored_by"] == "file_mtime"
    assert validate_trace(trace) == []


def test_newest_capture_wins(tmp_path):
    d, _ = _synthetic_run_dir(tmp_path)
    _synthetic_capture(d, name="2026_01_01_00_00_00")
    newer = _synthetic_capture(d, name="2026_01_02_00_00_00")
    assert find_device_trace_files(d) == [newer]


def test_ledgers_survive_the_default_streaming_shape(tmp_path):
    """The streaming defaults are transfer_stage=8 < steps_per_call=10:
    the loop then dispatches 8-step chunks, and the memory/comms ledgers
    must account THAT program. (They asked for a 10-step chunk of an
    8-row superbatch, raised, and were swallowed to a warning — first
    seen on the chip, where chip_smoke.py requires the ledgers.)"""
    from tpu_resnet.train import train

    cfg = load_config("smoke")
    cfg.model.name = "mlp"
    cfg.train.train_dir = str(tmp_path / "run")
    cfg.train.train_steps = 8
    cfg.train.global_batch_size = 16
    cfg.train.log_every = 8
    cfg.train.checkpoint_every = 8
    cfg.data.device_resident = "off"
    assert cfg.data.transfer_stage == 8 and cfg.train.steps_per_call == 10
    train(cfg)
    for name in ("memory.json", "comms.json"):
        with open(os.path.join(cfg.train.train_dir, name)) as f:
            (entry,) = json.load(f)["entries"].values()
        assert entry["program"] == "staged-chunk(steps=8,stage=8)"


# ------------------------------------------------------------- bench hook

def test_bench_hbm_snapshot(monkeypatch):
    import bench

    # CPU: no stats → {} (hbm fields simply absent from bench entries)
    assert bench._hbm_snapshot("cpu") == {}
    sample = {"hbm_bytes_in_use": 10.0e9, "hbm_bytes_peak": 12.0e9}
    monkeypatch.setattr(memory, "sample_device_memory",
                        lambda devices=None: dict(sample))
    out = bench._hbm_snapshot("TPU v5e")
    assert out["hbm_bytes_peak"] == int(12.0e9)
    assert out["hbm_bytes_limit"] == 16 * 1024 ** 3
    assert out["hbm_utilization"] == pytest.approx(
        12.0e9 / (16 * 1024 ** 3), abs=1e-4)
    # stats with an explicit limit win over the table
    monkeypatch.setattr(
        memory, "sample_device_memory",
        lambda devices=None: dict(sample, hbm_bytes_limit=24.0e9))
    assert bench._hbm_snapshot("TPU v5e")["hbm_bytes_limit"] == int(24e9)
