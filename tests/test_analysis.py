"""`tpu-resnet check` — the static-analysis suite (tpu_resnet/analysis).

Three layers:

- per-rule seeded fixtures (tests/fixtures/analysis/<case>/): each lint
  rule must flag its fixture — including the guard-parity fixture, which
  is the literal PRE-FIX constructor code from ADVICE r4 — and pass on
  the real tree;
- suppression machinery: pragma and baseline round-trips;
- the config-matrix verifier: golden-jaxpr drift detection, must-raise
  guard contracts, engine-invariance twins — and ``test_repo_is_clean``,
  the tier-1 gate that runs the whole suite over the repo.
"""

import json
import os
import subprocess
import sys

import pytest

from tpu_resnet.analysis import (apply_baseline, load_baseline,
                                 run_jaxlint, save_baseline)
from tpu_resnet.analysis import configmatrix
from tpu_resnet.analysis.configmatrix import MATRIX, MatrixEntry
from tpu_resnet.analysis.findings import Finding, pragma_sets

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "analysis")


def fixture_findings(case, rule=None):
    out = run_jaxlint(os.path.join(FIXTURES, case))
    return [f for f in out if rule is None or f.rule == rule]


# ------------------------------------------------------------ rule fixtures
def test_host_sync_fixture_flags_every_hazard():
    found = fixture_findings("host_sync_bad", "jit-host-sync")
    msgs = "\n".join(f.format() for f in found)
    for hazard in ("print", "time.time", "numpy.random", "random.random",
                   ".item()", "jax.device_get", ".block_until_ready()"):
        assert hazard in msgs, f"{hazard} not flagged:\n{msgs}"
    # the @jax.jit function outside the jit-scope modules is found too…
    assert any(f.path == "tpu_resnet/other/misc.py" and f.line == 9
               for f in found)
    # …while plain host functions and clean helpers stay silent
    assert not any(f.line == 15 and f.path.endswith("misc.py")
                   for f in found)
    assert not any("clean_helper" in f.message for f in found)


def test_static_args_fixture():
    found = fixture_findings("static_args_bad", "jit-static-args")
    by_line = {f.line for f in found}
    assert {7, 12, 27, 28, 29, 30} <= by_line, sorted(by_line)
    # covered call sites (static_argnums / static_argnames) are clean
    assert 25 not in by_line and 26 not in by_line
    # float-typed default params trace fine
    assert not any("covered_ok" in f.message or "eps" in f.message
                   for f in found)
    # both sub-checks fired: unhashable container + uncovered bool/str
    msgs = "\n".join(f.message for f in found)
    assert "int or tuple of ints" in msgs
    assert "bool-typed parameter" in msgs
    assert "str-typed parameter" in msgs
    # review fixes: symbolic argnums elements are legal (skip, don't
    # flag); posonly indices align with jax's counting; kwonly bool/str
    # params are still checked (coverable by name only)
    assert not any("symbolic_ok" in f.message or "posonly" in f.message
                   for f in found)
    assert any("kwonly_bad" in f.message and "train" in f.message
               for f in found)


def test_fork_safety_sees_try_nested_imports(tmp_path):
    """`try: import tensorflow` at module scope of a worker module runs
    in every spawned worker — must be flagged (review fix: the scan only
    looked at direct children of mod.body)."""
    pkg = tmp_path / "tpu_resnet" / "data"
    pkg.mkdir(parents=True)
    (tmp_path / "tpu_resnet" / "__init__.py").write_text("")
    (pkg / "__init__.py").write_text("")
    (pkg / "engine.py").write_text(
        "try:\n"
        "    import tensorflow\n"
        "except ImportError:\n"
        "    tensorflow = None\n")
    found = [f for f in run_jaxlint(str(tmp_path))
             if f.rule == "fork-safety"]
    assert any("'tensorflow'" in f.message and f.line == 2
               for f in found), found


def test_fork_safety_fixture():
    found = fixture_findings("fork_safety_bad", "fork-safety")
    msgs = "\n".join(f.format() for f in found)
    # transitive jax import with its witness chain
    assert "transitively import 'jax'" in msgs
    assert "engine.py -> tpu_resnet/data/__init__.py" in msgs
    # fork context + module-level lock
    assert "get_context('spawn')" in msgs
    assert "module-level threading.Lock()" in msgs


def test_fork_safety_scans_compound_statements(tmp_path):
    """A module-level lock inside a top-level try: that ALSO contains a
    def must still be flagged (review fix: ast.walk + break aborted the
    whole compound statement's subtree at the first nested def)."""
    pkg = tmp_path / "tpu_resnet" / "data"
    pkg.mkdir(parents=True)
    (tmp_path / "tpu_resnet" / "__init__.py").write_text("")
    (pkg / "__init__.py").write_text("")
    (pkg / "engine.py").write_text(
        "import threading\n"
        "try:\n"
        "    def helper():\n"
        "        pass\n"
        "    _lock = threading.Lock()\n"
        "except ImportError:\n"
        "    _lock = None\n")
    found = [f for f in run_jaxlint(str(tmp_path))
             if f.rule == "fork-safety"]
    assert any("module-level threading.Lock()" in f.message
               and f.line == 5 for f in found), found
    # locks created inside the def stay exempt (deferred execution)
    (pkg / "engine.py").write_text(
        "import threading\n"
        "def helper():\n"
        "    return threading.Lock()\n")
    assert run_jaxlint(str(tmp_path)) == []


def test_default_files_pins_installed_package(tmp_path):
    """Without a checkout marker beside the package (i.e. installed into
    site-packages), the default scan covers only tpu_resnet/ — never the
    whole environment (review fix)."""
    from tpu_resnet.analysis.cli import _default_files

    pkg = tmp_path / "tpu_resnet"
    pkg.mkdir()
    (pkg / "mod.py").write_text("x = 1\n")
    (tmp_path / "numpy").mkdir()
    (tmp_path / "numpy" / "big.py").write_text("y = 2\n")
    assert _default_files(str(tmp_path)) == ["tpu_resnet/mod.py"]
    # a source checkout lints wholesale (None = engine discovery)
    (tmp_path / "pyproject.toml").write_text("")
    assert _default_files(str(tmp_path)) is None


def test_signal_safety_fixture():
    found = fixture_findings("signal_bad", "signal-safety")
    msgs = "\n".join(f.message for f in found)
    for hazard in ("self._ckpt.save", "self._lock.acquire", "'open'",
                   "time.sleep"):
        assert hazard in msgs, f"{hazard} not flagged:\n{msgs}"
    # the transitive chain through _finalize is reported
    assert "_handle -> _finalize" in msgs


def test_serve_host_sync_fixture():
    """serve/infer.py is jit scope (the serving hot path): host clocks,
    I/O, host RNG and per-call device syncs there are flagged."""
    found = fixture_findings("serve_host_sync_bad", "jit-host-sync")
    msgs = "\n".join(f.format() for f in found)
    for hazard in ("time.perf_counter", "print", "numpy.random",
                   ".block_until_ready()"):
        assert hazard in msgs, f"{hazard} not flagged:\n{msgs}"
    assert all(f.path == "tpu_resnet/serve/infer.py" for f in found)
    assert not any("clean_helper" in f.message for f in found)


def test_epilogue_host_sync_fixture():
    """ops/epilogue.py sits in the ops/* jit scope: the fused-epilogue
    wrappers trace into every train step that enables them, so a host
    clock/RNG/sync seeded there must be flagged."""
    found = fixture_findings("epilogue_host_sync_bad", "jit-host-sync")
    msgs = "\n".join(f.format() for f in found)
    for hazard in ("time.monotonic", "random.random", "jax.device_get",
                   "print"):
        assert hazard in msgs, f"{hazard} not flagged:\n{msgs}"
    assert all(f.path == "tpu_resnet/ops/epilogue.py" for f in found)
    assert not any("clean_fold" in f.message for f in found)


def test_quant_host_sync_fixture():
    """ops/quant.py is jit scope (explicitly listed in JIT_SCOPE_FILES
    on top of the ops/ prefix): fake_quant/dequantize_variables trace
    into every quantized serve bucket program, so a seeded host clock,
    host RNG or device round-trip there must be flagged."""
    from tpu_resnet.analysis.jaxlint import JIT_SCOPE_FILES

    assert "tpu_resnet/ops/quant.py" in JIT_SCOPE_FILES
    found = fixture_findings("quant_host_sync_bad", "jit-host-sync")
    msgs = "\n".join(f.format() for f in found)
    for hazard in ("time.monotonic", "numpy.random", "jax.device_get",
                   "print"):
        assert hazard in msgs, f"{hazard} not flagged:\n{msgs}"
    assert all(f.path == "tpu_resnet/ops/quant.py" for f in found)
    assert not any("clean_dequant" in f.message for f in found)


def test_sweep_measure_host_sync_fixture():
    """tools/sweep_measure.py (the sweep harness's jit-program assembly)
    is jit scope: a host sync baked into the measured programs would
    corrupt every knob's number — the timing loop belongs in sweep.py."""
    found = fixture_findings("sweep_host_sync_bad", "jit-host-sync")
    msgs = "\n".join(f.format() for f in found)
    for hazard in ("time.perf_counter", "numpy.random", ".item()",
                   "print"):
        assert hazard in msgs, f"{hazard} not flagged:\n{msgs}"
    assert all(f.path == "tpu_resnet/tools/sweep_measure.py"
               for f in found)
    assert not any("clean_space" in f.message for f in found)


def test_canonicalize_is_stable_across_processes():
    """The golden hash must not depend on the process: addresses and the
    hash-order of printed frozensets (PYTHONHASHSEED) are normalized."""
    from tpu_resnet.analysis.configmatrix import canonicalize

    a = ("shard_map[manual_axes=frozenset({'model', 'data'}) "
         "fn=<function f at 0x7f01aa>]")
    b = ("shard_map[manual_axes=frozenset({'data', 'model'}) "
         "fn=<function f at 0x55e9bc>]")
    assert canonicalize(a) == canonicalize(b)
    assert "frozenset({'data', 'model'})" in canonicalize(a)
    assert canonicalize("frozenset({'x'})") == "frozenset({'x'})"


def test_mfu_cost_analysis_in_jit_scope_fixture():
    """obs/mfu.py's compile introspection (.cost_analysis()) is a
    one-time host-side startup cost: the rule flags it inside jit-scope
    modules so accounting can never creep into the per-step hot path —
    while the real obs/mfu.py (host-side, outside jit scope) stays
    clean (covered by test_repo_is_clean)."""
    found = fixture_findings("mfu_jit_bad", "jit-host-sync")
    msgs = "\n".join(f.format() for f in found)
    assert ".cost_analysis()" in msgs
    assert "never per step" in msgs
    assert all(f.path == "tpu_resnet/train/step.py" for f in found)


def test_memory_introspection_in_jit_scope_fixture():
    """obs/memory.py's introspection calls (device.memory_stats(),
    jax.live_arrays(), compiled.memory_analysis()) are log-boundary /
    crash-handler host costs: the rule flags all three inside jit-scope
    modules — while the real obs/memory.py (host-side, file pragma with
    justification) stays clean (covered by test_repo_is_clean)."""
    found = fixture_findings("mem_jit_bad", "jit-host-sync")
    msgs = "\n".join(f.format() for f in found)
    for hazard in (".memory_stats()", ".live_arrays()",
                   ".memory_analysis()"):
        assert hazard in msgs, f"{hazard} not flagged:\n{msgs}"
    assert all(f.path == "tpu_resnet/train/step.py" for f in found)


def test_serve_signal_fixture():
    """The serve SIGTERM anti-pattern (drain/teardown inline in the
    handler instead of a flag) is in the signal-safety covered set."""
    found = fixture_findings("serve_signal_bad", "signal-safety")
    msgs = "\n".join(f.message for f in found)
    for hazard in ("self._batcher.drain", "self._httpd.shutdown",
                   "time.sleep", "'open'"):
        assert hazard in msgs, f"{hazard} not flagged:\n{msgs}"
    # the transitive chain through the 'do it now' helper is reported
    assert "_handle -> _drain_now" in msgs


def test_guard_parity_fixture_flags_pre_fix_code():
    """The ADVICE r4 regression: the PRE-fix constructors (no
    _check_fused_bn_axis, no width guard) must all be flagged."""
    found = fixture_findings("guard_parity_bad", "guard-parity")
    wants = {("cifar_resnet_v2", "_check_fused_bn_axis"),
             ("cifar_resnet_v2", "width_multiplier"),
             ("imagenet_resnet_v2", "_check_fused_bn_axis"),
             ("BlockLayer.__call__", "_check_fused_bn_axis")}
    got = {(w, token) for w, token in wants
           if any(w in f.message and token in f.message for f in found)}
    assert got == wants, "\n".join(f.format() for f in found)


def test_lint_passes_on_real_tree():
    """Every rule must be clean on the repo itself (after pragmas) —
    the post-fix code satisfies the contracts the fixtures violate."""
    found = run_jaxlint(REPO)
    assert found == [], "\n".join(f.format() for f in found)


# ------------------------------------------------------- pragma + baseline
def test_pragma_line_and_file(tmp_path):
    pkg = tmp_path / "tpu_resnet" / "ops"
    pkg.mkdir(parents=True)
    src = ("import time\n"
           "def kernel(x):\n"
           "    t = time.time()\n"
           "    return x, t\n")
    (pkg / "k.py").write_text(src)
    found = run_jaxlint(str(tmp_path))
    assert [f.rule for f in found] == ["jit-host-sync"]

    (pkg / "k.py").write_text(src.replace(
        "t = time.time()",
        "t = time.time()  # check: disable=jit-host-sync"))
    assert run_jaxlint(str(tmp_path)) == []

    # file-level pragma, and pragma sets parse as documented
    (pkg / "k.py").write_text("# check: disable-file=jit-host-sync\n" + src)
    assert run_jaxlint(str(tmp_path)) == []
    per_line, whole = pragma_sets("x = 1  # check: disable=a, b\n")
    assert per_line == {1: {"a", "b"}} and whole == set()


def test_pragma_in_docstring_or_string_does_not_suppress(tmp_path):
    """Pragma-shaped text in a docstring/string (e.g. docs that MENTION
    the syntax) must not disable anything — only real comments count
    (review fix: the scan regexed raw lines)."""
    pkg = tmp_path / "tpu_resnet" / "ops"
    pkg.mkdir(parents=True)
    (pkg / "k.py").write_text(
        '"""Suppress with `# check: disable-file=jit-host-sync`."""\n'
        "import time\n"
        "def kernel(x):\n"
        "    s = 'also not real: # check: disable=jit-host-sync'\n"
        "    return time.time(), s\n")
    assert [f.rule for f in run_jaxlint(str(tmp_path))] == ["jit-host-sync"]


def test_pragma_other_rule_does_not_suppress(tmp_path):
    pkg = tmp_path / "tpu_resnet" / "ops"
    pkg.mkdir(parents=True)
    (pkg / "k.py").write_text(
        "import time\n"
        "def kernel(x):\n"
        "    return time.time()  # check: disable=fork-safety\n")
    assert [f.rule for f in run_jaxlint(str(tmp_path))] == ["jit-host-sync"]


def test_baseline_roundtrip(tmp_path):
    pkg = tmp_path / "tpu_resnet" / "ops"
    pkg.mkdir(parents=True)
    (pkg / "k.py").write_text(
        "import time\ndef kernel(x):\n    return time.time()\n")
    found = run_jaxlint(str(tmp_path))
    assert len(found) == 1
    bl_path = str(tmp_path / "baseline.json")
    save_baseline(bl_path, found)

    # baselined: suppressed, nothing new, nothing stale
    new, suppressed, stale = apply_baseline(found, load_baseline(bl_path))
    assert new == [] and len(suppressed) == 1 and stale == []

    # fingerprints are line-insensitive: shifting the file keeps the match
    (pkg / "k.py").write_text(
        "import time\n\n\ndef kernel(x):\n    return time.time()\n")
    moved = run_jaxlint(str(tmp_path))
    new, suppressed, stale = apply_baseline(moved, load_baseline(bl_path))
    assert new == [] and len(suppressed) == 1

    # fixing the violation leaves a stale entry (baseline must shrink)
    (pkg / "k.py").write_text("def kernel(x):\n    return x\n")
    new, suppressed, stale = apply_baseline(
        run_jaxlint(str(tmp_path)), load_baseline(bl_path))
    assert new == [] and suppressed == [] and len(stale) == 1


def test_checked_in_baseline_is_empty():
    """Acceptance: the repo is clean with an EMPTY baseline — findings
    were fixed or pragma'd with justification, never baselined away."""
    from tpu_resnet.analysis.cli import DEFAULT_BASELINE
    assert load_baseline(DEFAULT_BASELINE) == []


# ------------------------------------------------------------ config matrix
def _entry(base_name, **kw):
    base = next(e for e in MATRIX if e.name == base_name)
    return MatrixEntry(**{**base.__dict__, **kw})


def test_matrix_covers_required_combinations():
    """ISSUE acceptance: >= 24 combinations across the declared axes."""
    assert len(MATRIX) >= 24
    datasets = {e.dataset for e in MATRIX}
    assert {"cifar10", "cifar100", "synthetic", "imagenet"} <= datasets
    assert {e.dtype for e in MATRIX} >= {"float32", "bfloat16"}
    assert any(e.data_axis > 1 for e in MATRIX)
    # 2-D ("batch","model") pod shapes (ROADMAP item 1 pre-work):
    # replicated AND zero1 rows exist and the zero1 one is lowered on
    # the concrete 8-device mesh.
    two_d = [e for e in MATRIX if e.model_axis > 1 and not e.expect_error]
    assert len(two_d) >= 3
    assert any(e.partition == "zero1" and e.check_lowering
               for e in two_d)
    assert any(e.fused for e in MATRIX) and any(e.remat for e in MATRIX)
    assert any(e.engine == "process" for e in MATRIX)
    assert sum(1 for e in MATRIX if e.expect_error) >= 3


def test_golden_drift_detected():
    """Mutating a config (remat on, here) changes the traced program —
    the verifier must fail against the checked-in golden."""
    mutated = _entry("cifar10_rn8_f32", remat=True)
    findings, stats = configmatrix.verify_matrix(entries=(mutated,))
    assert any(f.rule == "golden-jaxpr-drift"
               and "CHANGED" in f.message for f in findings), findings
    assert stats["hash_checked"] == 1


def test_golden_missing_entry_reported():
    findings, _ = configmatrix.verify_matrix(
        entries=(_entry("cifar10_rn8_f32", name="no_such_entry"),))
    assert any(f.rule == "golden-jaxpr-drift"
               and "no golden recorded" in f.message for f in findings)


def test_golden_update_roundtrip(tmp_path):
    """--update-golden writes hashes that then verify clean."""
    golden = str(tmp_path / "golden.json")
    entry = (_entry("cifar10_rn8_f32"),)
    findings, stats = configmatrix.verify_matrix(
        entries=entry, update_golden=True, golden_path=golden)
    assert findings == [] and stats["updated"] == ["cifar10_rn8_f32"]
    findings, stats = configmatrix.verify_matrix(entries=entry,
                                                 golden_path=golden)
    assert findings == [] and stats["hash_checked"] == 1


def test_must_raise_guard_weakening_detected():
    """A config the guards are supposed to reject, declared as
    must-raise with the wrong expectation: if the guard ever weakens the
    verifier reports it. Here: a LEGAL config declared must-raise
    simulates exactly what a removed guard looks like."""
    legal_declared_raising = _entry("cifar10_rn8_f32",
                                    name="weakened_guard",
                                    expect_error="anything")
    findings, _ = configmatrix.verify_matrix(
        entries=(legal_declared_raising,))
    assert any("was accepted" in f.message for f in findings)


def test_must_raise_ctor_guard():
    """The direct-constructor bypass (ADVICE r4): cifar_resnet_v2 with
    fused_blocks+bn_axis_name must raise the fail-loud message."""
    ctor = next(e for e in MATRIX if e.builder == "ctor-bn-axis")
    findings, stats = configmatrix.verify_matrix(entries=(ctor,))
    assert findings == [] and stats["must_raise"] == 1


def test_matrix_contains_failures_per_entry():
    """A broken entry (wrong exception type on must-raise; trace crash
    on a supported combo) becomes a per-entry finding, never a crashed
    run that loses the rest of the report (review fix)."""
    bogus_raise = MatrixEntry(name="bogus_raise", dataset="nope",
                              expect_error="anything")
    bogus_trace = MatrixEntry(name="bogus_trace", dataset="nope")
    ok = _entry("cifar10_rn8_f32")
    findings, stats = configmatrix.verify_matrix(
        entries=(bogus_raise, bogus_trace, ok))
    msgs = "\n".join(f.message for f in findings)
    assert "instead of a ValueError" in msgs
    assert "FAILED to trace" in msgs
    assert stats["traced"] == 1  # the healthy entry still verified


def test_dangling_twin_reference_is_an_error():
    a = _entry("cifar10_rn8_f32", same_program_as="renamed_away")
    findings, _ = configmatrix.verify_matrix(entries=(a,))
    assert any("silently unverified" in f.message for f in findings)


def test_engine_twin_mismatch_detected():
    """same_program_as asserts program invariance — pointing it at a
    genuinely different program must fail."""
    a = _entry("cifar10_rn8_f32")
    b = _entry("cifar10_rn8_bf16", same_program_as="cifar10_rn8_f32")
    findings, _ = configmatrix.verify_matrix(entries=(a, b))
    assert any("declared-identical twin" in f.message for f in findings)


def test_repo_is_clean():
    """THE tier-1 gate: lints + concurrency + spmd + full config matrix
    over the repo, clean with the checked-in (empty) baseline and
    goldens."""
    from tpu_resnet.analysis import run_concurrency, run_spmd

    findings = run_jaxlint(REPO)
    findings += run_concurrency(REPO)
    findings += run_spmd(REPO)
    matrix_findings, stats = configmatrix.verify_matrix()
    findings += [f for f in matrix_findings if f.severity == "error"]
    assert findings == [], "\n".join(f.format() for f in findings)
    assert stats["traced"] >= 21 and stats["must_raise"] >= 3
    assert stats["hash_checked"] == stats["traced"]
    # donation/sharding contract lowered on the concrete 8-dev mesh
    # (mesh8 sync-BN + per-replica + the zero1 sharded-slot layout +
    # the 2-D mesh4x2 zero1 pod shape)
    assert stats["lowered"] == 4


# -------------------------------------------------------------- CLI/doctor
def test_cli_lint_only_clean_and_fast():
    proc = subprocess.run(
        [sys.executable, "-m", "tpu_resnet", "check", "--skip-matrix"],
        cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout
    assert "0 error(s)" in proc.stdout


def test_cli_flags_fixture_violations(tmp_path):
    out_json = str(tmp_path / "findings.json")
    proc = subprocess.run(
        [sys.executable, "-m", "tpu_resnet", "check", "--skip-matrix",
         "--root", os.path.join(FIXTURES, "guard_parity_bad"),
         "--baseline", str(tmp_path / "none.json"), "--json", out_json],
        cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, timeout=120)
    assert proc.returncode == 1, proc.stdout
    assert "guard-parity" in proc.stdout
    with open(out_json) as fh:
        payload = json.load(fh)
    assert len(payload["findings"]) == 4
    assert all(f["rule"] == "guard-parity" for f in payload["findings"])


def test_cli_write_baseline_adopts_findings(tmp_path):
    root = os.path.join(FIXTURES, "signal_bad")
    bl = str(tmp_path / "bl.json")
    # Pre-seed a matrix-engine entry: a --skip-matrix write must MERGE
    # (preserve entries of engines that didn't run), not overwrite
    # (review fix: overwriting deleted accepted matrix entries).
    with open(bl, "w") as fh:
        json.dump([{"fingerprint": "f" * 16, "rule": "golden-jaxpr-drift",
                    "path": "<config-matrix>/x", "message": "m"}], fh)
    base = [sys.executable, "-m", "tpu_resnet", "check", "--skip-matrix",
            "--root", root, "--baseline", bl]
    proc = subprocess.run(base + ["--write-baseline"], cwd=REPO,
                          stdout=subprocess.PIPE, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout
    assert "preserved" in proc.stdout
    with open(bl) as fh:
        rules = {e["rule"] for e in json.load(fh)}
    assert "golden-jaxpr-drift" in rules and "signal-safety" in rules
    proc = subprocess.run(base, cwd=REPO, stdout=subprocess.PIPE,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout
    assert "baselined" in proc.stdout


def test_cli_partial_run_never_reports_stale(tmp_path):
    """A baseline entry for a config-matrix finding must NOT be called
    stale by `--skip-matrix` — that engine simply didn't run (review
    fix: partial runs previously exited 1 telling the user to delete a
    live entry)."""
    bl = tmp_path / "bl.json"
    bl.write_text(json.dumps([{"fingerprint": "0" * 16,
                               "rule": "golden-jaxpr-drift",
                               "path": "<config-matrix>/x",
                               "message": "m"}]))
    proc = subprocess.run(
        [sys.executable, "-m", "tpu_resnet", "check", "--skip-matrix",
         "--baseline", str(bl)],
        cwd=REPO, stdout=subprocess.PIPE, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout
    assert "stale" not in proc.stdout


def test_doctor_check_section():
    from tpu_resnet.tools import doctor

    out = doctor._check_static_analysis(matrix=False)
    assert out["ok"] is True, out
    assert out["errors"] == 0 and out["stale_baseline"] == 0
    # the doctor child runs engine 4 too (concurrency + spmd)
    assert {"lint", "concurrency", "spmd"} <= set(out["engines"]), out


def test_registry_scope_fixture_flags_direct_jit_construction():
    """The registry-bypass anti-pattern stays flagged: jax.jit (call
    and decorator form) and pjit construction outside the
    registry-owned modules — a program built there is invisible to the
    key spelling, the golden engines AND the persistent AOT executable
    cache (tpu_resnet/programs)."""
    found = fixture_findings("registry_scope_bad", "registry-scope")
    assert len(found) == 3, found
    assert {f.line for f in found} == {13, 16, 24}
    assert all(f.path == "tpu_resnet/analysis/quickcheck.py"
               for f in found)
    assert "programs/registry.py" in found[0].message
    # the registry-owned constructors themselves stay silent
    from tpu_resnet.analysis.jaxlint import run_jaxlint as _lint

    clean = _lint(REPO, select=["registry-scope"],
                  files=["tpu_resnet/train/step.py",
                         "tpu_resnet/serve/infer.py",
                         "tpu_resnet/programs/registry.py"])
    assert not clean


def test_sharding_scope_fixture_flags_stray_sharding_construction():
    """The sharding-scope anti-pattern stays flagged: NamedSharding /
    with_sharding_constraint built outside the partitioner-owned modules
    — a layout decided there is invisible to StatePartitioner's rules,
    the golden memory/collectives engines, and the zero1 twin gates."""
    found = fixture_findings("sharding_scope_bad", "sharding-scope")
    assert len(found) == 3, found
    assert {f.line for f in found} == {13, 19, 20}
    assert all(f.path == "tpu_resnet/obs/layout_hack.py" for f in found)
    assert "StatePartitioner" in found[0].message
    # the partitioner-owned modules themselves stay silent
    from tpu_resnet.analysis.jaxlint import (SHARDING_SCOPE_FILES,
                                             run_jaxlint as _lint)

    clean = _lint(REPO, select=["sharding-scope"],
                  files=list(SHARDING_SCOPE_FILES))
    assert not clean


def test_route_fixture_flags_jax_import_and_handler_teardown():
    """The fleet-router anti-patterns stay flagged: a module-scope jax
    import in the host-isolated router (it must come up on a host whose
    accelerator stack is broken), and a SIGTERM handler that tears the
    fleet down inline instead of setting a flag for route()."""
    found = fixture_findings("route_bad")
    host = [f for f in found if f.rule == "host-isolation"]
    assert len(host) == 1
    assert "import of 'jax'" in host[0].message
    assert host[0].path == "tpu_resnet/serve/router.py"
    sig = "\n".join(f.message for f in found
                    if f.rule == "signal-safety")
    for hazard in ("self._httpd.shutdown", "self._prober.join",
                   "time.sleep", "self.drain_replica"):
        assert hazard in sig, f"{hazard} not flagged:\n{sig}"
    assert "_handle -> _teardown_now" in sig


def test_scenario_fixture_flags_jax_import_and_real_package_is_clean():
    """The scenario conductor is host-isolated like the router: a
    module-scope jax import in tpu_resnet/scenario/ must stay flagged,
    and the real package must keep passing the same rule."""
    found = fixture_findings("scenario_bad", "host-isolation")
    assert len(found) == 1, found
    assert "import of 'jax'" in found[0].message
    assert found[0].path == "tpu_resnet/scenario/conductor.py"

    from tpu_resnet.analysis.jaxlint import HOST_ONLY_FILES
    from tpu_resnet.analysis.jaxlint import run_jaxlint as _lint

    scoped = [f for f in HOST_ONLY_FILES
              if f.startswith("tpu_resnet/scenario/")]
    assert len(scoped) == 6, scoped
    assert not _lint(REPO, select=["host-isolation"], files=scoped)


def test_autopilot_fixture_flags_jax_import_and_real_package_is_clean():
    """The autopilot control plane is host-isolated like the router and
    the conductor: a module-scope jax import in tpu_resnet/autopilot/
    must stay flagged, and every shipped autopilot module must keep
    passing the same rule (the control loop has to keep steering while
    the accelerator stack is the thing that is melting)."""
    found = fixture_findings("autopilot_bad", "host-isolation")
    assert len(found) == 1, found
    assert "import of 'jax'" in found[0].message
    assert found[0].path == "tpu_resnet/autopilot/controller.py"

    from tpu_resnet.analysis.jaxlint import HOST_ONLY_FILES
    from tpu_resnet.analysis.jaxlint import run_jaxlint as _lint

    scoped = [f for f in HOST_ONLY_FILES
              if f.startswith("tpu_resnet/autopilot/")]
    assert len(scoped) == 6, scoped
    assert not _lint(REPO, select=["host-isolation"], files=scoped)
