"""`jaxlint` — AST lints for the repo's JAX/TPU contracts.

Each rule encodes a contract this codebase already relies on but (before
this subsystem) only enforced dynamically, if at all:

jit-host-sync     No side effects or host syncs in jit-reachable code
                  (``train/step.py``, ``serve/infer.py`` — the serving
                  hot path — ``ops/*``, ``tools/sweep_measure.py`` —
                  the sweep harness's program assembly — and any
                  ``@jax.jit`` function anywhere): ``print``,
                  ``time.*`` clocks,
                  ``np.random``/``random`` (host RNG under trace runs
                  ONCE and bakes a constant into the program),
                  ``.item()``/``jax.device_get``/``.block_until_ready``
                  (device round-trip per call).
jit-static-args   ``jax.jit``/``nn.remat`` call sites: static_argnums/
                  static_argnames literals must be hashable ints/strs,
                  and bool/str-typed parameters of a jitted function must
                  be marked static (a traced bool either fails at the
                  first Python branch or silently retraces per value).
fork-safety       The modules a spawn'd decode worker imports
                  (``data/engine.py`` and its transitive module-scope
                  import closure) must stay jax-free — a worker that
                  imports jax pays seconds of spawn latency and hundreds
                  of MB RSS; today this is only a convention held up by
                  the lazy ``data/__init__``. Also: module-level locks /
                  file handles in that closure, and process creation
                  outside an explicit spawn context.
signal-safety     Handlers registered via ``signal.signal`` may only set
                  flags, log, and re-raise. Checkpoint saves, lock
                  acquisition, sleeps or jax/numpy work inside a handler
                  run at an arbitrary bytecode boundary of the
                  interrupted main thread (mid-save, mid-dispatch) and
                  deadlock or corrupt state.
host-isolation    The serving fleet's host-side control plane
                  (``serve/router.py``, ``serve/batcher.py``) must stay
                  importable with NO accelerator stack: the router keeps
                  answering when the accelerator runtime is the thing
                  that is broken, and stdlib-only consumers (loadgen,
                  the doctor probes, supervise) import these modules on
                  machines with no backend. A module-scope jax/flax/tf
                  import there breaks that contract silently — the same
                  class of rot fork-safety pins for the decode workers.
registry-scope    Compiled-program construction (``jax.jit``/``pjit``
                  call sites and decorators) inside the tpu_resnet
                  package is allowed only in the registry-owned modules
                  (``REGISTRY_SCOPE_FILES``): every production program
                  must route through ``programs/registry.py`` so its
                  key spelling, golden identity, donation contract and
                  the persistent AOT executable cache all see it. A new
                  code path jitting directly would silently bypass the
                  cold-start cache AND the check engines' coverage map.
guard-parity      Fail-loud guard parity (ADVICE r4): the validation in
                  ``models.build_model`` must also exist in the public
                  constructors (``cifar_resnet_v2``/``imagenet_resnet_v2``)
                  and in ``BlockLayer``'s fused dispatch, so direct calls
                  fail with the same clear message instead of an obscure
                  downstream tile error or silent per-replica BN.

The engine is pure ``ast`` — importing this module never imports jax, so
``tpu-resnet-check`` (lint-only) runs in well under a second.
"""

from __future__ import annotations

import ast
import os
from typing import Dict, Iterable, List, Optional, Tuple

from tpu_resnet.analysis.findings import Finding, apply_pragmas

EXCLUDE_DIRS = {"tests", "docs", "launch", "__pycache__", ".git",
                ".jax_cache", "build", "dist"}

# jit-reachable modules linted wholesale (every function body).
# serve/infer.py is the serving hot path: its compiled inference fn runs
# per coalesced batch, so a host sync there multiplies into every
# request's latency (host-side serving code lives in serve/batcher.py
# and serve/server.py, which are NOT jit scope).
# tools/sweep_measure.py is the sweep harness's jit-program assembly —
# split from tools/sweep.py precisely so the measured programs sit in
# this scope while the timing loop (host clocks by design) stays out;
# ops/autotune.py inside the ops/ prefix is the deliberate exception
# (file-level pragma with justification: it IS the host-side prober).
JIT_SCOPE_FILES = ("tpu_resnet/train/step.py",
                   "tpu_resnet/serve/infer.py",
                   "tpu_resnet/tools/sweep_measure.py",
                   # the zero1 weight update and the constraint helpers
                   # it calls trace INSIDE the step program
                   "tpu_resnet/parallel/zero.py",
                   "tpu_resnet/parallel/partition.py",
                   # int8 quant/dequant math traces inside the serving
                   # program (the dequant fold in make_serve_infer) —
                   # already under the ops/ prefix, listed explicitly
                   # because it is a named serve-hot-path contract
                   "tpu_resnet/ops/quant.py")
JIT_SCOPE_PREFIXES = ("tpu_resnet/ops/",)

# Module-scope import closure of the spawn'd decode worker
# (data/engine.py runs as __main__-adjacent module in every worker; its
# parent packages' __init__ execute too).
FORK_ENTRY_FILES = ("tpu_resnet/data/engine.py",)
FORK_FORBIDDEN_ROOTS = {"jax", "jaxlib", "flax", "optax", "orbax",
                        "tensorflow", "torch"}

# Modules allowed to construct jitted programs (jax.jit / pjit sites).
# The registry (programs/registry.py) is the front door; the rest are
# the canonical constructors it routes — train/step.py (shard_step),
# data/device_data.py (staged chunk + resident shuffle), data/pipeline.py
# (the H2D staging take), serve/infer.py + evaluation/evaluator.py (the
# serving/eval programs), export/serialize.py (the frozen artifact),
# obs/memory.py + analysis/memorybudget.py (the ledger/golden engines,
# which deliberately compile the SAME constructors' programs),
# tools/analysis.py (the info CLI's one-off lowering) and
# ops/autotune.py (the A/B prober — compiles candidates by design).
# Scope is the tpu_resnet package: root-level tools/ and bench.py are
# measurement harnesses outside the production path.
REGISTRY_SCOPE_FILES = (
    "tpu_resnet/programs/registry.py",
    "tpu_resnet/train/step.py",
    "tpu_resnet/data/device_data.py",
    "tpu_resnet/data/pipeline.py",
    "tpu_resnet/serve/infer.py",
    "tpu_resnet/evaluation/evaluator.py",
    "tpu_resnet/export/serialize.py",
    "tpu_resnet/obs/memory.py",
    "tpu_resnet/analysis/memorybudget.py",
    "tpu_resnet/tools/analysis.py",
)
# The ops/ kernels may jit internally (custom-VJP reference arms, A/B
# probe candidates, parity helpers): those programs are either inlined
# into registry-routed traces or exist to be measured against them —
# kernel-internal, never a run-level dispatch path.
REGISTRY_SCOPE_PREFIXES = ("tpu_resnet/ops/",)

# Modules allowed to construct shardings (NamedSharding) or pin layouts
# (with_sharding_constraint). StatePartitioner (parallel/partition.py)
# is the single OWNER of state-layout decisions — the collectives
# engine's golden structure (analysis/collectives.py) is only a proof
# if no other code path can inject a sharding behind its back — with
# parallel/zero.py (the ZeRO update that applies the partitioner's
# constraints), parallel/mesh.py (the canonical batch/replicated
# sharding helpers everything else is supposed to call), train/step.py
# and data/device_data.py (the registry-scoped program constructors
# that pin their own argument layouts) as the documented call surface.
SHARDING_SCOPE_FILES = (
    "tpu_resnet/parallel/partition.py",
    "tpu_resnet/parallel/zero.py",
    "tpu_resnet/parallel/mesh.py",
    "tpu_resnet/train/step.py",
    "tpu_resnet/data/device_data.py",
)

# Host-isolated serving control plane: these modules must import with no
# accelerator stack present (router on a broken-runtime host; batcher in
# stdlib-only consumers). Direct module-scope imports only — unlike
# fork-safety there is no transitive closure walk, because the contract
# is per-module and the modules' own imports (server.py etc.) are the
# jax-aware layer by design.
HOST_ONLY_FILES = ("tpu_resnet/serve/router.py",
                   "tpu_resnet/serve/batcher.py",
                   "tpu_resnet/serve/discovery.py",
                   # The fleet aggregator is the control-plane sensor:
                   # it must keep scraping while the data plane's
                   # accelerator stack is the thing that is broken.
                   "tpu_resnet/obs/fleet.py",
                   # The scenario conductor drills hosts whose
                   # accelerator stack is the thing under test; only
                   # its CHILD processes may touch jax.
                   "tpu_resnet/scenario/__init__.py",
                   "tpu_resnet/scenario/assertions.py",
                   "tpu_resnet/scenario/catalog.py",
                   "tpu_resnet/scenario/cli.py",
                   "tpu_resnet/scenario/conductor.py",
                   "tpu_resnet/scenario/spec.py",
                   # The autoscaling control plane scales the fleet
                   # PRECISELY when the data plane is melting; a jax
                   # import here would tie the controller's fate to the
                   # stack it supervises.
                   "tpu_resnet/autopilot/__init__.py",
                   "tpu_resnet/autopilot/signals.py",
                   "tpu_resnet/autopilot/policy.py",
                   "tpu_resnet/autopilot/actuator.py",
                   "tpu_resnet/autopilot/controller.py",
                   "tpu_resnet/autopilot/cli.py")

HOST_SYNC_EXACT = {
    "print": "host I/O",
    "jax.device_get": "device→host transfer",
    "time.time": "host clock", "time.sleep": "host sleep",
    "time.perf_counter": "host clock", "time.monotonic": "host clock",
    "time.process_time": "host clock",
}
HOST_SYNC_PREFIXES = {
    "numpy.random": "host RNG (runs once at trace time — bakes a "
                    "constant into the compiled program)",
    "random": "host RNG (runs once at trace time — bakes a constant "
              "into the compiled program)",
}
HOST_SYNC_METHODS = {
    "item": "device sync per call",
    "block_until_ready": "device sync",
    # Compile introspection (obs/mfu.py accounting): .lower()/.compile()
    # .cost_analysis() re-traces and runs an HLO analysis pass — a
    # one-time host-side startup cost that must never land in the jitted
    # hot path (cost_analysis is the unambiguous marker; .lower/.compile
    # collide with str.lower/re.compile and are left to review).
    "cost_analysis": "XLA compile introspection (obs/mfu accounting) — "
                     "host-side only, once per program, never per step",
    # Memory introspection (obs/memory.py): device.memory_stats() is a
    # host RPC into the PJRT client and jax.live_arrays() walks every
    # live buffer — both are log-boundary/forensics calls that must
    # never creep into the jitted hot path. (memory_analysis, like
    # cost_analysis, only exists on AOT-compiled objects.)
    "memory_stats": "device-memory introspection (obs/memory gauges) — "
                    "host-side only, at log boundaries, never per step",
    "live_arrays": "live-buffer census (obs/memory OOM forensics) — "
                   "host-side only, crash handlers, never per step",
    "memory_analysis": "XLA compile introspection (obs/memory ledger) — "
                       "host-side only, once per program, never per step",
}

SIGNAL_DENY_PREFIXES = ("subprocess.", "jax.", "jax_", "numpy.",
                        "shutil.", "socket.", "os.system", "os.popen")
# os.kill: the ROUTER SIGTERM anti-pattern — cascading the drain signal
# to the replica fleet inline in the handler (the route() loop owns
# teardown; handlers only set the flag).
SIGNAL_DENY_EXACT = {"open", "time.sleep", "exec", "eval", "os.kill"}
# "drain"/"shutdown": the serve SIGTERM anti-pattern — draining the
# micro-batcher or tearing down the HTTP socket inline in the handler
# instead of setting a flag for the serve()/route() loop
# (serve/server.py, serve/router.py). "drain_replica": the router's
# rolling-drain method, which joins threads and signals processes.
SIGNAL_DENY_METHODS = {"save", "restore", "acquire", "join", "wait",
                       "sleep", "write", "flush", "dump", "drain",
                       "shutdown", "drain_replica"}
SIGNAL_LOG_ROOTS = {"log", "logger", "logging"}

# (file, qualname, requirement) — requirement is "calls:<fn>" (body must
# call <fn>) or "guard:<a>&<b>" (body must contain an If mentioning both
# identifiers whose branch raises).
GUARD_PARITY_REQS = (
    ("tpu_resnet/models/resnet.py", "cifar_resnet_v2",
     "calls:_check_fused_bn_axis",
     "sync-BN (bn_axis_name) + fused_blocks must raise, not silently "
     "compute per-replica BN (ADVICE r4)"),
    ("tpu_resnet/models/resnet.py", "cifar_resnet_v2",
     "guard:fused_blocks&width_multiplier",
     "the width_multiplier guard lives in the constructor, so that "
     "direct calls fail like build_model's (ADVICE r4)"),
    ("tpu_resnet/models/resnet.py", "imagenet_resnet_v2",
     "calls:_check_fused_bn_axis",
     "sync-BN (bn_axis_name) + fused_blocks must raise, not silently "
     "compute per-replica BN (ADVICE r4)"),
    ("tpu_resnet/models/resnet.py", "BlockLayer.__call__",
     "calls:_check_fused_bn_axis",
     "the fused dispatch must re-check bn_axis_name at apply time — "
     "BlockLayer is constructible directly (ADVICE r4)"),
)


# ----------------------------------------------------------------- file set
def discover(root: str) -> List[str]:
    """Root-relative posix paths of every lintable .py file."""
    out = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(d for d in dirnames
                             if d not in EXCLUDE_DIRS
                             and not d.startswith("."))
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                rel = os.path.relpath(os.path.join(dirpath, fn), root)
                out.append(rel.replace(os.sep, "/"))
    return out


class SourceTree:
    """Parsed view of the lintable files under a root."""

    def __init__(self, root: str, files: Optional[Iterable[str]] = None):
        self.root = os.path.abspath(root)
        self.sources: Dict[str, str] = {}
        self.trees: Dict[str, ast.AST] = {}
        for rel in (files if files is not None else discover(self.root)):
            path = os.path.join(self.root, rel)
            try:
                with open(path, encoding="utf-8") as fh:
                    src = fh.read()
                self.trees[rel] = ast.parse(src, filename=rel)
            except (OSError, SyntaxError) as e:
                # A file the toolchain can't parse is itself a finding —
                # surfaced by the engine, not swallowed.
                self.sources[rel] = ""
                self.trees[rel] = ast.Module(body=[], type_ignores=[])
                self.parse_errors = getattr(self, "parse_errors", [])
                self.parse_errors.append(Finding(
                    "parse", rel, getattr(e, "lineno", 0) or 0,
                    f"cannot parse: {e}", "error"))
                continue
            self.sources[rel] = src
        self.parse_errors = getattr(self, "parse_errors", [])

    def has(self, rel: str) -> bool:
        return rel in self.trees


# ------------------------------------------------------------- ast helpers
def _alias_map(tree: ast.AST) -> Dict[str, str]:
    """name-in-scope -> dotted module/attr it resolves to, from every
    import statement in the file (module or function scope)."""
    aliases: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                aliases[a.asname or a.name.split(".")[0]] = (
                    a.name if a.asname else a.name.split(".")[0])
        elif isinstance(node, ast.ImportFrom) and node.level == 0 \
                and node.module:
            for a in node.names:
                if a.name == "*":
                    continue
                aliases[a.asname or a.name] = f"{node.module}.{a.name}"
    return aliases


def _dotted(node: ast.AST) -> Optional[str]:
    """'a.b.c' for Name/Attribute chains, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _resolved(node: ast.AST, aliases: Dict[str, str]) -> Optional[str]:
    """Dotted name with the leading alias expanded through the file's
    imports: ``np.random.x`` -> ``numpy.random.x``."""
    d = _dotted(node)
    if d is None:
        return None
    head, _, rest = d.partition(".")
    full = aliases.get(head)
    if full is None:
        return d
    return f"{full}.{rest}" if rest else full


def _is_jax_jit(node: ast.AST, aliases: Dict[str, str]) -> bool:
    return _resolved(node, aliases) in ("jax.jit", "jax.api.jit")


def _identifiers(node: ast.AST) -> set:
    """All Name ids and Attribute attrs mentioned in an expression."""
    out = set()
    for n in ast.walk(node):
        if isinstance(n, ast.Name):
            out.add(n.id)
        elif isinstance(n, ast.Attribute):
            out.add(n.attr)
    return out


def _module_scope_nodes(mod: ast.AST, node_types) -> List[ast.AST]:
    """Every node of ``node_types`` that executes at module import time:
    the whole module tree — including top-level try/if bodies (the
    optional-dependency pattern runs in every importer) — minus
    def/class/lambda subtrees (deferred execution)."""
    out: List[ast.AST] = []

    def visit(node: ast.AST) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef, ast.Lambda)):
                continue
            if isinstance(child, node_types):
                out.append(child)
            visit(child)

    visit(mod)
    return out


def _module_scope_calls(mod: ast.AST) -> List[ast.Call]:
    return _module_scope_nodes(mod, ast.Call)


# =================================================================== rules
def rule_jit_host_sync(tree: SourceTree) -> List[Finding]:
    """host I/O, clocks, host RNG and device syncs in jit-reachable code."""
    findings = []
    seen = set()  # (rel, line, hazard): nested defs are walked twice
    for rel, mod in tree.trees.items():
        aliases = _alias_map(mod)
        in_scope_file = (rel in JIT_SCOPE_FILES
                         or rel.startswith(JIT_SCOPE_PREFIXES))
        for fn in ast.walk(mod):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            jitted = any(_is_jax_jit(dec, aliases)
                         or (isinstance(dec, ast.Call)
                             and _is_jax_jit(dec.func, aliases))
                         for dec in fn.decorator_list)
            if not (in_scope_file or jitted):
                continue
            where = (f"@jax.jit function '{fn.name}'" if jitted
                     else f"jit-reachable module function '{fn.name}'")
            for call in ast.walk(fn):
                if not isinstance(call, ast.Call):
                    continue
                resolved = _resolved(call.func, aliases)
                hazard = None
                if resolved in HOST_SYNC_EXACT:
                    hazard = (resolved, HOST_SYNC_EXACT[resolved])
                elif resolved:
                    for pref, why in HOST_SYNC_PREFIXES.items():
                        if resolved == pref or \
                                resolved.startswith(pref + "."):
                            hazard = (resolved, why)
                            break
                if hazard is None and isinstance(call.func, ast.Attribute) \
                        and call.func.attr in HOST_SYNC_METHODS:
                    hazard = (f".{call.func.attr}()",
                              HOST_SYNC_METHODS[call.func.attr])
                if hazard and (rel, call.lineno, hazard[0]) not in seen:
                    seen.add((rel, call.lineno, hazard[0]))
                    findings.append(Finding(
                        "jit-host-sync", rel, call.lineno,
                        f"{hazard[0]} inside {where}: {hazard[1]} — "
                        f"hoist it out of the jitted path (or "
                        f"jax.debug.print / a traced PRNG key)"))
    return findings


def rule_jit_static_args(tree: SourceTree) -> List[Finding]:
    """hashable/complete static_argnums|argnames at jax.jit/remat sites."""
    findings = []
    for rel, mod in tree.trees.items():
        aliases = _alias_map(mod)
        # module-level defs/lambdas for call-form target resolution
        local_defs: Dict[str, ast.AST] = {}
        for node in mod.body:
            if isinstance(node, ast.FunctionDef):
                local_defs[node.name] = node
            elif isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and isinstance(node.value, ast.Lambda):
                local_defs[node.targets[0].id] = node.value

        def check_static_kwargs(call: ast.Call, what: str):
            """Sub-check A: literal static_argnums/argnames hashability.
            Non-literal elements (names, attribute lookups) are legal —
            only provably-wrong literals are flagged; a symbolic element
            makes coverage unknowable, so sub-check B is skipped too."""
            covered_pos, covered_names = set(), set()
            resolvable = True
            for kw in call.keywords:
                if kw.arg not in ("static_argnums", "static_argnames"):
                    continue
                want_str = kw.arg == "static_argnames"
                v = kw.value
                if isinstance(v, (ast.Set, ast.Dict)):
                    findings.append(Finding(
                        "jit-static-args", rel, v.lineno,
                        f"{kw.arg} of {what} must be "
                        + ("a str or tuple of strs" if want_str
                           else "an int or tuple of ints")
                        + f", not a {type(v).__name__.lower()} literal "
                          f"(unhashable/wrong container)"))
                    continue
                elts = (v.elts if isinstance(v, (ast.Tuple, ast.List))
                        else [v] if isinstance(v, ast.Constant)
                        else None)
                if elts is None:       # wholly symbolic: can't evaluate
                    resolvable = False
                    continue
                for e in elts:
                    if not isinstance(e, ast.Constant):
                        resolvable = False  # symbolic element: unknowable
                        continue
                    ok = (isinstance(e.value, str) if want_str
                          else isinstance(e.value, int)
                          and not isinstance(e.value, bool))
                    if not ok:
                        findings.append(Finding(
                            "jit-static-args", rel, e.lineno,
                            f"{kw.arg} of {what} must be "
                            + ("a str or tuple of strs"
                               if want_str else "an int or tuple of ints")
                            + f", got {e.value!r}"))
                    elif want_str:
                        covered_names.add(e.value)
                    else:
                        covered_pos.add(e.value)
            return covered_pos, covered_names, resolvable

        def check_target(fn_node, covered_pos, covered_names, site_line,
                         what):
            """Sub-check B: bool/str-typed params must be static.
            Positional indices span posonlyargs + args (jax counts them
            together); keyword-only params are coverable by name only."""
            args_node = fn_node.args
            params = list(getattr(args_node, "posonlyargs", ())) \
                + list(args_node.args)
            defaults = [None] * (len(params) - len(args_node.defaults)) \
                + list(args_node.defaults)
            rows = [(i, p, d, i in covered_pos or p.arg in covered_names)
                    for i, (p, d) in enumerate(zip(params, defaults))]
            rows += [(None, p, d, p.arg in covered_names)
                     for p, d in zip(args_node.kwonlyargs,
                                     args_node.kw_defaults)]
            for _, p, default, covered in rows:
                name = p.arg
                if name in ("self", "cls") or covered:
                    continue
                bad_type = None
                ann = getattr(p, "annotation", None)
                if isinstance(ann, ast.Name) and ann.id in ("bool", "str"):
                    bad_type = ann.id
                elif isinstance(ann, ast.Constant) and ann.value in (
                        "bool", "str"):
                    bad_type = ann.value
                elif isinstance(default, ast.Constant) and isinstance(
                        default.value, (bool, str)):
                    bad_type = type(default.value).__name__
                if bad_type:
                    findings.append(Finding(
                        "jit-static-args", rel, site_line,
                        f"{bad_type}-typed parameter '{name}' of {what} is "
                        f"traced — a Python branch on it fails under jit "
                        f"(or silently retraces); add it to "
                        f"static_argnums/static_argnames"))

        for node in ast.walk(mod):
            # decorator form
            if isinstance(node, ast.FunctionDef):
                for dec in node.decorator_list:
                    if _is_jax_jit(dec, aliases):
                        check_target(node, set(), set(), node.lineno,
                                     f"jitted '{node.name}'")
                    elif isinstance(dec, ast.Call) and _is_jax_jit(
                            dec.func, aliases):
                        pos, names, resolvable = check_static_kwargs(
                            dec, f"@jax.jit '{node.name}'")
                        if resolvable:
                            check_target(node, pos, names, node.lineno,
                                         f"jitted '{node.name}'")
            # call form
            if isinstance(node, ast.Call):
                resolved = _resolved(node.func, aliases)
                if resolved in ("jax.jit",):
                    what = "jax.jit call"
                    pos, names, resolvable = check_static_kwargs(node, what)
                    if node.args and resolvable:
                        target = node.args[0]
                        fn_node = None
                        if isinstance(target, ast.Lambda):
                            fn_node = target
                        elif isinstance(target, ast.Name):
                            fn_node = local_defs.get(target.id)
                        if fn_node is not None and not isinstance(
                                fn_node, ast.ClassDef):
                            tname = getattr(target, "id", "<lambda>")
                            check_target(fn_node, pos, names, node.lineno,
                                         f"jitted '{tname}'")
                elif resolved in ("jax.checkpoint", "jax.remat",
                                  "flax.linen.remat", "nn.remat"):
                    check_static_kwargs(node, resolved or "remat")
    return findings


def rule_fork_safety(tree: SourceTree) -> List[Finding]:
    """spawn'd worker import closure stays jax-free; spawn context; no module-level locks."""
    entries = [e for e in FORK_ENTRY_FILES if tree.has(e)]
    if not entries:
        return []
    findings = []

    def rel_for_module(module: str) -> Optional[str]:
        base = module.replace(".", "/")
        for cand in (f"{base}.py", f"{base}/__init__.py"):
            if tree.has(cand):
                return cand
        return None

    def module_for_rel(rel: str) -> str:
        mod = rel[:-3] if rel.endswith(".py") else rel
        if mod.endswith("/__init__"):
            mod = mod[: -len("/__init__")]
        return mod.replace("/", ".")

    def parent_inits(rel: str) -> List[str]:
        out = []
        parts = rel.split("/")[:-1]
        for i in range(1, len(parts) + 1):
            init = "/".join(parts[:i]) + "/__init__.py"
            if tree.has(init):
                out.append(init)
        return out

    # BFS over module-scope imports, keeping one witness chain per module.
    chains: Dict[str, Tuple[str, ...]] = {}
    queue: List[str] = []
    for e in entries:
        for r in parent_inits(e) + [e]:
            if r not in chains:
                chains[r] = (e,) if r != e else ()
                queue.append(r)
    while queue:
        rel = queue.pop(0)
        mod = tree.trees[rel]
        pkg = module_for_rel(rel).rsplit(".", 1)[0] \
            if "." in module_for_rel(rel) else ""
        # Module-scope imports INCLUDING those inside top-level try/if
        # (the optional-dependency pattern executes in every worker too);
        # imports inside function bodies are lazy and exempt.
        for node in _module_scope_nodes(mod, (ast.Import, ast.ImportFrom)):
            targets: List[Tuple[str, int]] = []
            if isinstance(node, ast.Import):
                targets = [(a.name, node.lineno) for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                if node.level:
                    base_parts = module_for_rel(rel).split(".")
                    # level=1 in a module means its own package
                    if not rel.endswith("__init__.py"):
                        base_parts = base_parts[:-1]
                    base_parts = base_parts[: len(base_parts)
                                            - (node.level - 1)]
                    base = ".".join(base_parts)
                    base = f"{base}.{node.module}" if node.module else base
                else:
                    base = node.module or ""
                targets = [(base, node.lineno)]
                targets += [(f"{base}.{a.name}", node.lineno)
                            for a in node.names if a.name != "*"]
            for module, lineno in targets:
                root_name = module.split(".")[0]
                if root_name in FORK_FORBIDDEN_ROOTS:
                    chain = " -> ".join(chains[rel] + (rel,))
                    findings.append(Finding(
                        "fork-safety", rel, lineno,
                        f"spawn'd decode workers transitively import "
                        f"'{module}' at module scope (chain: {chain}): "
                        f"each worker pays the full jax import (seconds "
                        f"of spawn latency, 100s of MB RSS) — import it "
                        f"lazily inside the function that needs it"))
                    continue
                sub = rel_for_module(module)
                if sub is None:
                    continue
                for r in parent_inits(sub) + [sub]:
                    if r not in chains:
                        chains[r] = chains[rel] + (rel,)
                        queue.append(r)
        _ = pkg  # (kept for clarity; relative imports resolved above)

    # module-level locks / file handles + non-spawn process creation
    for rel in chains:
        mod = tree.trees[rel]
        aliases = _alias_map(mod)
        for node in ast.walk(mod):
            if not isinstance(node, ast.Call):
                continue
            resolved = _resolved(node.func, aliases)
            if resolved in ("multiprocessing.get_context",):
                ok = (node.args and isinstance(node.args[0], ast.Constant)
                      and node.args[0].value == "spawn")
                if not ok:
                    findings.append(Finding(
                        "fork-safety", rel, node.lineno,
                        "worker processes must use get_context('spawn') — "
                        "fork after jax/XLA init duplicates runtime "
                        "threads and locks into a broken child"))
            elif resolved in ("multiprocessing.Process",):
                findings.append(Finding(
                    "fork-safety", rel, node.lineno,
                    "bare multiprocessing.Process uses the platform "
                    "default start method (fork on Linux) — use "
                    "get_context('spawn').Process"))
        # Resource creation that runs at import time: every Call in the
        # module scope, including inside top-level try/if bodies, but
        # NOT inside def/class/lambda bodies (deferred execution). A
        # pruned recursion — ast.walk can't skip subtrees, and breaking
        # out of it on the first nested def would silently skip sibling
        # calls in the same compound statement.
        for call in _module_scope_calls(mod):
            resolved = _resolved(call.func, aliases)
            if resolved in ("open", "threading.Lock", "threading.RLock",
                            "threading.Condition", "multiprocessing.Lock"):
                findings.append(Finding(
                    "fork-safety", rel, call.lineno,
                    f"module-level {resolved}() in a "
                    f"worker-imported module: created at import "
                    f"time in every spawned worker; handles/locks "
                    f"captured this way are a deadlock hazard"))
    return findings


def rule_signal_safety(tree: SourceTree) -> List[Finding]:
    """signal handlers only set flags, log and re-raise."""
    findings = []
    for rel, mod in tree.trees.items():
        aliases = _alias_map(mod)
        # registration sites: signal.signal(sig, handler)
        module_fns = {n.name: n for n in mod.body
                      if isinstance(n, ast.FunctionDef)}
        classes = {n.name: n for n in mod.body
                   if isinstance(n, ast.ClassDef)}

        def enclosing_class(node) -> Optional[ast.ClassDef]:
            for cls in classes.values():
                for sub in ast.walk(cls):
                    if sub is node:
                        return cls
            return None

        for node in ast.walk(mod):
            if not (isinstance(node, ast.Call)
                    and _resolved(node.func, aliases) == "signal.signal"
                    and len(node.args) == 2):
                continue
            handler = node.args[1]
            cls = enclosing_class(node)
            target: Optional[ast.FunctionDef] = None
            owner = None
            hd = _dotted(handler)
            if hd and hd.startswith("self.") and cls is not None:
                owner = cls
                target = next((m for m in cls.body
                               if isinstance(m, ast.FunctionDef)
                               and m.name == hd.split(".", 1)[1]), None)
            elif isinstance(handler, ast.Name):
                target = module_fns.get(handler.id)
            if target is None:
                continue  # dynamic handler (restore loops etc.)

            # intra-module transitive walk from the handler
            seen = set()
            stack = [(target, (target.name,))]
            while stack:
                fn, chain = stack.pop()
                if fn.name in seen:
                    continue
                seen.add(fn.name)
                for call in ast.walk(fn):
                    if not isinstance(call, ast.Call):
                        continue
                    d = _dotted(call.func) or ""
                    resolved = _resolved(call.func, aliases) or ""
                    root_name = d.split(".")[0] if d else ""
                    if root_name in SIGNAL_LOG_ROOTS:
                        continue
                    hazard = None
                    if resolved in SIGNAL_DENY_EXACT:
                        hazard = resolved
                    elif resolved.startswith(SIGNAL_DENY_PREFIXES):
                        hazard = resolved
                    elif isinstance(call.func, ast.Attribute) \
                            and call.func.attr in SIGNAL_DENY_METHODS:
                        hazard = d or f".{call.func.attr}"
                    if hazard:
                        via = " -> ".join(chain)
                        findings.append(Finding(
                            "signal-safety", rel, call.lineno,
                            f"signal handler reaches '{hazard}' (via "
                            f"{via}): handlers run at an arbitrary "
                            f"bytecode boundary of the interrupted main "
                            f"thread — only set flags, log, and re-raise "
                            f"(the loop does the real work at the next "
                            f"chunk boundary)"))
                        continue
                    # recurse into same-module callees
                    callee = None
                    if d.startswith("self.") and owner is not None:
                        callee = next(
                            (m for m in owner.body
                             if isinstance(m, ast.FunctionDef)
                             and m.name == d.split(".", 1)[1]), None)
                    elif isinstance(call.func, ast.Name):
                        callee = module_fns.get(call.func.id)
                    if callee is not None and callee.name not in seen:
                        stack.append((callee, chain + (callee.name,)))
    return findings


def rule_host_isolation(tree: SourceTree) -> List[Finding]:
    """serving control-plane modules stay jax-free at module scope."""
    findings = []
    for rel in HOST_ONLY_FILES:
        if not tree.has(rel):
            continue
        mod = tree.trees[rel]
        for node in _module_scope_nodes(mod, (ast.Import, ast.ImportFrom)):
            if isinstance(node, ast.Import):
                modules = [(a.name, node.lineno) for a in node.names]
            else:
                if node.level:  # relative: stays inside tpu_resnet
                    continue
                modules = [(node.module or "", node.lineno)]
            for module, lineno in modules:
                if module.split(".")[0] in FORK_FORBIDDEN_ROOTS:
                    findings.append(Finding(
                        "host-isolation", rel, lineno,
                        f"module-scope import of '{module}' in a "
                        f"host-isolated serving module: the router/"
                        f"batcher must come up on a machine whose "
                        f"accelerator stack is broken, and stdlib-only "
                        f"consumers (loadgen, doctor, supervise) import "
                        f"this module backend-free — import it lazily "
                        f"inside the function that needs it"))
    return findings


def rule_registry_scope(tree: SourceTree) -> List[Finding]:
    """jax.jit/pjit construction only in registry-owned modules."""
    findings = []
    jit_names = ("jax.jit", "jax.api.jit", "pjit", "jax.pjit",
                 "jax.experimental.pjit.pjit")
    for rel, mod in tree.trees.items():
        if not rel.startswith("tpu_resnet/") \
                or rel in REGISTRY_SCOPE_FILES \
                or rel.startswith(REGISTRY_SCOPE_PREFIXES):
            continue
        aliases = _alias_map(mod)
        sites = []
        for node in ast.walk(mod):
            if isinstance(node, ast.Call) \
                    and _resolved(node.func, aliases) in jit_names:
                sites.append(node.lineno)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    target = dec.func if isinstance(dec, ast.Call) else dec
                    if _resolved(target, aliases) in jit_names:
                        sites.append(dec.lineno)
        for lineno in sorted(set(sites)):
            findings.append(Finding(
                "registry-scope", rel, lineno,
                "direct jax.jit/pjit construction outside the "
                "registry-owned modules: route the program through "
                "tpu_resnet/programs/registry.py (or one of the "
                "canonical constructors in REGISTRY_SCOPE_FILES, "
                "analysis/jaxlint.py) so its key spelling, golden "
                "identity, donation contract and the persistent AOT "
                "executable cache all see it — a bypassed program "
                "re-pays cold-start XLA compiles on every restart and "
                "is invisible to `tpu-resnet check` (docs/CHECKS.md)"))
    return findings


def rule_sharding_scope(tree: SourceTree) -> List[Finding]:
    """NamedSharding/with_sharding_constraint only in partitioner-owned
    modules."""
    findings = []
    target_names = ("jax.sharding.NamedSharding", "NamedSharding",
                    "jax.lax.with_sharding_constraint",
                    "with_sharding_constraint",
                    "jax.experimental.pjit.with_sharding_constraint")
    for rel, mod in tree.trees.items():
        if not rel.startswith("tpu_resnet/") \
                or rel in SHARDING_SCOPE_FILES:
            continue
        aliases = _alias_map(mod)
        sites = []
        for node in ast.walk(mod):
            if isinstance(node, ast.Call) \
                    and _resolved(node.func, aliases) in target_names:
                sites.append(node.lineno)
        for lineno in sorted(set(sites)):
            findings.append(Finding(
                "sharding-scope", rel, lineno,
                "NamedSharding construction / with_sharding_constraint "
                "outside the partitioner-owned modules: sharding "
                "decisions belong to parallel.StatePartitioner and the "
                "documented scope (SHARDING_SCOPE_FILES, "
                "analysis/jaxlint.py) — a sharding injected from "
                "anywhere else changes the compiled program's "
                "collective structure behind the golden comms ledgers' "
                "back (analysis/collectives.py), exactly the drift "
                "check engine 5 exists to catch (docs/CHECKS.md)"))
    return findings


def rule_guard_parity(tree: SourceTree) -> List[Finding]:
    """The fused-kernel guards live in the public constructors (ADVICE r4)."""
    findings = []

    def find_fn(mod: ast.AST, qualname: str) -> Optional[ast.FunctionDef]:
        parts = qualname.split(".")
        scope = mod.body
        node = None
        for i, part in enumerate(parts):
            node = next((n for n in scope
                         if isinstance(n, (ast.FunctionDef, ast.ClassDef))
                         and n.name == part), None)
            if node is None:
                return None
            scope = getattr(node, "body", [])
        return node if isinstance(node, ast.FunctionDef) else None

    for rel, qualname, req, why in GUARD_PARITY_REQS:
        if not tree.has(rel):
            continue
        fn = find_fn(tree.trees[rel], qualname)
        if fn is None:
            findings.append(Finding(
                "guard-parity", rel, 0,
                f"'{qualname}' not found — the guard-parity contract "
                f"names it ({why}); update analysis/jaxlint.py if it "
                f"moved intentionally"))
            continue
        kind, _, arg = req.partition(":")
        ok = False
        if kind == "calls":
            for node in ast.walk(fn):
                if isinstance(node, ast.Call):
                    d = _dotted(node.func) or ""
                    if d == arg or d.endswith("." + arg):
                        ok = True
                        break
        elif kind == "guard":
            idents = set(arg.split("&"))
            for node in ast.walk(fn):
                if isinstance(node, ast.If) \
                        and idents <= _identifiers(node.test) \
                        and any(isinstance(s, ast.Raise)
                                for s in ast.walk(node)):
                    ok = True
                    break
        if not ok:
            need = (f"a call to {arg}()" if kind == "calls"
                    else f"an If over {arg.replace('&', ' and ')} that "
                         f"raises")
            findings.append(Finding(
                "guard-parity", rel, fn.lineno,
                f"'{qualname}' is missing {need}: {why}"))
    return findings


RULES = {
    "jit-host-sync": rule_jit_host_sync,
    "jit-static-args": rule_jit_static_args,
    "fork-safety": rule_fork_safety,
    "signal-safety": rule_signal_safety,
    "host-isolation": rule_host_isolation,
    "registry-scope": rule_registry_scope,
    "sharding-scope": rule_sharding_scope,
    "guard-parity": rule_guard_parity,
}


def run_jaxlint(root: str, select: Optional[Iterable[str]] = None,
                files: Optional[Iterable[str]] = None,
                tree: Optional["SourceTree"] = None) -> List[Finding]:
    """Run the AST rules over ``root``; pragma suppression applied.

    ``select`` limits to a subset of rule ids; ``files`` limits the file
    set (root-relative paths); ``tree`` reuses a pre-parsed SourceTree
    (the CLI parses once and shares it across the AST engines)."""
    tree = tree if tree is not None else SourceTree(root, files=files)
    selected = set(select) if select else set(RULES)
    unknown = selected - set(RULES)
    if unknown:
        raise ValueError(f"unknown rule(s) {sorted(unknown)}; "
                         f"have {sorted(RULES)}")
    findings = list(tree.parse_errors)
    for rule_id in sorted(selected):
        findings.extend(RULES[rule_id](tree))
    return apply_pragmas(findings, tree.sources)
