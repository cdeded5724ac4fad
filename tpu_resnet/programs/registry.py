"""Unified compiled-program registry — one owner for program identity.

Before this module, the spelling that identifies a compiled program
(``train|cifar10_rn50_bf16|mesh8x1|b128``) was re-derived independently
by the FLOPs registry (obs/mfu.py), the memory ledger (obs/memory.py),
the golden-jaxpr/memory check engines (analysis/), and the autotune
decision table (ops/autotune.py) — and the five program-construction
paths (train loop, evaluator, serve bucket warmup, the check engines,
sweep_measure) each built their jitted programs through their own code.
Every one of those paths also re-paid XLA compilation on every process
start, which PR 10's capacity waves and PR 11's rolling upgrades turned
from an exceptional cost into a routine one: serving economics at fleet
scale are set by time-to-ready as much as steady-state throughput, and
pjit-era systems treat ahead-of-time compilation and executable reuse
as a first-class scaling tool (arXiv:2204.06514).

This module owns three things:

``spell`` / ``spell_entry`` / ``spell_shape``
    THE canonical key spelling. ``obs.mfu.train_program_key`` and
    ``ops.autotune.shape_key`` now delegate here, the config-matrix
    verifier asserts every traced entry resolves through it (one key =
    one program), and the cache below is keyed by it.

``ProgramRegistry``
    Per-run handle that routes program construction: when the cache is
    disabled it is an identity pass-through (the exact jit objects the
    constructors always built — golden jaxprs byte-unchanged); when
    enabled it goes ahead-of-time (``jitted.lower(avals).compile()``),
    asserts the donation contract on the lowered program, and
    round-trips the compiled executable through the persistent cache.

``ExecutableCache``
    The persistent cross-process AOT executable cache:
    ``jax.experimental.serialize_executable`` payloads on disk, one file
    per (program key × backend × device-kind × device-count), with the
    jax/jaxlib versions and a sharding/donation **fingerprint** of the
    lowered program recorded in the header. Stale (version or
    fingerprint mismatch), truncated, or corrupt entries are DELETED and
    recompiled — never trusted.

**The PR 1 hazard, engineered around, not ignored.** This jaxlib's CPU
executable deserialization was observed (tests/conftest.py) to (a)
SIGSEGV on the second in-process deserialization of the same entry and
(b) once serve a silently wrong executable. The cache is therefore:

- **cross-process only**: an entry this process just stored is never
  re-loaded by it (the in-memory compiled object is already in hand);
- **load-at-most-once per process**: a process-global ledger of
  deserialized entries; a second request for the same entry recompiles
  instead of deserializing again (``_loaded_once``);
- **fingerprint-verified before use**: every entry records the
  sharding/donation fingerprint of the lowered program it serialized
  (HLO text + donation vector + in/out shardings), plus a
  **precondition digest** over everything lowering is a deterministic
  function of (tpu_resnet source digest, the resolved model/data/optim/
  mesh config, the avals, library versions, XLA flags, the autotune
  decision table). A load first checks the precondition: a match proves
  re-lowering would reproduce the recorded fingerprint, so the entry is
  trusted without paying a fresh trace (the warm-restart fast path); on
  a mismatch the program is re-lowered and the full fingerprint is
  compared — match re-blesses the entry under the new precondition,
  mismatch DELETES it. ``TPU_RESNET_PROGRAM_CACHE_VERIFY=1`` forces the
  re-lowering path on every load (the paranoid switch). Either way a
  cache key collision or a drifted program can never hand back the
  wrong executable;
- **payload-hashed**: the serialized bytes carry their sha256; torn or
  bit-rotted files fail the hash and are deleted, never deserialized;
- **kill-switched**: ``TPU_RESNET_PROGRAM_CACHE=0`` disables every load
  AND store, whatever the config says.

Module import stays jax-free (jax only inside functions) so stdlib-only
consumers (bench parent, perfwatch, doctor) can use the spelling and
inspect cache dirs without a backend.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
import struct
import threading
import time
from typing import Dict, Optional, Tuple

log = logging.getLogger("tpu_resnet")

CACHE_DIR_ENV = "TPU_RESNET_PROGRAM_CACHE_DIR"
CACHE_KILL_ENV = "TPU_RESNET_PROGRAM_CACHE"
CACHE_VERIFY_ENV = "TPU_RESNET_PROGRAM_CACHE_VERIFY"
CACHE_SUBDIR = "progcache"

_MAGIC = b"TPRC1\n"
_FORMAT = 1

class DonationContractError(ValueError):
    """A program the registry was about to cache violates its declared
    donation contract — a real program bug that must surface loudly
    (cached with the wrong donation it would silently double parameter
    HBM for every consumer), unlike the registry's other failure modes,
    which all degrade to plain jit dispatch."""


# Process-global ledger of cache entries already deserialized once:
# this jaxlib segfaults on the SECOND in-process deserialization of an
# entry (PR 1, tests/conftest.py) — a repeat request recompiles instead.
_loaded_once: set = set()
_loaded_lock = threading.Lock()


# ================================================================ spelling
def spell(cfg, mesh_shape: Dict[str, int], kind: str = "train",
          batch: Optional[int] = None) -> str:
    """THE canonical program-key spelling:

        train|cifar10_rn50_bf16|mesh8x1|b128
        train|cifar10_rn8_f32_zero1|mesh8x1|b16
        serve|cifar10_rn50_bf16|mesh1x1|b4
        train|tokens4096_afmoe5l_e8of128_bf16|mesh1x1|b2

    One key names exactly one compiled program (the config-matrix
    coverage check enforces it), so it carries every config dimension
    that changes the traced program. The data part and the model part
    (``cifar10``, ``rn50``; ``tokens4096``, ``afmoe5l_e8of128``) are the
    model family's to spell (``models.Family.spell``), as are the
    suffixes of its own switches (``Family.variants``): the ResNet
    family's are ``_fused`` (block implementation), ``_ep``
    (fused_epilogue forced on) and ``_nos2d`` (ImageNet stem without
    space-to-depth). This function joins them with what every family
    has: the compute dtype, ``_remat``, ``_pr`` (per-replica BN — the
    shard_map dispatch is a different program from the auto-sharded
    sync-BN jit), the partition mode when not replicated, and ``_q8``.
    ``data.engine`` is deliberately NOT part of the key:
    thread and process engines feed byte-identical programs (the
    engine-invariance twins the verifier pins). ``fused_epilogue=auto``
    spells like ``off`` — its dispatch is probe-dependent by design, and
    the executable cache's lowered-program fingerprint (not the key) is
    what guards an auto run against a mismatched cached program.

    ``batch`` overrides ``cfg.train.global_batch_size`` — the serve
    path spells one key per bucket shape.
    """
    from tpu_resnet.models import family

    m = cfg.model
    fam = family(cfg)
    dataset, name = fam.spell(cfg)
    before, after = fam.variants(cfg)
    dtype = {"bfloat16": "bf16", "float32": "f32"}.get(
        m.compute_dtype, m.compute_dtype)
    data_axis = mesh_shape.get("data", 1)
    partition = cfg.mesh.partition
    per_replica = (not m.sync_bn) and data_axis > 1
    quantized = kind == "serve" and cfg.serve.quantize == "int8"
    variant = (before
               + ("_remat" if m.remat else "")
               + after
               + ("_pr" if per_replica else "")
               + (f"_{partition}" if partition != "replicated" else "")
               # Quantized serve programs (serve.quantize=int8) take the
               # int8 argument tree of ops/quant.py — a different
               # signature AND different math, so a different key family
               # (the _ep/_zero1 pattern). Serve-only: training is never
               # quantized here.
               + ("_q8" if quantized else ""))
    b = batch if batch is not None else cfg.train.global_batch_size
    return (f"{kind}|{dataset}_{name}_{dtype}{variant}"
            f"|mesh{data_axis}x{mesh_shape.get('model', 1)}|b{b}")


def spell_entry(entry) -> str:
    """Key for one config-matrix row (analysis/configmatrix.MatrixEntry)
    — the registry-coverage bridge between the check engines and the
    runtime: the verifier asserts every traced entry resolves through
    this, and that no two entries with different programs share a key.
    Staged-chunk rows spell under kind ``chunk`` with their stage/step
    shape appended (``|s8c4``) — matching the sub-keys the train loop's
    registry uses for its per-chunk programs, because the fused
    multi-step dispatch is a different program per (stage, c). The
    FLOPs/memory entries of a RUN keep kind ``train`` — one run entry
    covers all its dispatch shapes, as documented there."""
    if getattr(entry, "builder", "config") == "staged-chunk":
        base = spell(entry.to_config(),
                     {"data": entry.data_axis, "model": entry.model_axis},
                     kind="chunk", batch=entry.batch)
        return f"{base}|s{entry.stage_rows}c{entry.chunk_steps}"
    if getattr(entry, "builder", "config") == "serve":
        # Serve rows spell under kind "serve" — the exact bucket keys the
        # CheckpointBackend's registry uses (quantized rows pick up the
        # _q8 suffix from serve.quantize in to_config()).
        return spell(entry.to_config(),
                     {"data": entry.data_axis, "model": entry.model_axis},
                     kind="serve", batch=entry.batch)
    return spell(entry.to_config(),
                 {"data": entry.data_axis, "model": entry.model_axis},
                 kind="train", batch=entry.batch)


def spell_shape(*dims) -> str:
    """Canonical shape-key spelling, e.g. ``b128x1000`` — the autotune
    decision table's key (ops/autotune.py delegates here)."""
    return "x".join(str(int(d)) for d in dims)


# ============================================================= fingerprint
def fingerprint_lowered(lowered) -> str:
    """Sharding/donation fingerprint of a lowered program: sha256 over
    the canonicalized module text, the per-leaf donation vector, and the
    input/output sharding reprs. Two programs with the same key but
    different math, donation, or layout can never exchange executables —
    the "silently wrong executable" incident class (PR 1) is excluded
    by construction, not by hope."""
    import jax

    from tpu_resnet.analysis.configmatrix import canonicalize

    parts = [canonicalize(lowered.as_text())]
    parts.append(repr([bool(i.donated) for i in
                       jax.tree_util.tree_leaves(lowered.args_info)]))
    for tree in (lowered.in_avals, lowered.out_info):
        parts.append(repr([(tuple(x.shape), str(x.dtype),
                            str(getattr(x, "sharding", None)))
                           for x in jax.tree_util.tree_leaves(tree)]))
    return hashlib.sha256("\n".join(parts).encode()).hexdigest()


_source_digest_cache: Dict[str, str] = {}


def source_digest() -> str:
    """sha256 over every ``.py`` file of the installed tpu_resnet
    package (path + content), computed once per process (~15 ms). The
    coarse half of the cache precondition: ANY source edit — model
    code, step construction, a helper three imports away — invalidates
    every fast-path load, because lowering is a function of the whole
    package and a precondition must never be cleverer than that."""
    if "v" in _source_digest_cache:
        return _source_digest_cache["v"]
    import tpu_resnet

    root = os.path.dirname(os.path.abspath(tpu_resnet.__file__))
    h = hashlib.sha256()
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(d for d in dirnames
                             if d != "__pycache__" and not d.startswith("."))
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            h.update(os.path.relpath(path, root).encode())
            try:
                with open(path, "rb") as f:
                    h.update(f.read())
            except OSError:
                h.update(b"<unreadable>")
    _source_digest_cache["v"] = h.hexdigest()
    return _source_digest_cache["v"]


def default_cache_dir(cfg) -> str:
    """<train_dir>/progcache — the per-run default when the cache is on
    but no explicit directory was configured. Serve replicas restarting
    against one train_dir (the PR 11 rolling-upgrade window) land on the
    same directory and hit each other's entries."""
    return os.path.join(cfg.train.train_dir, CACHE_SUBDIR)


# ============================================================ on-disk cache
class ExecutableCache:
    """Persistent cross-process AOT executable cache.

    One file per (program key × backend × device-kind × device-count):
    ``<sha16>.aotx`` = magic + header-JSON + pickled
    ``serialize_executable.serialize`` payload. The header records the
    producing jax/jaxlib versions, the program fingerprint, and the
    payload sha256; any mismatch on load DELETES the entry and reports a
    miss (the caller recompiles and overwrites). Writes are atomic
    (tmp + rename) so concurrent replicas never read a torn entry."""

    def __init__(self, cache_dir: str):
        self.dir = cache_dir
        self.evictions = 0

    # -------------------------------------------------------------- naming
    @staticmethod
    def _env() -> dict:
        import jax
        import jaxlib

        dev = jax.devices()[0]
        return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                "backend": jax.default_backend(),
                "device_kind": str(getattr(dev, "device_kind", "?")),
                "n_devices": int(jax.device_count())}

    def path_for(self, key: str, env: dict) -> str:
        material = "|".join((key, env["backend"], env["device_kind"],
                             str(env["n_devices"])))
        digest = hashlib.sha256(material.encode()).hexdigest()[:24]
        return os.path.join(self.dir, f"{digest}.aotx")

    # --------------------------------------------------------------- store
    def _write(self, path: str, header: dict, payload: bytes
               ) -> Optional[str]:
        hdr = json.dumps(header, sort_keys=True).encode()
        try:
            os.makedirs(self.dir, exist_ok=True)
            tmp = path + f".tmp{os.getpid()}"
            with open(tmp, "wb") as f:
                f.write(_MAGIC)
                f.write(struct.pack(">I", len(hdr)))
                f.write(hdr)
                f.write(payload)
            os.replace(tmp, path)
            return path
        except OSError as e:
            log.warning("program cache: cannot write %s: %s", path, e)
            return None

    def store(self, key: str, fingerprint: str, precondition: str,
              compiled) -> Optional[str]:
        """Serialize ``compiled`` under ``key``; best-effort (a cache
        that cannot write must never fail the run). Returns the path or
        None."""
        from jax.experimental import serialize_executable

        try:
            payload = pickle.dumps(serialize_executable.serialize(compiled))
        except Exception as e:  # noqa: BLE001 - backend-specific
            log.warning("program cache: cannot serialize %s (%s: %s)",
                        key, type(e).__name__, e)
            return None
        env = self._env()
        header = dict(env, format=_FORMAT, key=key,
                      fingerprint=fingerprint,
                      precondition=precondition,
                      # The devices the program was compiled for: a load
                      # must hand exactly these back, or jax defaults to
                      # every device of the backend and a 1-device program
                      # on an N-device host rejects its arguments.
                      device_ids=[int(d.id) for d in compiled
                                  .runtime_executable().local_devices()],
                      payload_sha256=hashlib.sha256(payload).hexdigest(),
                      payload_bytes=len(payload),
                      created_unix=round(time.time(), 3))
        return self._write(self.path_for(key, env), header, payload)

    # ---------------------------------------------------------------- load
    def read_header(self, path: str) -> Optional[dict]:
        """Header of one entry file (None when unreadable/corrupt)."""
        try:
            with open(path, "rb") as f:
                if f.read(len(_MAGIC)) != _MAGIC:
                    return None
                (n,) = struct.unpack(">I", f.read(4))
                return json.loads(f.read(n))
        except (OSError, ValueError, struct.error):
            return None

    def _evict(self, path: str, why: str) -> None:
        self.evictions += 1
        log.warning("program cache: evicting %s (%s) — will recompile",
                    os.path.basename(path), why)
        try:
            os.remove(path)
        except OSError:
            pass

    def _read_checked(self, key: str):
        """(path, header, payload) for ``key`` after the structural and
        environment checks shared by both load paths: magic, header
        parse, jax/jaxlib/backend/device-kind/count match, format/key
        match, payload sha256. Every failure evicts and returns None —
        a torn or stale entry is never deserialized."""
        env = self._env()
        path = self.path_for(key, env)
        if not os.path.exists(path):
            return None
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except OSError:
            return None
        if not blob.startswith(_MAGIC) or len(blob) < len(_MAGIC) + 4:
            self._evict(path, "bad magic/truncated")
            return None
        try:
            (n,) = struct.unpack(
                ">I", blob[len(_MAGIC):len(_MAGIC) + 4])
            header = json.loads(blob[len(_MAGIC) + 4:len(_MAGIC) + 4 + n])
            payload = blob[len(_MAGIC) + 4 + n:]
        except (ValueError, struct.error):
            self._evict(path, "corrupt header")
            return None
        for field in ("jax", "jaxlib", "backend", "device_kind",
                      "n_devices"):
            if header.get(field) != env[field]:
                self._evict(path, f"{field} mismatch "
                                  f"({header.get(field)!r} != "
                                  f"{env[field]!r})")
                return None
        if header.get("format") != _FORMAT or header.get("key") != key:
            self._evict(path, "format/key mismatch")
            return None
        if hashlib.sha256(payload).hexdigest() != \
                header.get("payload_sha256"):
            self._evict(path, "payload hash mismatch (torn/bit-rot)")
            return None
        return path, header, payload

    def _deserialize(self, key: str, path: str, header: dict,
                     payload: bytes):
        with _loaded_lock:
            if path in _loaded_once:
                # PR 1 hazard: this jaxlib segfaults on the SECOND
                # in-process deserialization of an entry. Recompile.
                log.info("program cache: %s already deserialized once in "
                         "this process — recompiling instead of a second "
                         "deserialization (PR 1 hazard)", key)
                return None
            _loaded_once.add(path)
        import jax
        from jax.experimental import serialize_executable

        try:
            by_id = {int(d.id): d for d in jax.devices()}
            ser, in_tree, out_tree = pickle.loads(payload)
            return serialize_executable.deserialize_and_load(
                ser, in_tree, out_tree,
                execution_devices=[by_id[i] for i in header["device_ids"]])
        except Exception as e:  # noqa: BLE001 - never crash on a cache
            self._evict(path, f"deserialization failed "
                              f"({type(e).__name__}: {e})")
            return None

    def load_fast(self, key: str, precondition: str):
        """The warm-restart fast path: trust the entry WITHOUT
        re-lowering when its recorded precondition digest matches —
        lowering is a deterministic function of everything the digest
        covers, so a match proves a fresh trace would reproduce the
        recorded fingerprint. None on any mismatch (the caller then
        takes :meth:`load_verified`, which re-lowers)."""
        found = self._read_checked(key)
        if found is None:
            return None
        path, header, payload = found
        if not precondition or header.get("precondition") != precondition:
            return None  # not evicted: load_verified decides its fate
        return self._deserialize(key, path, header, payload)

    def load_verified(self, key: str, fingerprint: str,
                      precondition: str = ""):
        """The full check: the entry's recorded lowered-program
        fingerprint must equal ``fingerprint`` (computed by the caller
        from a FRESH lowering). A match under a new ``precondition``
        re-blesses the entry (header rewritten) so the next restart
        takes the fast path again; a mismatch means the program for
        this key CHANGED — serving the entry anyway is the PR 1
        incident, so it is deleted instead."""
        found = self._read_checked(key)
        if found is None:
            return None
        path, header, payload = found
        if header.get("fingerprint") != fingerprint:
            self._evict(path, "program fingerprint drifted")
            return None
        if precondition and header.get("precondition") != precondition:
            header["precondition"] = precondition
            self._write(path, header, payload)
        return self._deserialize(key, path, header, payload)


# =============================================================== programs
class _Program:
    """A registry-built program: the AOT executable (cached or freshly
    compiled), with the plain jitted function for calls whose argument
    SHAPES differ from the compiled signature (jax raises TypeError
    before anything runs) — those pay one normal jit compile. Every
    other rejection (devices, shardings, a loaded executable that
    cannot run) raises: it means the cache handed back a program that
    does not fit this process, which must be seen, not papered over."""

    def __init__(self, compiled, jitted, key: str):
        self._compiled = compiled
        self._jitted = jitted
        self.key = key

    def __call__(self, *args):
        try:
            return self._compiled(*args)
        except TypeError as e:
            log.warning("program %s: call does not match the compiled "
                        "signature (%s) — jit dispatch for this call",
                        self.key, e)
            return self._jitted(*args)


class ProgramRegistry:
    """Per-run program-construction front door.

    ``context`` selects the cache default under ``programs.cache=auto``:
    serve replicas cache by default (cold start IS their cost model —
    the PR 11 rolling-upgrade window); train/eval/sweep cache only when
    a directory is configured (``programs.cache_dir`` or the
    ``TPU_RESNET_PROGRAM_CACHE_DIR`` env — the elastic-resume and sweep
    levers). ``TPU_RESNET_PROGRAM_CACHE=0`` kills the cache everywhere.

    With the cache disabled every ``wrap``/builder call returns its
    input jit object untouched: the registry is an identity transform
    on compiled programs (the golden-jaxpr acceptance contract)."""

    def __init__(self, cfg, mesh=None, telemetry=None, spans=None,
                 cache_dir: Optional[str] = None, context: str = "train"):
        self.cfg = cfg
        self.mesh = mesh
        self.telemetry = telemetry
        self.spans = spans
        self.context = context
        self.hits = 0
        self.misses = 0
        mode = str(getattr(getattr(cfg, "programs", None), "cache",
                           "auto")).lower()
        if mode not in ("auto", "on", "off"):
            raise ValueError(f"programs.cache must be auto|on|off, "
                             f"got {mode!r}")
        configured = (cache_dir
                      or getattr(getattr(cfg, "programs", None),
                                 "cache_dir", "")
                      or os.environ.get(CACHE_DIR_ENV, ""))
        if os.environ.get(CACHE_KILL_ENV, "1") == "0":
            enabled = False  # the operator's hard off-switch
        elif mode == "off":
            enabled = False
        elif mode == "on":
            enabled = True
        else:  # auto
            enabled = bool(configured) or context == "serve"
        self.cache: Optional[ExecutableCache] = None
        if enabled:
            self.cache = ExecutableCache(
                configured or default_cache_dir(cfg))

    # ------------------------------------------------------------- spelling
    @property
    def cache_enabled(self) -> bool:
        return self.cache is not None

    def key(self, kind: str = "train", batch: Optional[int] = None) -> str:
        mesh_shape = dict(self.mesh.shape) if self.mesh is not None else {}
        return spell(self.cfg, mesh_shape, kind=kind, batch=batch)

    # ------------------------------------------------------------ telemetry
    def _count(self, hit: bool) -> None:
        if hit:
            self.hits += 1
        else:
            self.misses += 1
        if self.telemetry is not None:
            try:
                self.telemetry.set("compile_cache_hits", float(self.hits))
                self.telemetry.set("compile_cache_misses",
                                   float(self.misses))
            except Exception:  # noqa: BLE001 - accounting must not kill
                pass

    def stats(self) -> dict:
        return {"compile_cache_hits": self.hits,
                "compile_cache_misses": self.misses,
                "cache_dir": self.cache.dir if self.cache else None,
                "evictions": self.cache.evictions if self.cache else 0}

    # ----------------------------------------------------------- assertions
    @staticmethod
    def assert_donation(lowered, key: str, donated_args=()) -> None:
        """The registry's donation contract on a program it is about to
        cache: every leaf of each argument index in ``donated_args``
        must be donated in the lowered program, and no other argument
        may be. An executable cached with the wrong donation would
        silently double parameter HBM on every consumer — fail loudly
        at build time instead."""
        import jax

        info = lowered.args_info
        args = info[0] if isinstance(info, tuple) and len(info) == 2 \
            and isinstance(info[1], dict) else info
        for i, arg in enumerate(args):
            leaves = jax.tree_util.tree_leaves(arg)
            donated = [bool(leaf.donated) for leaf in leaves]
            if i in donated_args and not all(donated):
                raise DonationContractError(
                    f"program {key}: argument {i} must be fully donated "
                    f"but {donated.count(False)}/{len(donated)} leaves "
                    f"are not — the donation contract the registry "
                    f"certifies (docs/CHECKS.md) is broken")
            if i not in donated_args and any(donated):
                raise DonationContractError(
                    f"program {key}: argument {i} is donated but only "
                    f"{tuple(donated_args)} may be — an input buffer "
                    f"a consumer still owns would be invalidated")

    # --------------------------------------------------------- precondition
    def _precondition(self, avals: Tuple) -> str:
        """Digest over everything lowering is a deterministic function
        of, short of the trace itself: the package source digest, the
        resolved model/data/optim/mesh config sections, the argument
        avals (shape/dtype/sharding), library versions, XLA/x64 flags,
        and the autotune decision table (probe-dependent dispatch —
        ops/autotune.py — is trace-time input too). A matching digest
        lets a load trust the recorded lowered-program fingerprint
        without re-paying the trace; anything uncovered lands in the
        slow path, never in a wrong executable."""
        import jax

        from tpu_resnet.ops import autotune

        cfg_dict = self.cfg.to_dict()
        sections = {k: cfg_dict.get(k)
                    for k in ("model", "data", "optim", "mesh")}
        leaves = [(tuple(x.shape), str(x.dtype),
                   str(getattr(x, "sharding", None)))
                  for x in jax.tree_util.tree_leaves(avals)]
        versions = {}
        for mod in ("flax", "optax", "numpy"):
            try:
                versions[mod] = __import__(mod).__version__
            except Exception:  # noqa: BLE001
                versions[mod] = "?"
        # Only the DISPATCH-relevant slice of the autotune table: the
        # trace reads use_pallas() per (op, shape), never the measured
        # microsecond timings — digesting those would change the digest
        # every process and permanently defeat the fast path for
        # exactly the auto-dispatch configs it targets.
        dispatch = {k: bool(v.get("use_pallas"))
                    for k, v in autotune.decisions().items()}
        material = json.dumps(
            {"source": source_digest(), "config": sections,
             "avals": leaves, "versions": versions,
             "xla_flags": os.environ.get("XLA_FLAGS", ""),
             "x64": os.environ.get("JAX_ENABLE_X64", ""),
             "autotune": dispatch},
            sort_keys=True, default=str)
        return hashlib.sha256(material.encode()).hexdigest()

    # ------------------------------------------------------------- the core
    def wrap(self, key: str, jitted, avals: Tuple,
             donated_args: Tuple[int, ...] = ()):
        """Route one program through the registry: identity when the
        cache is off; else AOT-compile (or cache-load) over ``avals``
        and return a :class:`_Program`. Returns ``(program,
        cache_hit)``. A cache entry that cannot be read or loaded is
        evicted and recompiled (a logged miss); a lowering or compile
        error raises, exactly as it would under jit dispatch.

        Load order: precondition fast path (no re-trace) →
        fingerprint-verified path (fresh lowering; re-blesses or evicts
        the entry) → AOT compile + store. ``TPU_RESNET_PROGRAM_CACHE_VERIFY=1``
        skips the fast path so every load re-verifies the full
        fingerprint."""
        if self.cache is None:
            return jitted, False
        t0 = time.time()
        pre = self._precondition(avals)
        if os.environ.get(CACHE_VERIFY_ENV, "0") != "1":
            loaded = self.cache.load_fast(key, pre)
            if loaded is not None:
                self._count(True)
                self._span(key, t0, hit=True, verified="precondition")
                return _Program(loaded, jitted, key), True
        lowered = jitted.lower(*avals)
        fp = fingerprint_lowered(lowered)
        loaded = self.cache.load_verified(key, fp, precondition=pre)
        if loaded is not None:
            self._count(True)
            self._span(key, t0, hit=True, verified="fingerprint")
            return _Program(loaded, jitted, key), True
        compiled = lowered.compile()
        self.assert_donation(lowered, key, donated_args)
        self.cache.store(key, fp, pre, compiled)
        self._count(False)
        self._span(key, t0, hit=False)
        return _Program(compiled, jitted, key), False

    def _span(self, key: str, t0: float, hit: bool,
              verified: str = "") -> None:
        if self.spans is None:
            return
        try:
            attrs = {"program_key": key, "cache_hit": hit}
            if verified:
                attrs["verified_by"] = verified
            self.spans.record("cache_load", t0, time.time(), **attrs)
        except Exception:  # noqa: BLE001
            pass


def state_avals(state):
    """ShapeDtypeStruct avals (shardings included) of a concrete state
    tree — what the registry lowers train programs over. One helper so
    every caller spells avals identically."""
    import jax

    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=x.sharding), state)


# Every dataset's host pipeline delivers raw uint8 images; the float
# conversion and the augmentation are on-device, inside the step.
BATCH_DTYPE = "uint8"


def init_program(model):
    """``init(rng, sample, train=False)`` of a model as ONE program. A
    model whose forward pass is many operations (a transformer) is drawn
    by one compile, not by one small eager program an operation. Only
    the state comes back (``params``, ``batch_stats``): nothing of the
    forward pass on ``sample`` is then alive, and the compiler drops it,
    kernels and all (22 s of 24 for the block-diffusion preset, compiled
    for a described v5e; what a forward pass sows, ``counters``, is drawn
    again by every step)."""
    import jax

    def state_only(rng, sample, train):
        drawn = model.init(rng, sample, train=train)
        return {k: v for k, v in drawn.items()
                if k in ("params", "batch_stats")}

    return jax.jit(state_only, static_argnames=("train",))


def batch_avals(cfg, sharding=None, rows: int = 0):
    """``(inputs, labels)`` avals of one global batch as the input edge
    hands it to the step or, with ``rows``, of a staged superbatch of that
    many: uint8 images with a label an example, or int32 ids with a label
    a position. The one place the check engines, the ledgers and the
    executable cache spell a batch."""
    import jax

    from tpu_resnet.models import family

    lead = ((rows,) if rows else ()) + (cfg.train.global_batch_size,)
    kw = {} if sharding is None else {"sharding": sharding}
    if family(cfg).inputs == "tokens":
        ids = jax.ShapeDtypeStruct(lead + (cfg.data.seq_len,), "int32", **kw)
        return ids, ids
    size = cfg.data.resolved_image_size
    return (jax.ShapeDtypeStruct(lead + (size, size, 3), BATCH_DTYPE, **kw),
            jax.ShapeDtypeStruct(lead, "int32", **kw))


def wrap_train_step(registry: ProgramRegistry, step_fn, avals,
                    donate_state: bool = True):
    """Route the single-step train program through the registry over
    the canonical batch avals. The one spelling of the single-step key
    (+``|nodon`` for the sweep's donation knob), shared by the train
    loop and sweep_measure so their cache entries can never drift."""
    from tpu_resnet import parallel

    program, _ = registry.wrap(
        registry.key("train") + ("" if donate_state else "|nodon"),
        step_fn,
        (avals,) + batch_avals(registry.cfg,
                               parallel.batch_sharding(registry.mesh)),
        donated_args=(0,) if donate_state else ())
    return program


def staged_chunk_hook(registry: ProgramRegistry, avals, rows: int,
                      donate_state: bool = True):
    """``program_hook`` for ``device_data.compile_staged_stream_steps``
    / ``compile_resident_steps``: routes each per-``c`` chunk jit
    through the registry under the canonical
    ``chunk|…[|nodon]|s{rows}c{c}`` key over the canonical staged
    avals. One constructor (train loop AND sweep_measure) so the
    one-key-one-program invariant can't be broken by two drifting
    copies."""
    import jax

    from tpu_resnet import parallel

    gi, gl = batch_avals(registry.cfg,
                         parallel.staged_batch_sharding(registry.mesh),
                         rows=rows)
    off = jax.ShapeDtypeStruct((), "int32")
    base_key = registry.key("chunk") + ("" if donate_state else "|nodon")
    donated = (0,) if donate_state else ()

    def hook(c, jitted):
        program, _ = registry.wrap(f"{base_key}|s{rows}c{c}", jitted,
                                   (avals, gi, gl, off),
                                   donated_args=donated)
        return program

    return hook
