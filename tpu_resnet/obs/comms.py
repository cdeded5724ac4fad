"""Comms observability — the wire twin of the FLOPs and HBM accounting.

The reference system's entire distributed story was its collective
structure (SURVEY §2: ``SyncReplicasOptimizer``, Horovod allreduce), and
every remaining scaling direction — the 2-D ("data","model") mesh
multi-host push, ZeRO-2/3 — stands or falls on putting exactly the
right collectives on exactly the right mesh axes. ``obs/mfu.py`` gave a
run its compute truth and ``obs/memory.py`` its space truth; this
module gives it the third axis: what the compiled program puts ON THE
WIRE per step, measured once at startup and pinned golden by the
collectives check engine (``analysis/collectives.py``).

``extract_collectives``   every collective op (all-reduce, all-gather,
                          reduce-scatter, collective-permute,
                          all-to-all) from a compiled program's HLO
                          module text, with payload bytes, replica
                          groups (both the explicit ``{{0,2},{1,3}}``
                          and the iota ``[2,4]<=[4,2]T(1,0)`` forms)
                          and a mesh-axis bucket (data / model / all /
                          mixed) derived from the run's (data, model)
                          mesh shape.
``summarize_collectives`` the per-program comms budget: op multiset,
                          canonical structure signature, analytic
                          bytes-on-wire per step bucketed by mesh axis
                          (ring-algorithm cost model), and the ZeRO
                          exchange components (reduce-scatter /
                          all-gather / plain all-reduce bytes) the
                          zero1 twin gate reads.
``CommsLedger``           per-compiled-program comms entries keyed
                          EXACTLY like ``flops.json`` / ``memory.json``
                          (``registry.spell``), persisted to
                          ``<train_dir>/comms.json``.
``ICI_BYTES_BY_KIND``     per-chip interconnect bandwidth (public chip
                          specs) — the ``HBM_BYTES_BY_KIND`` pattern,
                          ``TPU_RESNET_ICI_BYTES`` override — feeding
                          the predicted time-on-wire and the
                          ``predicted_comms_fraction`` gauge.

One subtlety the parser owns so every consumer doesn't have to: XLA's
CPU pipeline runs the reduce-scatter DECOMPOSER (reduce-scatter becomes
a full all-reduce whose result is immediately sliced), so a ZeRO-1
gradient exchange never shows a literal ``reduce-scatter`` op in a CPU
compile. ``extract_collectives`` re-derives the LOGICAL op: an
all-reduced array whose every consumer keeps at most ``1/group_size``
of it is classified (and costed) as a reduce-scatter; ``raw_op`` keeps
the opcode actually emitted. A four-chip v5e compile (jax 0.9.0, PR 21)
showed the same form, not a literal op: the zero1 gradient exchange was
one combined tuple all-reduce (bf16) whose elements are then sliced —
so on that backend the ring cost of the re-derived op UNDER-counts the
wire, which carries the full all-reduce. A literal ``reduce-scatter``
classifies identically where a compiler emits one.

Like the FLOPs/HBM accountants this pays its cost ONCE per run at first
dispatch (one extra XLA compile, gated by ``train.comms_ledger``,
charged to the compile window) and degrades to absent — never a
per-step cost. Module import stays jax-free (jax only inside functions)
so stdlib-only consumers (tools/perfwatch.py, the doctor checks, the
analysis engines' compare paths) can parse HLO text and read ledger
files without a backend.
"""
# check: disable-file=jit-host-sync — this module IS the host-side
# comms prober: compiled-program introspection at startup/check time
# only, never from jit scope.

from __future__ import annotations

import dataclasses
import json
import logging
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

log = logging.getLogger("tpu_resnet")

LEDGER_FILE = "comms.json"

# Per-chip aggregate inter-chip-interconnect bandwidth in bytes/s by
# device_kind substring (public Cloud TPU chip specs: v4 2400 Gb/s, v5e
# 1600 Gb/s, v5p 4800 Gb/s, v6e 3584 Gb/s per chip) — the comms twin of
# mfu.PEAK_FLOPS_BY_KIND / memory.HBM_BYTES_BY_KIND. Order matters:
# more specific names first.
_GBPS = 1e9 / 8
ICI_BYTES_BY_KIND = (
    ("v5p", 4800 * _GBPS),
    ("v5 lite", 1600 * _GBPS), ("v5e", 1600 * _GBPS),
    ("v5litepod", 1600 * _GBPS),
    ("v6 lite", 3584 * _GBPS), ("v6e", 3584 * _GBPS),
    ("v4", 2400 * _GBPS),
)

COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "collective-permute", "all-to-all")
FLOAT_DTYPES = {"f16", "bf16", "f32", "f64"}

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1,
                "f8e4m3fn": 1, "f8e5m2": 1,
                "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
                "s32": 4, "u32": 4, "f32": 4,
                "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16}

_SHAPE_RE = re.compile(r"([a-z]\w*)\[([0-9,]*)\]")
# A tuple type may hold parentheses of its own — TPU layouts print their
# tiling inside the braces, ``f32[512,128]{1,0:T(8,128)}`` — so it runs
# (non-greedily) up to the ``) opcode(`` that ends it, not to the first ")".
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%(?P<name>[\w.-]+)\s*=\s*"
    r"(?P<type>\(.*?\)|[a-z]\w*\[[0-9,]*\](?:\{[^}]*\})?)\s+"
    r"(?P<op>[\w-]+)\(")
_REF_RE = re.compile(r"%([\w.-]+)")
_GTE_INDEX_RE = re.compile(r"get-tuple-element\(.*\), index=(\d+)\b")
_EXPLICIT_GROUPS_RE = re.compile(r"replica_groups=\{(\{[0-9, ]*\}"
                                 r"(?:,\{[0-9, ]*\})*)?\}")
_IOTA_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]"
                             r"<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?")
_PAIRS_RE = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)*)\}")


def ici_bytes_per_chip(device_kind: str,
                       env_var: str = "TPU_RESNET_ICI_BYTES"
                       ) -> Optional[float]:
    """Aggregate ICI bandwidth in bytes/s for one chip of
    ``device_kind``; None when the kind is unknown (CPU, new silicon).
    ``env_var`` overrides the table — the escape hatch for chips it
    hasn't learned yet (and how CPU CI exercises the prediction path)."""
    env = os.environ.get(env_var)
    if env:
        try:
            return float(env)
        except ValueError:
            log.warning("ignoring non-numeric %s=%r", env_var, env)
    kind = (device_kind or "").lower()
    for sub, bw in ICI_BYTES_BY_KIND:
        if sub in kind:
            return bw
    return None


def _type_bytes(type_text: str) -> int:
    """Total bytes of an HLO result/operand type string — scalar
    (``f32[]``), array (``f32[3,3,16,16]{3,2,1,0}``) or tuple (every
    array inside the parens summed)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _type_dtype(type_text: str) -> str:
    m = _SHAPE_RE.search(type_text)
    return m.group(1) if m else "?"


def _iota_groups(n_groups: int, group_size: int, dims: Sequence[int],
                 perm: Optional[Sequence[int]]) -> List[Tuple[int, ...]]:
    """Expand XLA's IotaReplicaGroupList form
    ``[n_groups,group_size]<=[dims]T(perm)``: device ids are
    ``iota(prod(dims))`` reshaped to ``dims``, transposed by ``perm``,
    then reshaped row-major to ``[n_groups, group_size]``."""
    dims = list(dims)
    perm = list(perm) if perm is not None else list(range(len(dims)))
    pdims = [dims[p] for p in perm]
    total = 1
    for d in dims:
        total *= d
    flat: List[int] = []
    coords = [0] * len(pdims)
    for _ in range(max(total, 0)):
        orig = [0] * len(dims)
        for k, p in enumerate(perm):
            orig[p] = coords[k]
        v = 0
        for d, c in zip(dims, orig):
            v = v * d + c
        flat.append(v)
        for k in reversed(range(len(coords))):
            coords[k] += 1
            if coords[k] < pdims[k]:
                break
            coords[k] = 0
    return [tuple(flat[i * group_size:(i + 1) * group_size])
            for i in range(n_groups)]


def _parse_groups(line: str, n_devices: int) -> List[Tuple[int, ...]]:
    """Replica groups of one collective line, in either HLO spelling;
    empty ``replica_groups={}`` means one group of every device."""
    m = _IOTA_GROUPS_RE.search(line)
    if m:
        dims = [int(d) for d in m.group(3).split(",")]
        perm = ([int(p) for p in m.group(4).split(",")]
                if m.group(4) else None)
        return _iota_groups(int(m.group(1)), int(m.group(2)), dims, perm)
    m = _EXPLICIT_GROUPS_RE.search(line)
    if m:
        if not m.group(1):
            return [tuple(range(n_devices))]
        return [tuple(int(x) for x in g.split(",") if x.strip())
                for g in re.findall(r"\{([0-9, ]*)\}", m.group(1))]
    m = _PAIRS_RE.search(line)
    if m and m.group(1):
        return [tuple(int(x) for x in p.split(","))
                for p in re.findall(r"\{(\d+,\d+)\}", m.group(1))]
    return [tuple(range(n_devices))]


def classify_groups(groups: Sequence[Tuple[int, ...]], data_axis: int,
                    model_axis: int) -> str:
    """Mesh-axis bucket of a collective's replica groups on the
    row-major ("data","model") device mesh: ``"data"`` / ``"model"``
    (groups vary exactly one mesh coordinate), ``"all"`` (one group,
    the full mesh), ``"mixed"`` (both coordinates vary in a group that
    is NOT the whole mesh — the axis-confinement violation), ``"self"``
    (degenerate single-member groups). On a 1-D mesh (model_axis == 1)
    the full mesh classifies as ``"data"`` — there is no second axis to
    confuse it with."""
    n = data_axis * model_axis
    buckets = set()
    for g in groups:
        members = set(g)
        if len(members) <= 1:
            buckets.add("self")
            continue
        d_varies = len({i // model_axis for i in members}) > 1
        m_varies = len({i % model_axis for i in members}) > 1
        if d_varies and m_varies:
            buckets.add("all" if len(members) == n and len(groups) == 1
                        else "mixed")
        elif d_varies:
            buckets.add("data")
        elif m_varies:
            buckets.add("model")
    buckets.discard("self")
    if not buckets:
        return "self"
    if len(buckets) == 1:
        return buckets.pop()
    return "mixed"


@dataclasses.dataclass
class Collective:
    """One collective op extracted from compiled HLO: the effective op
    (decomposed reduce-scatter re-derived), full logical payload bytes,
    replica-group shape and the analytic per-device bytes-on-wire under
    the ring cost model."""
    op: str                # effective op (all-reduce | all-gather | ...)
    raw_op: str            # opcode as spelled in the HLO text
    name: str              # instruction name
    dtype: str
    payload_bytes: int     # full (unsharded) logical payload
    group_size: int
    n_groups: int
    bucket: str            # data | model | all | mixed | self
    wire_bytes: float      # per participating device, per execution

    def signature(self) -> str:
        """Canonical structure key: effective op, payload dtype+bytes,
        mesh-axis bucket and group size — the multiset the golden
        compare pins (instruction names and channel ids are compiler
        noise and deliberately excluded)."""
        return (f"{self.op}|{self.dtype}:{self.payload_bytes}b"
                f"|{self.bucket}|g{self.group_size}")


def _split_computations(hlo_text: str) -> List[List[str]]:
    """HLO module text → instruction-line blocks, one per computation
    (collectives and their consumers always live in the same
    computation; fusions are separate blocks)."""
    blocks: List[List[str]] = []
    current: Optional[List[str]] = None
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if stripped.endswith("{") and ("(" in stripped or
                                       stripped.startswith(("ENTRY", "%"))):
            current = []
            continue
        if stripped == "}":
            if current:
                blocks.append(current)
            current = None
            continue
        if current is not None and stripped:
            current.append(line)
    if current:
        blocks.append(current)
    return blocks


def _ring_wire_bytes(op: str, payload: int, group_size: int) -> float:
    """Per-device bytes-on-wire of one collective under the standard
    ring algorithms (payload S, group size G): all-reduce moves
    2·S·(G−1)/G (reduce-scatter phase + all-gather phase), all-gather /
    reduce-scatter / all-to-all move S·(G−1)/G, collective-permute
    forwards the payload once."""
    g = max(group_size, 1)
    if g == 1:
        return 0.0
    if op == "all-reduce":
        return 2.0 * payload * (g - 1) / g
    if op in ("all-gather", "reduce-scatter", "all-to-all"):
        return float(payload) * (g - 1) / g
    return float(payload)  # collective-permute


def extract_collectives(hlo_text: str, data_axis: int,
                        model_axis: int) -> List[Collective]:
    """Every collective op in ``hlo_text`` (post-SPMD-partitioner HLO —
    collectives only exist after partitioning) with payloads, groups,
    axis buckets and ring-model wire bytes. Async ``-start``/``-done``
    pairs count once; an all-reduced array whose consumers all keep at
    most ``1/group_size`` of it is re-derived as the logical
    reduce-scatter XLA's CPU decomposer hid (see module docstring) — per
    element when the combiner merged reductions into one tuple op."""
    n_devices = max(data_axis * model_axis, 1)
    out: List[Collective] = []
    for block in _split_computations(hlo_text):
        instrs = []  # (name, result_bytes, match, line)
        users = {}   # operand name -> [(user name, user bytes, user line)]
        for line in block:
            m = _INSTR_RE.match(line)
            if m:
                rec = (m.group("name"), _type_bytes(m.group("type")), m,
                       line)
                instrs.append(rec)
                for ref in set(_REF_RE.findall(line.split(" = ", 1)[-1])):
                    if ref != rec[0]:
                        users.setdefault(ref, []).append(
                            (rec[0], rec[1], line))
        for name, result_bytes, m, line in instrs:
            raw_op = m.group("op")
            base_op = raw_op[:-6] if raw_op.endswith("-start") else raw_op
            if base_op not in COLLECTIVE_OPS:
                continue
            type_text = m.group("type")
            groups = _parse_groups(line, n_devices)
            group_size = max((len(set(g)) for g in groups), default=1)
            if base_op == "collective-permute":
                # source_target_pairs: payload forwarded once per pair;
                # per-device cost is one payload send.
                group_size = 2
            payload = result_bytes
            if base_op == "reduce-scatter":
                # Output is the shard: the logical payload is the full
                # operand. Operand types sit inside the call parens.
                tail = line[m.end():]
                depth = 1
                for i, ch in enumerate(tail):
                    if ch == "(":
                        depth += 1
                    elif ch == ")":
                        depth -= 1
                        if depth == 0:
                            payload = _type_bytes(tail[:i]) or result_bytes
                            break
            bucket = classify_groups(groups, data_axis, model_axis)

            def emit(op, payload):
                out.append(Collective(
                    op=op, raw_op=raw_op, name=name,
                    dtype=_type_dtype(type_text), payload_bytes=payload,
                    group_size=group_size, n_groups=len(groups),
                    bucket=bucket,
                    wire_bytes=_ring_wire_bytes(op, payload, group_size)))

            if base_op != "all-reduce" or group_size <= 1:
                emit(base_op, payload)
                continue
            # Re-derive the decomposed reduce-scatter: a reduced array
            # whose every consumer keeps <= ceil(bytes/G) (+ one element
            # of layout slack) of it. XLA's all-reduce combiner may have
            # merged many reductions into ONE tuple-typed all-reduce, so
            # the test runs per tuple element (through its
            # get-tuple-element) and the op splits into its scattered
            # and its plain part.
            if type_text.startswith("("):
                by_index = {}  # tuple index -> users of its get-tuple-element
                for gn, _, gl in users.get(name, ()):
                    gte = _GTE_INDEX_RE.search(gl)
                    if gte:
                        by_index.setdefault(int(gte.group(1)), []).extend(
                            users.get(gn, ()))
                elements = [(_type_bytes(t.group(0)), by_index.get(i, ()))
                            for i, t in
                            enumerate(_SHAPE_RE.finditer(type_text))]
            else:
                elements = [(payload, users.get(name, ()))]
            slack = _DTYPE_BYTES.get(_type_dtype(type_text), 4)
            scattered = sum(
                nbytes for nbytes, keepers in elements
                if keepers and all(
                    cb <= (nbytes + group_size - 1) // group_size + slack
                    for _, cb, _ in keepers))
            if scattered:
                emit("reduce-scatter", scattered)
            if payload - scattered or not scattered:
                emit("all-reduce", payload - scattered)
    return out


def summarize_collectives(hlo_text: str, data_axis: int,
                          model_axis: int) -> dict:
    """The per-program comms budget the golden engine pins and the
    ledger persists: op multiset (effective ops), canonical structure
    signature counts, per-axis bytes-on-wire, and the ZeRO exchange
    components — ``all_gather_bytes`` / ``reduce_scatter_bytes`` /
    ``plain_all_reduce_bytes`` are FULL float payload bytes (not wire
    bytes), because the zero1 twin gate compares them against the
    analytic parameter footprint."""
    cols = extract_collectives(hlo_text, data_axis, model_axis)
    ops: Dict[str, int] = {}
    structure: Dict[str, int] = {}
    bytes_by_axis: Dict[str, int] = {}
    ag = rs = ar = 0
    wire = 0.0
    for c in cols:
        ops[c.op] = ops.get(c.op, 0) + 1
        structure[c.signature()] = structure.get(c.signature(), 0) + 1
        bytes_by_axis[c.bucket] = int(bytes_by_axis.get(c.bucket, 0)
                                      + c.wire_bytes)
        wire += c.wire_bytes
        if c.dtype in FLOAT_DTYPES:
            if c.op == "all-gather":
                ag += c.payload_bytes
            elif c.op == "reduce-scatter":
                rs += c.payload_bytes
            elif c.op == "all-reduce":
                ar += c.payload_bytes
    return {
        "mesh": f"{data_axis}x{model_axis}",
        "collective_count": len(cols),
        "ops": dict(sorted(ops.items())),
        "structure": dict(sorted(structure.items())),
        "bytes_by_axis": dict(sorted(bytes_by_axis.items())),
        "wire_bytes_per_device": int(wire),
        "all_gather_bytes": int(ag),
        "reduce_scatter_bytes": int(rs),
        "plain_all_reduce_bytes": int(ar),
    }


def hlo_text_of(compiled) -> Optional[str]:
    """Post-SPMD-partitioner HLO text of a compiled program (the only
    stage where collectives exist for auto-sharded jit programs); None
    when the backend exposes neither accessor."""
    try:
        modules = compiled.hlo_modules()
        if modules:
            return "\n".join(m.to_string() for m in modules)
    except Exception as e:  # noqa: BLE001 - accounting must never crash
        log.debug("hlo_modules unavailable: %s", e)
    try:
        return compiled.as_text()
    except Exception as e:  # noqa: BLE001
        log.debug("compiled.as_text unavailable: %s", e)
        return None


def comms_from_compiled(compiled, data_axis: int,
                        model_axis: int) -> Optional[dict]:
    """``summarize_collectives`` over a compiled program's HLO text;
    None when the backend reports no HLO."""
    text = hlo_text_of(compiled)
    if text is None:
        return None
    return summarize_collectives(text, data_axis, model_axis)


class CommsLedger:
    """Per-compiled-program comms entries, persisted per run.

    One entry per program key (the FlopsRegistry/MemoryLedger key
    spelling, so ``comms.json`` certifies the same programs as
    ``flops.json`` and ``memory.json``): the collective summary plus
    provenance and the predicted time-on-wire. ``<train_dir>/comms.json``
    is what perfwatch's sweep-comm series, the doctor and operators
    read back."""

    def __init__(self):
        self._entries: Dict[str, dict] = {}

    def register(self, key: str, summary: Optional[dict],
                 **extra) -> dict:
        entry = dict(summary) if summary else {"comms_source": "none"}
        if summary:
            entry["comms_source"] = "compiled_hlo"
        entry.update(extra)
        self._entries[key] = entry
        return entry

    def get(self, key: str) -> Optional[dict]:
        return self._entries.get(key)

    def keys(self) -> List[str]:
        return sorted(self._entries)

    def to_dict(self) -> dict:
        return {"format": 1, "entries": dict(self._entries)}

    def save(self, train_dir: str) -> Optional[str]:
        """Atomic ``<train_dir>/comms.json`` (tmp + rename, like every
        other run artifact)."""
        try:
            os.makedirs(train_dir, exist_ok=True)
            path = os.path.join(train_dir, LEDGER_FILE)
            tmp = path + f".tmp{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(self.to_dict(), f, indent=1)
            os.replace(tmp, path)
            return path
        except OSError as e:
            log.warning("could not write %s: %s", LEDGER_FILE, e)
            return None

    @classmethod
    def load(cls, train_dir: str) -> "CommsLedger":
        ledger = cls()
        try:
            with open(os.path.join(train_dir, LEDGER_FILE)) as f:
                payload = json.load(f)
            ledger._entries.update(payload.get("entries", {}))
        except (OSError, ValueError):
            pass
        return ledger


def predicted_time_on_wire(summary: Optional[dict],
                           device_kind: str) -> Optional[float]:
    """Predicted seconds-on-wire per step: per-device ring-model bytes
    over the chip's ICI bandwidth (:data:`ICI_BYTES_BY_KIND`,
    ``TPU_RESNET_ICI_BYTES`` override). None when either side is
    unknown — an unknown chip reports no number rather than a wrong
    one."""
    bw = ici_bytes_per_chip(device_kind)
    if not bw or not summary:
        return None
    return summary.get("wire_bytes_per_device", 0) / bw


def account_train_step(cfg, mesh, state, base_step,
                       per_replica_bn: bool = False,
                       stage_rows: int = 1, chunk_steps: int = 1,
                       variant: str = "single-step",
                       partitioner=None,
                       flops_per_step: Optional[float] = None,
                       ledger: Optional[CommsLedger] = None,
                       train_dir: Optional[str] = None) -> dict:
    """Measure and register the train step's comms budget for ``cfg``
    on ``mesh``. Called ONCE per run at first dispatch, inside the
    compile window: like the memory ledger this needs a COMPILED
    program (collectives only exist post-SPMD-partitioning) and the AOT
    path shares no cache with the jit dispatch — one extra XLA compile,
    gated by ``train.comms_ledger``, never a per-step cost.

    The probe compiles the program the run's input edge actually
    dispatches (``obs.memory.lower_train_step`` — the shared builder
    the memory accountant uses, donation and partitioner identical), so
    a ``comms.json`` entry can never describe a different program than
    the run executes. ``flops_per_step`` (the MFU accountant's number,
    when it ran) feeds ``predicted_comms_fraction`` = time-on-wire /
    (time-on-wire + peak-compute time) — the gauge that says whether
    the next scaling step is compute- or comms-bound before a pod is
    ever booked."""
    from tpu_resnet.obs.memory import lower_train_step
    from tpu_resnet.obs.mfu import peak_flops_per_chip, train_program_key

    ledger = ledger if ledger is not None else CommsLedger()
    key = train_program_key(cfg, dict(mesh.shape))
    lowered, variant = lower_train_step(
        cfg, mesh, state, base_step, per_replica_bn=per_replica_bn,
        stage_rows=stage_rows, chunk_steps=chunk_steps, variant=variant,
        partitioner=partitioner)
    shape = dict(mesh.shape)
    summary = comms_from_compiled(lowered.compile(),
                                  shape.get("data", 1),
                                  shape.get("model", 1))
    kind = mesh.devices.flat[0].device_kind
    extra = {"program_key": key, "program": variant,
             "device_kind": kind, "n_devices": int(mesh.size),
             "ici_bytes_per_chip": ici_bytes_per_chip(kind)}
    if partitioner is not None:
        extra["partition"] = partitioner.describe()
    t_wire = predicted_time_on_wire(summary, kind)
    if t_wire is not None:
        extra["predicted_time_on_wire_s"] = t_wire
        peak = peak_flops_per_chip(kind)
        if flops_per_step and peak:
            t_compute = flops_per_step / (peak * max(int(mesh.size), 1))
            extra["predicted_comms_fraction"] = round(
                t_wire / (t_wire + t_compute), 4) if (t_wire + t_compute) \
                else 0.0
    entry = ledger.register(key, summary, **extra)
    if train_dir:
        ledger.save(train_dir)
    return entry
