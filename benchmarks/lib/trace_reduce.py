"""From a ``jax.profiler`` capture to device busy time, the operations that
took it and the gaps between them.

The capture is an ``.xplane.pb``, read through ``jax.profiler.ProfileData``:
planes (one per device, one for the host), each with lines, each with
events. A device plane's lines overlap one another (steps contain modules
contain operations), so busy time is the UNION of the intervals on the
plane's operations line alone, never a sum over its lines; it is taken per
device and averaged over the devices. A capture with no device plane, or a
device whose operations line is empty, is an error and never ``busy_s = 0``.

Little is kept in memory: a capture of some seconds holds millions of host
events, and a TPU names an operation by its whole HLO instruction, kilobytes
a name (a first version that kept every event as a tuple met the machine's
40 GiB; my chip run, PR 25).
"""

from __future__ import annotations

import glob
import os
import re
import sys
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[int, int]  # start_ns, end_ns
Op = Tuple[str, int, int]   # family, start_ns, end_ns

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
_SUFFIX = re.compile(r"[.\-_]?\d+$")


class TraceError(RuntimeError):
    """The capture does not hold what the reduction needs."""


# ----------------------------------------------------------------- loading
def find_capture(directory: str) -> str:
    """The newest ``.xplane.pb`` under ``directory``."""
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise TraceError(f"no .xplane.pb under {directory}")
    return max(paths, key=os.path.getmtime)


def load(path: str):
    """The capture as ``jax.profiler.ProfileData``."""
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def op_family(name: str) -> str:
    """``fusion.123`` and ``fusion.7`` are one row of the breakdown. A TPU
    capture names an operation by its whole HLO instruction
    (``%fusion.7 = f32[...] fusion(...)``): the name is what stands before
    the ``=``."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return (_SUFFIX.sub("", head) or head)[:64]


def device_ops(profile, cpu_stand_in: bool = False) -> List[Dict]:
    """Each device plane's operations line as ``{"name", "ops": [(family,
    start_ns, end_ns), ...]}``, in device order; no other line is kept.

    ``cpu_stand_in`` is for rehearsals off the chip only: the CPU backend
    has no device plane, so its XLA worker threads' events stand in as the
    operations line of one, and the same reduction runs over them."""
    families: Dict[str, str] = {}

    def events_of(line) -> List[Op]:
        out = []
        for ev in line.events:
            raw = ev.name
            if raw not in families:
                families[raw] = sys.intern(op_family(raw))
            start = int(ev.start_ns)
            out.append((families[raw], start, start + int(ev.duration_ns)))
        return out

    found, seen = [], []
    for plane in profile.planes:
        seen.append(plane.name)
        if DEVICE_PLANE.match(plane.name):
            ops: List[Op] = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = events_of(line)
            if not ops:
                raise TraceError(
                    f"{plane.name} ran no operation in the capture (lines: "
                    f"{[line.name for line in plane.lines]})")
            found.append({"name": plane.name, "ops": ops})
        elif cpu_stand_in and plane.name == "/host:CPU":
            ops = [op for line in plane.lines
                   if line.name.startswith("tf_XLA")
                   for op in events_of(line)]
            if ops:
                found.append({"name": "/device:TPU:0", "ops": ops})
    if not found:
        raise TraceError("the capture holds no device plane: "
                         + ", ".join(seen))
    return sorted(found, key=lambda d: int(d["name"].rsplit(":", 1)[1]))


# --------------------------------------------------------------- intervals
def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same set."""
    out: List[Interval] = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def union_ns(intervals: Sequence[Interval]) -> int:
    return sum(hi - lo for lo, hi in merge(intervals))


# --------------------------------------------------------------- reduction
def clip(ops: Sequence[Op], lo: int, hi: int) -> List[Op]:
    """The operations as far as they lie inside ``[lo, hi]``."""
    return [(f, max(s, lo), min(e, hi)) for f, s, e in ops
            if e > lo and s < hi]


def reduce(profile, window_ns: Interval, cpu_stand_in: bool = False,
           host_spans: Sequence[Op] = ()) -> Dict:
    """``busy_s`` (union per device, averaged over devices) and
    ``window_s``, both of the same window: ``window_ns`` on the capture's
    clock (nanoseconds since ``start_trace`` was entered), to which every
    operation is clipped. Beside them the per-device busy seconds, the summed time of
    each operation family on the first device (self time, see
    ``self_time_by_family``), and its longest idle gaps named by the host
    event that overlapped each most. ``host_spans`` are the caller's own
    ``(name, start_ns, end_ns)`` on the same clock, read beside the
    capture's host events. A device that ran nothing inside the window is
    an error."""
    devs = device_ops(profile, cpu_stand_in)
    first_op = min(s for d in devs for _, s, _ in d["ops"])
    last_op = max(e for d in devs for _, _, e in d["ops"])
    lo, hi = window_ns
    if hi <= lo:
        raise TraceError(f"the window {lo}..{hi} ns is empty")
    per_device = []
    for d in devs:
        whole = union_ns([(s, e) for _, s, e in d["ops"]])
        d["ops"] = clip(d["ops"], lo, hi)
        inside = union_ns([(s, e) for _, s, e in d["ops"]])
        if inside <= 0:
            raise TraceError(f"{d['name']} ran no operation inside the "
                             f"window {lo}..{hi} ns (its operations span "
                             f"{first_op}..{last_op} ns)")
        d["outside_s"] = (whole - inside) / 1e9
        per_device.append(inside / 1e9)
    window_s = (hi - lo) / 1e9
    busy_s = sum(per_device) / len(per_device)
    first = devs[0]["ops"]
    by_family = self_time_by_family(first)
    top = sorted(by_family.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "per_device_busy_s": per_device,
        "outside_window_s": [d["outside_s"] for d in devs],
        "device_ops": [[k, v / 1e9] for k, v in top],
        "first_op_s": first_op / 1e9,
        "last_op_s": last_op / 1e9,
        "idle_gaps": idle_gaps(profile, first, host_spans=host_spans),
    }


def self_time_by_family(ops: List[Op]) -> Dict[str, int]:
    """Nanoseconds by family, each operation counted for the time that no
    operation nested inside it covers (a ``while`` spans its whole body on
    the same line): the families then add up to the busy time and no
    container heads the list."""
    out: Dict[str, int] = {}
    open_ops: List[list] = []  # [family, end_ns, self_ns]

    def close(until: int) -> None:
        while open_ops and open_ops[-1][1] <= until:
            fam, _, self_ns = open_ops.pop()
            out[fam] = out.get(fam, 0) + max(self_ns, 0)

    for fam, start, end in sorted(ops, key=lambda o: (o[1], -o[2])):
        close(start)
        if open_ops:  # nested: the parent loses what this one covers
            open_ops[-1][2] -= min(end, open_ops[-1][1]) - start
        open_ops.append([fam, end, end - start])
    close(max((o[2] for o in ops), default=0))
    return out


def _host_events(profile, host_spans: Sequence[Op]):
    yield from host_spans
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if line.name.startswith("tf_XLA"):
                continue  # the CPU backend's own operation threads
            for ev in line.events:
                s = int(ev.start_ns)
                yield ev.name, s, s + int(ev.duration_ns)


def idle_gaps(profile, ops: List[Op], top: int = 10,
              host_spans: Sequence[Op] = ()) -> List[List]:
    """The longest gaps between operations on one device, each named by
    the shortest host event or caller's span that covers at least half of
    it (``idle`` where none does). The host planes are read in one pass,
    and nothing of them is kept but the best name for each gap."""
    busy = merge([(s, e) for _, s, e in ops])
    gaps = sorted(((b[0] - a[1], a[1], b[0])
                   for a, b in zip(busy, busy[1:])), reverse=True)[:top]
    if not gaps:
        return []
    best = [(None, "idle")] * len(gaps)   # (event length, name)
    first_lo = min(lo for _, lo, _ in gaps)
    last_hi = max(hi for _, _, hi in gaps)
    for name, s, e in _host_events(profile, host_spans):
        if e <= first_lo or s >= last_hi:
            continue
        for i, (length, lo, hi) in enumerate(gaps):
            if min(e, hi) - max(s, lo) >= 0.5 * length and (
                    best[i][0] is None or e - s < best[i][0]):
                best[i] = (e - s, name[:64])
    return [[name, length / 1e9]
            for (_, name), (length, _, _) in zip(best, gaps)]
