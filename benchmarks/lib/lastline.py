"""The one result line a run prints, built and checked in one place.

``build`` assembles the contract's object and ``validate`` refuses it
before it is printed where it does not have the contract's shape: the keys
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device``; under
``metrics`` every metric ``BENCHMARK.json`` lists for this cell in this
trace mode, each with a finite value and its unit; under ``device`` the
platform, kind, count and peak memory and, traced, ``busy_s`` and
``window_s`` with ``0 < busy_s <= window_s``. ``compared`` (each number
the comparison read, beside its limit) comes last.
"""

from __future__ import annotations

import json
import math
from typing import Dict, List, Optional


class LastLineError(ValueError):
    """The result does not have the contract's shape; nothing is printed."""


def _finite(x) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool) \
        and math.isfinite(x)


def validate(line: Dict, metrics: List[Dict], trace: bool) -> None:
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        if key not in line:
            raise LastLineError(f"result lacks {key!r}")
    if not isinstance(line["correct"], bool):
        raise LastLineError("correct is not a boolean")
    for key in ("attempted", "failed"):
        if not isinstance(line[key], int) or line[key] < 0:
            raise LastLineError(f"{key} is not a count")
    if line["failed"] > line["attempted"]:
        raise LastLineError("failed exceeds attempted")
    want = {m["name"]: m["unit"] for m in metrics}
    got = line["metrics"]
    for name, unit in want.items():
        if name not in got:
            raise LastLineError(f"metric {name!r} is missing")
        entry = got[name]
        if not _finite(entry.get("value")):
            raise LastLineError(f"metric {name!r} has no finite value: "
                                f"{entry.get('value')!r}")
        if entry.get("unit") != unit:
            raise LastLineError(f"metric {name!r} has unit "
                                f"{entry.get('unit')!r}, not {unit!r}")
        if (name.endswith("_roofline") or "mfu" in name) \
                and entry["value"] > 105:
            raise LastLineError(f"{name} reads {entry['value']}% of a peak")
    for name in got:
        if name not in want:
            raise LastLineError(f"metric {name!r} is not one of this "
                                f"cell's in this trace mode")
    dev = line["device"]
    for key in ("platform", "kind"):
        if not isinstance(dev.get(key), str) or not dev[key]:
            raise LastLineError(f"device.{key} is missing")
    if not isinstance(dev.get("count"), int) or dev["count"] < 1:
        raise LastLineError("device.count is missing")
    if not isinstance(dev.get("memory_peak_bytes"), int) \
            or dev["memory_peak_bytes"] <= 0:
        raise LastLineError("device.memory_peak_bytes is missing")
    if trace:
        busy, window = dev.get("busy_s"), dev.get("window_s")
        if not _finite(busy) or not _finite(window):
            raise LastLineError("traced run lacks device.busy_s/window_s")
        if not 0 < busy <= window:
            raise LastLineError(f"need 0 < busy_s <= window_s, got "
                                f"busy_s={busy} window_s={window}")
        bd = line.get("breakdown")
        if bd is not None:
            for key in ("device_ops", "idle_gaps"):
                rows = bd.get(key)
                if not isinstance(rows, list) or len(rows) > 10 or any(
                        len(r) != 2 or not isinstance(r[0], str)
                        or not _finite(r[1]) for r in rows):
                    raise LastLineError(f"breakdown.{key} is malformed")


def build(*, correct: bool, attempted: int, failed: int,
          values: Dict[str, float], metrics: List[Dict], device: Dict,
          trace: bool, breakdown: Optional[Dict] = None,
          compared: Optional[Dict] = None) -> str:
    """The validated line as JSON text. ``values`` maps metric names to
    numbers; a metric whose reader found nothing is simply absent and
    fails validation where the cell lists it."""
    line = {
        "correct": bool(correct), "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {m["name"]: {"value": values[m["name"]],
                                "unit": m["unit"]}
                    for m in metrics if values.get(m["name"]) is not None},
        "device": device,
    }
    if trace and breakdown is not None:
        line["breakdown"] = breakdown
    validate(line, metrics, trace)
    line["compared"] = compared or {}
    return json.dumps(line)
