"""The comparison that decides ``correct``: what the timed path's first
dispatch produced against the plain reference that followed the same steps
from the same start on the same rows.

Which numbers are read is the model family's to say
(``families/<family>.py::readings``); each has a limit of its own
(``limits/<cell>.json``), and ``correct`` is true when none is over
(``judge``). Here is the arithmetic the families share: a group of leaves
(momentum buffers, the parameters' change, ...) is read as ``_gap``, the
worst leaf's gap between the program's norm and the reference's, over the
reference's norm of that leaf or of the median leaf, whichever is larger;
``_med``, the median leaf's gap; ``_all``, the gap of the norms over all
leaves together; and ``_cos``, one minus the cosine between the program's
and the reference's whole vector. The last three are steadier from seed to
seed than a worst leaf, and ``_cos`` is what sees rows left out, which
change a direction and hardly a norm. Which of them have limits is the
limits file's to say (PERF.md gives the readings); the others are printed
and not compared.
"""

from __future__ import annotations

import math
import statistics
from typing import Dict, Tuple

import numpy as np


def norm(x) -> float:
    return float(np.sqrt(np.sum(np.square(np.asarray(x, np.float64)))))


def leaf_gaps(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray]
              ) -> Dict[str, float]:
    """Per leaf: | ||prog|| - ||ref|| | / max(||ref||, the median leaf's
    ||ref||)."""
    if set(prog) != set(ref):
        raise KeyError(f"program and reference disagree on leaves: "
                       f"{sorted(set(prog) ^ set(ref))[:5]}")
    ref_norms = {k: norm(v) for k, v in ref.items()}
    med = statistics.median(ref_norms.values())
    gaps = {k: abs(norm(prog[k]) - ref_norms[k])
            / max(ref_norms[k], med, 1e-30) for k in ref}
    return {k: g if math.isfinite(g) else math.inf for k, g in gaps.items()}


def delta(after: Dict, before: Dict) -> Dict[str, np.ndarray]:
    return {k: np.asarray(after[k], np.float64)
            - np.asarray(before[k], np.float64) for k in after}


def rel(a: float, b: float) -> float:
    if not (math.isfinite(a) and math.isfinite(b)):
        return math.inf
    return abs(a - b) / max(abs(b), 1e-30)


def whole(prog: Dict, ref: Dict) -> Tuple[float, float]:
    """Over all leaves together: the gap of the two norms over the
    reference's, and one minus the cosine between the two."""
    all_p = math.sqrt(sum(norm(v) ** 2 for v in prog.values()))
    all_r = math.sqrt(sum(norm(v) ** 2 for v in ref.values()))
    dot = sum(float(np.sum(np.asarray(prog[k], np.float64)
                           * np.asarray(ref[k], np.float64))) for k in ref)
    return (abs(all_p - all_r) / max(all_r, 1e-30),
            1.0 - dot / max(all_p * all_r, 1e-30))


def still_leaves(ref_gradient: Dict[str, np.ndarray]) -> set:
    """Leaves whose reference gradient (its momentum buffer) is under a
    thousandth of the median leaf's: round-off alone moves them, and a
    family leaves them out of the parameters' change."""
    norms = {k: norm(v) for k, v in ref_gradient.items()}
    med = statistics.median(norms.values())
    return {k for k, n in norms.items() if n < 1e-3 * med}


def group_readings(name: str, prog: Dict, ref: Dict, skip=()
                   ) -> Dict[str, float]:
    """One group of leaves as ``<name>_gap``, ``_med``, ``_all`` and
    ``_cos``; the leaves in ``skip`` are left out of the worst leaf."""
    gaps = leaf_gaps(prog, ref)
    out = {f"{name}_gap": max(g for k, g in gaps.items() if k not in skip),
           f"{name}_med": statistics.median(gaps.values())}
    out[f"{name}_all"], out[f"{name}_cos"] = whole(prog, ref)
    return out


def worst_leaves(groups: Dict[str, Tuple[Dict, Dict]], top: int = 3
                 ) -> Dict:
    """The leaves behind each ``_gap`` of a family's ``groups(program,
    reference)``, by name: for the look into a number that reads far
    off."""
    out = {}
    for name, (prog, ref) in groups.items():
        gaps = leaf_gaps(prog, ref)
        out[name] = [(k, round(gaps[k], 5)) for k in
                     sorted(gaps, key=gaps.get, reverse=True)[:top]]
    return out


def judge(values: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, list]]:
    """``correct`` and ``{name: [value, limit]}``. A number without a
    limit is shown and not compared (``limit`` null); a limit without a
    number is a failure."""
    compared, ok = {}, True
    for name in sorted(set(values) | set(limits)):
        v, lim = values.get(name), limits.get(name)
        if v is not None and not math.isfinite(v):
            v = 1e30  # JSON has no infinity
        compared[name] = [v, lim]
        if lim is not None and (v is None or v > lim):
            ok = False
    return ok, compared
