"""The comparison that decides ``correct``: what the timed path's first
dispatch produced against the plain reference that followed the same steps
from the same start on the same rows.

Each number has a limit of its own (``limits/<cell>.json``); ``correct``
is true when none is over. The numbers:

``loss_rel``     the chunk's reported loss (its last step's) against the
                 reference's loss at that step, relative.
``gnorm_rel``    the reported global gradient norm of that step, likewise.
``mom_gap``      the momentum buffers after the chunk, worst leaf: the gap
                 between the program's norm and the reference's, over the
                 reference's norm of that leaf or of the median leaf,
                 whichever is larger. The buffer is the gradients as the
                 optimizer got them, summed with decay.
``dparam_gap``   the parameters' change over the chunk, worst leaf, same
                 measure. Leaves whose reference gradient (its momentum
                 buffer) is under a thousandth of the median leaf's are
                 left out: round-off alone moves them.
``bn_gap``       the BN running statistics' change over the chunk, worst
                 leaf, same measure.
``step_count``   the program's step counter after the chunk against the
                 rows it was fed; exact.

``head_gap``, ``head_cos``  the momentum buffer of the leaf next to the
                 loss (the dense layer's kernel): the gap of its norm, and
                 one minus its cosine with the reference's.
``head_bias_cos``  one minus the cosine of the dense layer's bias buffer:
                 the gradient at the logits, softmax minus labels, meaned
                 over each batch and summed over the chunk's steps. The
                 reference's reading of it moves with its forward pass
                 alone (no normalization layer's backward pass stands
                 behind it), which is why the fp8 control moves it far;
                 the program's own reading is set by how it sums that
                 gradient from bf16 cotangents (PERF.md section 6).
``bn_mean_cos``  one minus the cosine of the change of the BN running
                 *means* alone (``bn_cos`` without the variances): rounded
                 weights shift a channel's mean at first order, which no
                 batch averages away, and its variance at second.

Beside each worst-leaf ``_gap`` stand the same group's ``_med`` (the median
leaf's gap), ``_all`` (the gap of the norms over all leaves together) and
``_cos`` (one minus the cosine between the program's and the reference's
whole vector): steadier from seed to seed than a worst leaf, and ``_cos``
is what sees rows left out, which change a direction and hardly a norm.
Which of them have limits is the limits file's to say (PERF.md gives the
readings); the others are printed and not compared.
"""

from __future__ import annotations

import math
import statistics
from typing import Dict, Tuple

import numpy as np


def _norm(x) -> float:
    return float(np.sqrt(np.sum(np.square(np.asarray(x, np.float64)))))


def _leaf_gaps(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray]
               ) -> Dict[str, float]:
    """Per leaf: | ||prog|| - ||ref|| | / max(||ref||, the median leaf's
    ||ref||)."""
    if set(prog) != set(ref):
        raise KeyError(f"program and reference disagree on leaves: "
                       f"{sorted(set(prog) ^ set(ref))[:5]}")
    ref_norms = {k: _norm(v) for k, v in ref.items()}
    med = statistics.median(ref_norms.values())
    gaps = {k: abs(_norm(prog[k]) - ref_norms[k])
            / max(ref_norms[k], med, 1e-30) for k in ref}
    return {k: g if math.isfinite(g) else math.inf for k, g in gaps.items()}


def _delta(after: Dict, before: Dict) -> Dict[str, np.ndarray]:
    return {k: np.asarray(after[k], np.float64)
            - np.asarray(before[k], np.float64) for k in after}


def _rel(a: float, b: float) -> float:
    if not (math.isfinite(a) and math.isfinite(b)):
        return math.inf
    return abs(a - b) / max(abs(b), 1e-30)


def _whole(prog: Dict, ref: Dict) -> Tuple[float, float]:
    """Over all leaves together: the gap of the two norms over the
    reference's, and one minus the cosine between the two."""
    all_p = math.sqrt(sum(_norm(v) ** 2 for v in prog.values()))
    all_r = math.sqrt(sum(_norm(v) ** 2 for v in ref.values()))
    dot = sum(float(np.sum(np.asarray(prog[k], np.float64)
                           * np.asarray(ref[k], np.float64))) for k in ref)
    return (abs(all_p - all_r) / max(all_r, 1e-30),
            1.0 - dot / max(all_p * all_r, 1e-30))


def _groups(program: Dict, reference: Dict) -> Dict[str, Tuple[Dict, Dict]]:
    """What is compared leaf by leaf, the program's beside the
    reference's: momentum buffers, the parameters' change, the BN running
    statistics' change."""
    return {
        "mom": (program["mom"], reference["mom"]),
        "dparam": (_delta(program["params"], program["params0"]),
                   _delta(reference["params"], program["params0"])),
        "bn": (_delta(program["stats"], program["stats0"]),
               _delta(reference["stats"], program["stats0"])),
    }


def readings(program: Dict, reference: Dict,
             head: str = "final_dense/kernel",
             head_bias: str = "final_dense/bias") -> Dict[str, float]:
    """The numbers compared. ``program`` and ``reference`` hold ``params,
    stats, mom`` after the chunk, ``loss`` and ``gnorm`` of its last step;
    the program's also ``params0, stats0`` (the shared start), ``step0``
    and ``step`` (its counter before and after) and ``rows`` (steps fed).
    For each of ``mom``, ``dparam`` and ``bn``: ``_gap`` the worst leaf,
    ``_med`` the median leaf, ``_all`` the norms over all leaves, ``_cos``
    one minus the cosine over all leaves."""
    ref_mom_norms = {k: _norm(v) for k, v in reference["mom"].items()}
    med = statistics.median(ref_mom_norms.values())
    still = {k for k, n in ref_mom_norms.items() if n < 1e-3 * med}
    out = {
        "loss_rel": _rel(program["loss"], reference["loss"]),
        "gnorm_rel": _rel(program["gnorm"], reference["gnorm"]),
        "step_count": float(abs(program["step"] - program.get("step0", 0)
                                - program["rows"])),
    }
    # The leaf next to the loss: its gradient passes through no
    # normalization layer on the way back, so it is the one gradient that
    # rounding in the activations does not scramble (PERF.md section 6).
    out["head_gap"], out["head_cos"] = _whole(
        {head: program["mom"][head]}, {head: reference["mom"][head]})
    out["head_bias_cos"] = _whole({head_bias: program["mom"][head_bias]},
                                  {head_bias: reference["mom"][head_bias]})[1]
    for name, (prog, ref) in _groups(program, reference).items():
        gaps = _leaf_gaps(prog, ref)
        skip = still if name == "dparam" else ()
        out[f"{name}_gap"] = max(g for k, g in gaps.items() if k not in skip)
        out[f"{name}_med"] = statistics.median(gaps.values())
        out[f"{name}_all"], out[f"{name}_cos"] = _whole(prog, ref)
    prog, ref = _groups(program, reference)["bn"]
    means = [k for k in ref if k.endswith("/mean")]
    out["bn_mean_cos"] = _whole({k: prog[k] for k in means},
                                {k: ref[k] for k in means})[1]
    return out


def worst_leaves(program: Dict, reference: Dict, top: int = 3) -> Dict:
    """The leaves behind each ``_gap``, by name: for the look into a
    number that reads far off."""
    out = {}
    for name, (prog, ref) in _groups(program, reference).items():
        gaps = _leaf_gaps(prog, ref)
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
