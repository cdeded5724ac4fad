"""Compile every Pallas kernel family for the chip, at the shapes the
models really produce, and compare each with its module's own reference.

On the CPU the kernels only ever run under the Pallas interpreter, where
they lower to ordinary XLA ops — so the first Mosaic compile of a kernel
is on a TPU, and a lowering error or a VMEM plan that does not fit shows
up only there. This tool is that first compile, outside any training run:

    python tools/pallas_compile_smoke.py                 # all three families
    python tools/pallas_compile_smoke.py --family xent --family epilogue

Families and shapes (batch 128, the per-chip batch of the rn50 configs):

- ``xent``        ops/softmax_xent.py, forward and custom VJP, at
                  b128x10, b128x100, b128x1000 (float32 logits).
- ``epilogue``    ops/epilogue.py, both ops, forward and VJP, at every
                  BN+ReLU site of CIFAR rn50 and ImageNet rn50 (bf16).
- ``block``       ops/fused_block.py at the CIFAR rn50 stages (16/32/64
                  channels at 32/16/8): folded forward + VJP, live-stats
                  forward + three-pass VJP (bf16).

A check passes when ``max|got - want| / max(1, max|want|) < 2e-2`` — wide
enough for bf16 activations and the MXU's default matmul precision on
both sides, far tighter than any indexing or tiling mistake.

Every check of every case runs even after a failure, so one call reports
the whole picture, direction by direction. Exit code 0 only if every
check compiled, ran and matched; the last stdout line is one JSON object
naming the device and each case's verdict (``--out`` also writes it, with
full compiler messages, to a file). ``--interpret --tiny`` runs toy shapes under the interpreter so
the harness itself is testable on the CPU (tests/test_compile_smoke.py).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

FAMILIES = ("xent", "epilogue", "block")
TOL = 2e-2
BATCH = 128


def _err(got, want):
    """Worst normalized error over two matching pytrees."""
    import jax
    import numpy as np

    worst = 0.0
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        g = np.asarray(g, dtype="float32")
        w = np.asarray(w, dtype="float32")
        if g.shape != w.shape:
            raise AssertionError(f"shape {g.shape} != reference {w.shape}")
        if not np.all(np.isfinite(g)):
            return float("inf")
        worst = max(worst, float(np.max(np.abs(g - w))
                                 / max(1.0, float(np.max(np.abs(w))))))
    return worst


def _sq(fn):
    """Scalar loss over a function's first output (grad target)."""
    import jax.numpy as jnp

    def loss(args):
        out = fn(*args)
        out = out[0] if isinstance(out, tuple) else out
        return jnp.sum(out.astype(jnp.float32) ** 2)

    return loss


# ------------------------------------------------------------------ families
def _cases_xent(interpret, tiny):
    import jax

    from tpu_resnet.ops import softmax_xent as sx

    for b, c in ([(8, 10)] if tiny else
                 [(BATCH, 10), (BATCH, 100), (BATCH, 1000)]):
        logits = 3 * jax.random.normal(jax.random.PRNGKey(c), (b, c))
        labels = jax.random.randint(jax.random.PRNGKey(1), (b,), 0, c)

        def case(logits=logits, labels=labels):
            kern = lambda x: sx.softmax_xent_mean(x, labels,
                                                  interpret=interpret)
            ref = lambda x: sx.softmax_xent_reference(x, labels)
            return {"fwd": lambda: _err(kern(logits), ref(logits)),
                    "bwd": lambda: _err(jax.grad(kern)(logits),
                                        jax.grad(ref)(logits))}

        yield f"b{b}x{c}", case


def _epilogue_shapes(tiny):
    if tiny:
        return [(4, 8, 8, 16)]
    from tpu_resnet.config import load_config
    from tpu_resnet.ops.epilogue import model_epilogue_shapes

    shapes = set()
    for preset in ("cifar10", "imagenet"):
        cfg = load_config(preset)  # both presets are rn50
        shapes.update(model_epilogue_shapes(cfg, BATCH))
    return sorted(shapes)


def _cases_epilogue(interpret, tiny):
    import jax
    import jax.numpy as jnp

    from tpu_resnet.ops import epilogue as ep

    for shape in _epilogue_shapes(tiny):
        def case(shape=shape):
            kx, kr, ks, kb = jax.random.split(jax.random.PRNGKey(0), 4)
            dtype = jnp.float32 if tiny else jnp.bfloat16
            x = jax.random.normal(kx, shape, dtype)
            r = jax.random.normal(kr, shape, dtype)
            s = jax.random.uniform(ks, shape[-1:], jnp.float32, 0.5, 1.5)
            b = jax.random.normal(kb, shape[-1:], jnp.float32)
            sbr = lambda *a: ep.scale_bias_relu(*a, None, interpret)
            add = lambda *a: ep.scale_bias_relu_add(*a, None, interpret)
            out = {}
            for name, kern, ref, args in (
                    ("sbr", sbr, ep.scale_bias_relu_reference, (x, s, b)),
                    ("add", add, ep.scale_bias_relu_add_reference,
                     (x, s, b, r))):
                out[f"{name}_fwd"] = (
                    lambda k=kern, r=ref, a=args: _err(k(*a), r(*a)))
                out[f"{name}_bwd"] = (
                    lambda k=kern, r=ref, a=args: _err(
                        jax.grad(_sq(k))(a), jax.grad(_sq(r))(a)))
            return out

        yield "x".join(map(str, shape)), case


def _cases_block(interpret, tiny):
    import jax
    import jax.numpy as jnp

    from tpu_resnet.ops import fused_block as fb

    for b, h, c in ([(8, 8, 32)] if tiny else
                    [(BATCH, 32, 16), (BATCH, 16, 32), (BATCH, 8, 64)]):
        def case(b=b, h=h, c=c):
            ks = jax.random.split(jax.random.PRNGKey(c), 5)
            dtype = jnp.float32 if tiny else jnp.bfloat16
            x = jax.random.normal(ks[0], (b, h, h, c), dtype)
            w1 = jax.random.normal(ks[1], (3, 3, c, c)) * 0.1
            w2 = jax.random.normal(ks[2], (3, 3, c, c)) * 0.1
            g = jax.random.uniform(ks[3], (c,), jnp.float32, 0.5, 1.5)
            be = jax.random.normal(ks[4], (c,)) * 0.1
            # The tile the model dispatches with (FusedBuildingBlock).
            bt = fb.auto_batch_tile(x.shape, cap=16)
            folded = (x, w1, w2, g, be, g, be)
            apply = lambda *a: fb.block_apply(*a, bt, interpret)
            train = lambda *a: fb.block_train_apply(*a, 1e-5, bt, interpret)
            return {
                "fwd": lambda: _err(apply(*folded),
                                    fb.block_fwd_reference(*folded)),
                "bwd": lambda: _err(
                    jax.grad(_sq(apply))(folded),
                    jax.grad(_sq(fb.block_fwd_reference))(folded)),
                "train_fwd": lambda: _err(
                    train(*folded), fb.block_train_fwd_reference(*folded)),
                "train_bwd": lambda: _err(
                    jax.grad(_sq(train))(folded),
                    jax.grad(_sq(fb.block_train_fwd_reference))(folded)),
            }

        yield f"b{b}_{h}x{h}x{c}", case


_CASES = {"xent": _cases_xent, "epilogue": _cases_epilogue,
          "block": _cases_block}


def run_family(family, interpret, tiny):
    """{case: {"ok", "checks", "errors", "seconds"}} for one family. Each
    case maps check names (one per kernel direction) to thunks; a thunk
    that raises (Mosaic refusal, VMEM overflow, runtime fault) is recorded
    with the compiler's message and every other check still runs."""
    out = {}
    for name, case in _CASES[family](interpret, tiny):
        t0 = time.time()
        checks, errors = {}, {}
        try:
            thunks = case()
        except Exception as e:  # noqa: BLE001 - the failure IS the result
            thunks, errors["setup"] = {}, f"{type(e).__name__}: {e}"
        for check, thunk in thunks.items():
            try:
                checks[check] = round(thunk(), 6)
            except Exception as e:  # noqa: BLE001
                errors[check] = f"{type(e).__name__}: {e}"
        rec = {"ok": not errors and max(checks.values()) < TOL,
               "checks": checks, "errors": errors,
               "seconds": round(time.time() - t0, 1)}
        out[name] = rec
        print(f"[compile_smoke] {family} {name} ({rec['seconds']}s): "
              f"{'OK' if rec['ok'] else 'FAIL'} {json.dumps(checks)}",
              flush=True)
        for check, msg in errors.items():
            print(f"[compile_smoke]   {check}: {msg.strip()[:400]}",
                  flush=True)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--family", action="append", choices=FAMILIES,
                    help="repeatable; default: all three")
    ap.add_argument("--interpret", action="store_true",
                    help="run under the Pallas interpreter (CPU harness "
                         "test); default compiles for the ambient backend")
    ap.add_argument("--tiny", action="store_true",
                    help="toy shapes (with --interpret: seconds on a CPU)")
    ap.add_argument("--out", default="",
                    help="also write the JSON report (full compiler "
                         "messages) to this file")
    ns = ap.parse_args(argv)

    from tpu_resnet.hostenv import enable_compile_cache
    enable_compile_cache()
    import jax

    dev = jax.devices()[0]
    report = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(jax.devices())},
              "interpret": bool(ns.interpret), "tolerance": TOL,
              "families": {}}
    if dev.platform != "tpu" and not ns.interpret:
        print(f"[compile_smoke] no TPU found (platform={dev.platform}); "
              "a Mosaic compile needs the chip — pass --interpret for "
              "the CPU harness test", file=sys.stderr)
        return 1
    for family in ns.family or FAMILIES:
        cases = run_family(family, ns.interpret or None, ns.tiny)
        report["families"][family] = {
            "ok": all(c["ok"] for c in cases.values()), "cases": cases}
    report["ok"] = all(f["ok"] for f in report["families"].values())
    if ns.out:
        os.makedirs(os.path.dirname(os.path.abspath(ns.out)), exist_ok=True)
        with open(ns.out, "w") as fh:
            json.dump(report, fh, indent=1)
    for fam in report["families"].values():  # stdout line stays short
        for case in fam["cases"].values():
            case["errors"] = {k: v.strip()[:300]
                              for k, v in case["errors"].items()}
    print(json.dumps(report))
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
