"""The benchmark's command: one run of one cell, one result line.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Fails (non-zero, no result line) where JAX finds no TPU or fewer chips
than the cell asks for, or where the checkout lacks the program.
``--control`` and ``--fault`` are for reading the comparison's limits
(PERF.md); a driver's run passes neither.
"""

import time

_STARTED = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default="",
                    help="the reference in a lower precision, read in "
                         "the program's place as well: names of the "
                         "family's STAND_INS, with commas")
    ap.add_argument("--fault", default="")
    args = ap.parse_args(argv)

    from benchmarks.lib import faults, harness
    from benchmarks.lib.lastline import LastLineError
    from benchmarks.lib.manifest import ManifestError

    try:
        return harness.run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace),
            started=_STARTED, control=args.control,
            fault=faults.FAULTS[args.fault] if args.fault else None)
    except (harness.BenchmarkError, ManifestError, LastLineError,
            ImportError) as e:
        print(f"benchmark: {type(e).__name__}: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
