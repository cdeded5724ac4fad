"""Nothing of the benchmark's reads this file: the FLOPs of one example
are the model family's to count (``families/<family>.py``). It stays, one
import long, because ``tests/test_mfu.py``, outside the benchmark's own
directories and so not this benchmark's to edit, imports
``train_flops_per_image`` from here (PERF.md section 7: for the PR that
may edit that test to delete)."""

from benchmarks.families.resnet_v2 import (  # noqa: F401
    train_flops_per_example as train_flops_per_image)
