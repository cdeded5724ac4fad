"""The benchmark of tpu-resnet: BENCHMARK.json names ``benchmarks/run.py``
as its command; PERF.md says what it measures and why."""
