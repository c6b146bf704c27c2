"""orion-tpu's benchmark: harness, configurations, traffic mixes, per-layer
metric readers, the plain reference and the trace reduction. Everything a
cell needs is a file found by the name ``BENCHMARK.json`` gives it."""
