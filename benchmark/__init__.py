"""paddle_tpu's benchmark: see BENCHMARK.json, PERF.md and harness.py."""
