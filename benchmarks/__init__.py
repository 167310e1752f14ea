"""The benchmark: the harness `BENCHMARK.json` names, its data and its
yardstick. `run.py` is the entry point; PERF.md says what is measured
and why."""
