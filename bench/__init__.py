"""The benchmark: cells of BENCHMARK.json run on one accelerator (see run.py)."""
