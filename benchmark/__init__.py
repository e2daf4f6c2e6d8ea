"""The notary benchmark: one cell of BENCHMARK.json per run (run.py)."""
