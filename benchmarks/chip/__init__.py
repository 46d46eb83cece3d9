"""The on-chip benchmark: one command per cell, driven by BENCHMARK.json
(see run.py)."""
