"""The benchmark: one command per cell, driven by data (see run.py)."""
