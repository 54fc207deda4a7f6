"""The chip benchmark of the exact ε-graph build (see run.py)."""
