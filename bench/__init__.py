"""Serving benchmark: harness, traffic generator, trace reduction and references (see run.py)."""
