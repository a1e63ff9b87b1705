"""Benchmark of the checkpointing system on the chip (see run.py)."""
