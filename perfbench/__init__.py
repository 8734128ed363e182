"""Chip benchmark of the ZeRO-Infinity trainer (see BENCHMARK.json)."""
