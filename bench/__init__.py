"""Chip benchmark of the compressed-consensus train step (see PERF.md)."""
