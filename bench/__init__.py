"""Chip benchmark of the async trainer and the slot server (see PERF.md)."""
