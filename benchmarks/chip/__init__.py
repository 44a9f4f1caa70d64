"""Chip benchmark of the online embedding service (see ``harness``)."""
