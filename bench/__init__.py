"""Chip benchmark of the structured-dropout training path (see README.md)."""
