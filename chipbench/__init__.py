"""Chip benchmark of the entity-matching system (see ``harness.py``)."""
