"""Chip benchmark of the served OReO decision path (see ``run.py``)."""
