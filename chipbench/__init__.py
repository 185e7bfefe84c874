"""Chip benchmark of the HARP programming and analog serving paths."""
