"""Chip benchmark of the ImaGen serving path; see ``run.py``."""
