"""Chip benchmark of the FedGS scan engine (see ``run.py``)."""
