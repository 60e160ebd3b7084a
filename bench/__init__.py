"""Chip benchmark of the BPipe training path (see ``bench/run.py``)."""
