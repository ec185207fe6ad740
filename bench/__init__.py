"""The chip benchmark: harness, traffic, configurations, references and
per-layer metric readers (see ``bench/run.py``)."""
