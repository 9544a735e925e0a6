"""Serving benchmark of the SALS system: harness, traffic, references and
the reduction from traces to metrics (see ``bench/run.py``)."""
