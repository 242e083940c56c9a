"""``kind: backlog``: every request due at t=0 (``harness/serving.py``)."""

from benchmarks.harness.serving import run  # noqa: F401
