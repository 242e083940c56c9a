"""``kind: paced``: open loop at a fixed rate (``harness/serving.py``)."""

from benchmarks.harness.serving import run  # noqa: F401
