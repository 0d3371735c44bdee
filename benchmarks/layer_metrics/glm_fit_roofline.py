"""The multinomial GLM fit's share of its roofline (``lib/roofline.py``)."""
from benchmarks.lib.roofline import read  # noqa: F401
