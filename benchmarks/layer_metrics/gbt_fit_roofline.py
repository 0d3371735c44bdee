"""The gradient-boosted fit's share of its roofline: ``lib/roofline.py`` over
the work count the cell's configuration names (``work/gbt_work.py``: binning
and the histogram builds of every lane over all columns, a round a tree)."""
from benchmarks.lib.roofline import read  # noqa: F401
