"""The tree fit's share of its roofline: ``lib/roofline.py`` over the work
count the cell's configuration names (``work/tree_fit.py``: binning and
histogram builds)."""
from benchmarks.lib.roofline import read  # noqa: F401
