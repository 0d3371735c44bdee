"""The forest fit's share of its roofline: ``lib/roofline.py`` over the work
count the cell's configuration names (``work/forest_work.py``: binning and
the histogram builds of every lane over all columns)."""
from benchmarks.lib.roofline import read  # noqa: F401
