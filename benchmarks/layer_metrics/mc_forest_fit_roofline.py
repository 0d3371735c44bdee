"""The K-class forest fit's share of its roofline: ``lib/roofline.py`` over
the work count the cell's configuration names
(``work/forest_multiclass_work.py``: the histogram builds of every lane
over all columns with K statistics a cell, no binning)."""
from benchmarks.lib.roofline import read  # noqa: F401
