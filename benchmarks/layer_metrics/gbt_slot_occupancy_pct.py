"""Share of the boosted fits' histogram node slots that held a live node:
``hist_slot_occupancy_pct``'s reader under the GBT cell's name (what the
256-slot chunks of its depth-12 levels waste)."""
from benchmarks.layer_metrics.hist_slot_occupancy_pct import read  # noqa: F401
