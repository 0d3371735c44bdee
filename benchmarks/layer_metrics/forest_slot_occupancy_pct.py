"""Share of the forest fits' histogram node slots that held a live node:
``hist_slot_occupancy_pct``'s reader under the forest cell's name (what the
256-slot chunks of its deep levels waste)."""
from benchmarks.layer_metrics.hist_slot_occupancy_pct import read  # noqa: F401
