"""Share of the K-class forest fits' histogram node slots that held a live
node: ``hist_slot_occupancy_pct``'s reader under the multiclass cell's name
(what its 128-slot chunks and 32-slot rungs waste)."""
from benchmarks.layer_metrics.hist_slot_occupancy_pct import read  # noqa: F401
