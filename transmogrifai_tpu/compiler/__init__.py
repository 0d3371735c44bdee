"""Compile plane — the shared compilation-and-dispatch subsystem.

Program ACQUISITION (tracing, XLA compilation, executable loading) is what
a fresh process pays before it executes anything; on small data it is most
of the wall-clock. This package is the one place that cost is managed:

* :mod:`.stats` — the ``compileStats`` ledger (programs compiled / cache
  hits / dedup hits / warmup overlap), surfaced in the selector summary,
  ``summary_pretty()``, ``score_fn.metadata()``, and the bench JSON;
* :mod:`.warmup` — async background warmup: ``Workflow.train`` and the
  serving closure start a thread that loads the banked executables the
  traced DAG will actually need, overlapping acquisition with host-side
  ingest/prep instead of serializing it;
* :mod:`.bucketing` — cross-candidate lane buckets: GLM sweeps that differ
  only in lane COUNT pad onto a small set of shape buckets so near-miss
  sweeps reuse one executable;
* :mod:`.dispatch` — donated-buffer dispatch (backend-aware ``jit`` twins
  with ``donate_argnums``) and the transfer-prefetch seam that overlaps
  device uploads for layer k+1 with layer k's host work;
* :mod:`.fused` — the fused end-to-end scoring graph: the fitted serving
  plan (member vectorizers + plane assembly + feature removal + model
  predict) compiled into ONE donated, bucketed XLA dispatch per
  steady-state batch, with a counted staged-loop fallback (TPX008). See
  docs/tpu.md "The fused scoring graph".

The persistent on-disk program cache itself lives in ``utils/aot.py``
(``aot_call`` / ``prewarm``); every model family and the serving path route
through it, and it reports here; :mod:`.cache` resolves the one directory
both it and JAX's own compilation cache persist under. See docs/tpu.md for
cache location and invalidation rules.
"""
from __future__ import annotations

# NOTE: `compiler.stats` must stay the SUBMODULE (call sites do
# `from ..compiler import stats as cstats; cstats.stats()`), so the
# module-level accessor function is re-exported as `get_stats` only.
from . import stats  # noqa: F401
from .stats import CompileStats, delta, snapshot  # noqa: F401
from .stats import stats as get_stats  # noqa: F401
