"""Donated-buffer, pipelined dispatch helpers.

Two seams that cut hot-path dispatch cost without touching any math:

* **Donation** — ``donating(name, jit_fn, ...)`` builds a ``jax.jit`` twin
  of a module-level jitted function with ``donate_argnums`` set, so a
  carried buffer (the boosting margin between chunk programs, a sweep's
  fresh mask stack) is aliased into the output instead of copied. Callers
  must treat donated args as CONSUMED — every wired call site passes a
  buffer it never reads again. ``TPTPU_DONATE=0`` falls back to the
  undonated original.

* **Transfer prefetch** — ``prefetch_f32(arr)`` starts the async
  host→device upload of a float32 view of ``arr`` while host-side work
  (layer transforms, checkpoint saves, row codecs) is still running;
  ``device_f32(arr)`` picks the in-flight buffer up at dispatch time (or
  falls back to its own upload). This is how layer k+1's input
  transfer overlaps layer k's compute. Prefetch is a
  no-op under an active execution mesh — GSPMD placement stays with the
  sharding helpers in ``parallel/mesh.py``. Both upload in the layout the
  host array HAS (``host_layout``): a column-major plane goes up as its
  row-major transpose view and is transposed on the device, so the host
  never re-lays the plane out.
"""
from __future__ import annotations

import functools
import logging
import os
import threading
import weakref
from typing import Any, Callable, Sequence

import numpy as np

log = logging.getLogger(__name__)

_DONATED: dict[str, Any] = {}
_DONATED_LOCK = threading.Lock()


def donating(
    name: str,
    jit_fn: Callable,
    donate_argnums: tuple[int, ...],
    static_argnames: Sequence[str] = (),
) -> Callable:
    """Donation-enabled twin of ``jit_fn`` (cached by ``name``). Returns
    ``jit_fn`` unchanged when donation is disabled or the wrapped python
    function is not recoverable."""
    if os.environ.get("TPTPU_DONATE", "1") == "0":
        return jit_fn
    with _DONATED_LOCK:
        got = _DONATED.get(name)
    if got is not None:
        return got
    base = getattr(jit_fn, "__wrapped__", None)
    if base is None:
        got = jit_fn
    else:
        import jax

        try:
            got = jax.jit(  # tp: disable=TPL003 — cached in _DONATED
                base,
                static_argnames=tuple(static_argnames),
                donate_argnums=donate_argnums,
            )
        except Exception as e:  # donation must never break a fit
            log.info("donated twin of %s unavailable (%s)", name, e)
            got = jit_fn
    with _DONATED_LOCK:
        _DONATED.setdefault(name, got)
        return _DONATED[name]


# ---------------------------------------------------------------- prefetch
# id -> (weakref-to-source, device buffer); small FIFO — entries exist only
# between a prefetch and the dispatch that consumes them
_PREFETCH: dict[int, tuple] = {}
_PREFETCH_LOCK = threading.Lock()
_PREFETCH_CAP = 8


def _mesh_active() -> bool:
    try:
        from ..parallel.mesh import execution_mesh

        return execution_mesh() is not None
    except Exception:
        return False


def host_layout(arr) -> str:
    """``"F"`` for a 2-D column-major host array (what a fancy column index
    of a row-major plane returns: the SanityChecker's output), else
    ``"C"``: how ``device_f32`` uploads it, and the ``layout`` attribute of
    the ``tree/upload`` and ``compile/prefetch`` spans."""
    if (
        isinstance(arr, np.ndarray) and arr.ndim == 2
        and arr.flags.f_contiguous and not arr.flags.c_contiguous
    ):
        return "F"
    return "C"


@functools.cache
def _transpose_program():
    """The jitted device transpose, made once (this module imports jax
    lazily): ``aot_call`` and jax's own cache key on the object."""
    import jax
    import jax.numpy as jnp

    return jax.jit(jnp.transpose)  # tp: disable=TPL003 — cached


def _upload_f32(arr: np.ndarray):
    """``arr`` as a float32 device array of ``arr``'s shape; the dtype is
    converted on the HOST (an eager device-side convert compiles a
    per-process program: see gbdt._binned). The runtime needs a row-major
    buffer, and makes one with a strided host pass when handed anything
    else (2.2-2.9 s for a column-major 1,002,701 x 357 plane, the device
    idle). A column-major array's transpose IS row-major, so that view
    goes up as it lies and one device program transposes it back (2 x the
    plane of HBM traffic): the same values in the same device layout."""
    import jax

    host = np.asarray(arr, dtype=np.float32)
    if host_layout(host) == "C":
        return jax.device_put(host)
    # through the executable bank, as every program on a fit's path
    from ..utils.aot import aot_call

    return aot_call(
        "plane_transpose", _transpose_program(), (jax.device_put(host.T),), {}
    )


def prefetch_f32(arr) -> None:
    """Start the async device upload of ``np.asarray(arr, float32)``;
    ``device_f32`` on the SAME object (by identity) picks it up. Errors are
    swallowed — prefetch is purely an overlap optimization."""
    try:
        if _mesh_active():
            return
        src = arr
        key = id(src)
        with _PREFETCH_LOCK:
            if key in _PREFETCH:
                return
        from ..telemetry import runlog as _runlog
        from ..telemetry import spans as _tspans

        nbytes = int(getattr(arr, "nbytes", 0))
        with _tspans.span(
            "compile/prefetch", bytes=nbytes, layout=host_layout(arr)
        ):
            t0 = _tspans.clock()
            buf = _upload_f32(arr)
            # runtime transfer census (telemetry/runlog.py): every upload
            # through this seam is one host->device crossing the run
            # ledger counts — the live counterpart of the static TPX
            # census in analysis/plan_audit.py
            _runlog.record_upload(
                buf.nbytes if hasattr(buf, "nbytes") else nbytes,
                _tspans.clock() - t0,
            )
        try:
            ref = weakref.ref(src)
        except TypeError:  # source not weakref-able: skip (no way to
            return         # detect the id being recycled)
        with _PREFETCH_LOCK:
            _PREFETCH[key] = (ref, buf)
            while len(_PREFETCH) > _PREFETCH_CAP:
                _PREFETCH.pop(next(iter(_PREFETCH)))
    except Exception as e:
        log.debug("prefetch skipped: %s", e)


def prefetch_pending(arr) -> bool:
    """Whether ``device_f32(arr)`` would pick up a prefetched buffer
    rather than upload (what the ``tree/upload`` span records)."""
    with _PREFETCH_LOCK:
        hit = _PREFETCH.get(id(arr))
    return hit is not None and hit[0]() is arr and not _mesh_active()


def device_f32(arr):
    """The prefetched device buffer for ``arr`` if one is in flight (and
    the source object is still alive — a dead ref means the id may have
    been recycled), else a float32 upload in the layout ``arr`` has
    (``_upload_f32``). Entries are NOT consumed: several model families
    dispatch on the same training matrix. Callers must not mutate ``arr``
    between prefetch and dispatch."""
    import jax.numpy as jnp

    key = id(arr)
    with _PREFETCH_LOCK:
        hit = _PREFETCH.get(key)
        # purge dead refs opportunistically so recycled ids cannot alias
        # (r is a weakref deref — runs no user code, takes no locks)
        for k in [k for k, (r, _) in _PREFETCH.items() if r() is None]:  # tp: disable=TPC004
            _PREFETCH.pop(k, None)
    if hit is not None:
        ref, buf = hit
        if ref() is arr and not _mesh_active():
            # the upload was already counted at prefetch time — a pickup
            # is not a second transfer
            return buf
    import jax

    if isinstance(arr, jax.Array):
        # already-device: re-wraps without crossing the boundary — no
        # census entry, no clock reads on this fast path
        return jnp.asarray(arr, dtype=jnp.float32)
    from ..telemetry import runlog as _runlog
    from ..telemetry import spans as _tspans

    t0 = _tspans.clock()
    if isinstance(arr, np.ndarray):
        out = _upload_f32(arr)
    else:
        out = jnp.asarray(arr, dtype=jnp.float32)
    # fresh upload (no prefetch in flight): one host->device crossing
    # on the run ledger's runtime transfer census
    _runlog.record_upload(
        int(getattr(out, "nbytes", getattr(arr, "nbytes", 0))),
        _tspans.clock() - t0,
    )
    return out


def clear_prefetch() -> None:
    """Release every prefetched device buffer. The phases that prefetch
    (DAG fit, columnar scoring) call this when they finish — without it a
    long-lived process would pin up to ``_PREFETCH_CAP`` training-matrix
    buffers in device memory for its lifetime."""
    with _PREFETCH_LOCK:
        _PREFETCH.clear()
