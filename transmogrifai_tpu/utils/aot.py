"""Disk-backed AOT program cache (serialized executables).

A fresh process pays program ACQUISITION (trace, compile, load) before it
executes anything; for a short fit that is most of its wall-clock. What
the bank saves over JAX's own compilation cache on a local chip has not
been measured (ROADMAP S3/D2).

This module is the persistent layer of the compile plane
(``transmogrifai_tpu/compiler/``): every model family and the serving path
route their jitted entry points through ``aot_call``, and every event
(compile, hit, corruption drop, invalidation) lands in the
``compiler.stats`` ledger surfaced as ``compileStats``.

Layers, fastest first:
  1. in-memory table (``_MEM``) — same-process repeats are free;
  2. serialized EXECUTABLE cache (``jax.experimental.serialize_executable``)
     — a fresh process skips trace AND compile AND compile-cache load.
     ``prewarm()`` loads banked executables for the current (backend,
     device-count) on a thread pool — optionally filtered to the program
     NAMES a DAG will actually need (``compiler.warmup`` drives this) — so
     the model-selector phase finds them in ``_MEM``;
  3. transparent fallback to a direct ``jit_fn(*args, **statics)`` call on
     ANY failure (new shapes still work; blobs self-invalidate via a
     source-version salt in the key).

Program identity = (source salt incl. jax version, backend, device count,
ambient mesh fingerprint, arg tree structure + shapes/dtypes/shardings,
static kwargs). Blob files are ``{salt}-{name}-{key}.jaxexec`` under
``<cache>/execs/{backend}-{ndev}``, ``<cache>`` being the directory
``compiler/cache.py`` resolves (``JAX_COMPILATION_CACHE_DIR``, else
``<checkout>/.jax_cache``; ``TPTPU_COMPILE_CACHE`` moves the bank
alone); writes are atomic (unique tmp + ``os.replace``),
corrupt/truncated blobs are deleted and recompiled. See docs/tpu.md.

Opt out with TPTPU_AOT=0.
"""
from __future__ import annotations

import hashlib
import logging
import os
import pickle
import re
import threading
import time as _time
from typing import Any, Callable

log = logging.getLogger(__name__)

_LOCK = threading.Lock()
_MEM: dict = {}
_PENDING: set = set()
_FAILED: set = set()
_THREADS: list = []
_SALT: str | None = None

_START = _time.monotonic()


def _stats():
    from ..compiler import stats as _s

    return _s.stats()


def _drain_exports() -> None:
    """Give in-flight background executable saves a chance to land before
    the process exits — daemon threads are otherwise killed mid-compile and
    the blob never materializes. The wait is scaled to process lifetime (a
    process that ran t seconds waits at most min(600, max(5, 2t))): quick
    scoring CLI runs exit within seconds, while long bench/training runs
    may sit out a background compile that takes minutes — capping those at
    60 s starved the bank forever (the same key re-missed every run)."""
    elapsed = _time.monotonic() - _START
    # long-lived processes (bench/training runs) may be draining a save
    # whose background compile is minutes — capping those at 60 s starves
    # the bank forever (the same key misses every run); quick CLI runs
    # stay bounded by twice their own lifetime
    budget = min(600.0, max(5.0, 2.0 * elapsed))
    deadline = _time.monotonic() + budget
    for th in list(_THREADS):
        th.join(timeout=max(0.0, deadline - _time.monotonic()))
    alive = [th for th in _THREADS if th.is_alive()]
    if alive:
        log.info("abandoning %d unfinished AOT saves at exit", len(alive))


import atexit  # noqa: E402

atexit.register(_drain_exports)


class DonatedArgsConsumed(RuntimeError):
    """A banked executable donated (deleted) some of the caller's args and
    then failed — no in-place fallback can run. Propagated past aot_call's
    transparent-fallback handler so the caller-level retry (the
    candidate-sweep RetryPolicy) re-enters with fresh buffers."""


def _enabled() -> bool:
    return os.environ.get("TPTPU_AOT", "1") != "0"


def _exec_dir() -> str:
    from ..compiler.cache import bank_dir

    return bank_dir()


def _version_salt() -> str:
    """Hash of the source files whose tracing the cache skips — a code
    change invalidates every blob. The jax version rides the salt too: a
    serialized executable is runtime-specific, and loading one saved under
    a different jax/XLA build is undefined behavior at best."""
    global _SALT
    if _SALT is None:
        import jax

        h = hashlib.sha256()
        # filename layout salt-name-key.jaxexec; payload (executable,
        # in_tree, out_tree, execution device ids)
        h.update(b"aot-format-3")
        h.update(f"jax={jax.__version__}".encode())
        pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        # every file that DEFINES an aot_call-routed jit_fn must be listed,
        # or editing it serves stale banked executables of the old code
        for rel in (
            "models/trees.py", "models/hist_pallas.py", "models/solvers.py",
            "models/gbdt.py", "models/serve_pallas.py", "compiler/fused.py",
            "ops/embeddings.py",
        ):
            try:
                with open(os.path.join(pkg, rel), "rb") as fh:
                    h.update(fh.read())
            except OSError:
                h.update(rel.encode())
        # TPTPU_DONATE is program identity too: donation is baked into the
        # serialized executable, so a donating blob served to a donate-off
        # process would still delete the caller's buffers (and a donate-off
        # blob would permanently disable the optimization).
        h.update(f"TPTPU_DONATE={os.environ.get('TPTPU_DONATE', '')}".encode())
        _SALT = h.hexdigest()[:16]
    return _SALT


def _mesh_fp() -> str:
    """Compact ambient-execution-mesh fingerprint: a blob compiled for a
    4-device data mesh must never shadow the single-device program of the
    same shapes (and per-leaf shardings alone miss fully-replicated
    args)."""
    try:
        from ..parallel.mesh import execution_mesh

        mesh = execution_mesh()
    except Exception:
        return "none"
    if mesh is None:
        return "none"
    try:
        return ",".join(
            f"{name}{int(mesh.shape[name])}" for name in mesh.axis_names
        )
    except Exception:
        return "unknown"


def _key(name: str, args: tuple, statics: dict) -> str:
    import jax

    # device count + per-leaf shardings are part of program identity: a
    # blob exported single-device must not shadow a mesh-sharded variant
    # (and vice versa) on the same backend/shapes
    parts = [name, _version_salt(), jax.default_backend(),
             f"ndev={len(jax.devices())}", f"mesh={_mesh_fp()}"]
    parts.append(str(jax.tree_util.tree_structure(args)))
    for a in jax.tree_util.tree_leaves(args):
        parts.append(f"{getattr(a, 'shape', ())}:{getattr(a, 'dtype', type(a).__name__)}")
        sharding = getattr(a, "sharding", None)
        if sharding is not None:
            parts.append(str(sharding))
    for k in sorted(statics):
        parts.append(f"{k}={statics[k]}")
    return hashlib.sha256("|".join(map(str, parts)).encode()).hexdigest()[:24]


def _safe_name(name: str) -> str:
    """Program name as a filename segment (no dashes: the filename parser
    splits on them)."""
    return re.sub(r"[^A-Za-z0-9_]", "_", name)


def _blob_path(name: str, key: str) -> str:
    return os.path.join(
        _exec_dir(), f"{_version_salt()}-{_safe_name(name)}-{key}.jaxexec"
    )


def _parse_blob_name(fn: str) -> tuple[str, str, str] | None:
    """(salt, name, key) from ``salt-name-key.jaxexec``; None for files in
    an unknown layout (deleted on sight, like any stale-version blob)."""
    if not fn.endswith(".jaxexec"):
        return None
    parts = fn[: -len(".jaxexec")].split("-")
    if len(parts) != 3:
        return None
    return parts[0], parts[1], parts[2]


def _load_exec(path: str):
    """pickle → deserialize_and_load → callable; raises on a corrupt or
    truncated blob (callers delete-and-recompile)."""
    from jax.experimental import serialize_executable as SE

    t0 = _time.monotonic()
    with open(path, "rb") as fh:
        blob = pickle.loads(fh.read())
    if not isinstance(blob, tuple) or len(blob) != 4:
        # pickle decoded but the payload is not ours — a torn write that
        # happened to truncate on a valid pickle boundary
        raise ValueError(f"malformed executable blob (got {type(blob).__name__})")
    payload, in_tree, out_tree, device_ids = blob
    import jax

    # load over the devices the program was compiled for: the default is
    # every local device, and a single-device executable loaded that way
    # fails its first call on any host with more than one
    by_id = {d.id: d for d in jax.devices()}
    compiled = SE.deserialize_and_load(
        payload, in_tree, out_tree,
        execution_devices=[by_id[i] for i in device_ids],
    )
    log.info(
        "AOT load %s (%.1f MB) in %.2f s", os.path.basename(path),
        os.path.getsize(path) / 1e6, _time.monotonic() - t0,
    )
    try:
        os.utime(path)  # recency marker for pruning
    except OSError:
        pass
    return lambda *a: compiled(*a)


def _acquire_banked(path: str, name: str, key: str):
    """Lazy (non-prewarm) acquire of a banked executable, guarded the same
    way ``prewarm`` guards its loads: a corrupt/truncated ``.jaxexec`` is
    deleted so the caller recompiles, instead of crashing the sweep thread
    that happened to touch it first. Returns a callable or None."""
    if not os.path.exists(path):
        return None
    try:
        return _load_exec(path)
    except Exception as e:
        log.info("AOT executable %s unusable (%s); removing", key, e)
        _stats().bump("corruptBlobsDropped")
        try:
            os.remove(path)
        except OSError:
            pass
        return None


def prewarm(
    max_workers: int = 8,
    max_bytes: int = 32_000_000,
    names: set | frozenset | None = None,
) -> int:
    """Load banked executables for this backend/device-count into ``_MEM``
    on a thread pool. Call early (e.g. right after backend init) so
    acquisition overlaps the data/feature phases; returns the number of
    programs loaded. ``names`` restricts the load to those program names
    (the DAG-aware warmup passes the families it will actually fit) —
    unlisted blobs stay on disk untouched. Files from other source
    versions can never hit (the key embeds the salt), so they are deleted
    on sight — without this the bank grows by a full program set per source
    edit and prewarm ships gigabytes of dead executables."""
    if not _enabled():
        return 0
    try:
        d = _exec_dir()
    except Exception:
        return 0
    salt = _version_salt()
    safe_names = None if names is None else {_safe_name(n) for n in names}
    paths = []
    for fn in os.listdir(d):
        if not fn.endswith(".jaxexec"):
            continue
        p = os.path.join(d, fn)
        parsed = _parse_blob_name(fn)
        if parsed is None or parsed[0] != salt:
            _stats().bump("versionInvalidations")
            try:
                os.remove(p)
            except OSError:
                pass
            continue
        _salt_seg, name_seg, _key_seg = parsed
        if safe_names is not None and name_seg not in safe_names:
            continue
        try:
            if os.path.getsize(p) > max_bytes:
                # big executables load lazily instead, inside whichever
                # family thread needs them, so their load does not
                # contend with the foreground work's device traffic. The
                # cap is not re-measured on a local chip; see ROADMAP S3.
                continue
        except OSError:
            continue
        paths.append(p)
    if not paths:
        return 0
    from concurrent.futures import ThreadPoolExecutor

    loaded = [0]

    def _one(p):
        key = _parse_blob_name(os.path.basename(p))[2]
        with _LOCK:
            if key in _MEM:
                return
        try:
            call = _load_exec(p)
        except Exception as e:
            log.info("prewarm: dropping unusable executable %s (%s)", p, e)
            _stats().bump("corruptBlobsDropped")
            try:
                os.remove(p)
            except OSError:
                pass
            return
        with _LOCK:
            _MEM.setdefault(key, call)
            loaded[0] += 1

    with ThreadPoolExecutor(max_workers=max_workers) as pool:
        list(pool.map(_one, paths))
    log.info("prewarm: %d executables loaded", loaded[0])
    return loaded[0]


def aot_call(
    name: str, jit_fn: Callable, args: tuple, statics: dict
) -> Any:
    """``jit_fn(*args, **statics)`` through the executable cache.

    NOTE on donation: when ``jit_fn`` was built with ``donate_argnums``
    (compiler.dispatch.donating), the banked executable donates too —
    callers must treat those args as consumed on EVERY path through here.
    """
    if not _enabled():
        return jit_fn(*args, **statics)
    try:
        key = _key(name, args, statics)
        with _LOCK:
            call = _MEM.get(key)
        if call is not None:
            # NOTE: dispatch is async — timing this call would measure
            # enqueue latency, not execution
            log.debug("AOT hit %s (%s)", name, key)
            _stats().bump("cacheHitsMemory")
            return call(*args)
        path = _blob_path(name, key)
        call = _acquire_banked(path, name, key)
        if call is not None:
            try:
                out = call(*args)
                with _LOCK:
                    _MEM[key] = call
                _stats().bump("cacheHitsDisk")
                return out
            except Exception as e:
                # blob deserialized but the executable is broken (stale
                # runtime, torn payload): remove it so a future first-use
                # re-saves instead of permanently disabling the cache
                log.info("AOT executable %s unusable (%s); removing", key, e)
                _stats().bump("corruptBlobsDropped")
                try:
                    os.remove(path)
                except OSError:
                    pass
                import jax

                if any(
                    getattr(a, "is_deleted", lambda: False)()
                    for a in jax.tree_util.tree_leaves(args)
                ):
                    # the broken executable DONATED some args before
                    # failing — the direct-call fallback below would crash
                    # on the deleted buffers with a baffling error deep in
                    # dispatch. Re-raise instead: the candidate-level
                    # RetryPolicy (selector/validators.py) re-enters the
                    # sweep with fresh buffers, and the blob is gone.
                    log.warning(
                        "AOT executable %s consumed donated args before "
                        "failing; re-raising for caller-level retry", key,
                    )
                    raise DonatedArgsConsumed(
                        f"banked executable for {name} failed after "
                        f"donating its inputs: {e}"
                    ) from e
        # first use of this program version: run directly, then save the
        # compiled executable in the background so FUTURE processes skip
        # trace+compile. _PENDING dedupes concurrent validator threads;
        # _FAILED is the negative cache; the tmp suffix is unique per
        # thread so racing writers can't interleave one file.
        t_direct = _time.monotonic()
        import warnings

        with warnings.catch_warnings():
            # donated lane params ([K] reg/elastic-net) alias the [K]
            # intercept output; the [K'] bucketed twin of a sweep whose
            # shapes DON'T line up is expected to fall back to copy —
            # jax warns per-compile, which would spam every sweep
            warnings.filterwarnings(
                "ignore", message=".*donated buffers.*"
            )
            out = jit_fn(*args, **statics)
        log.info(
            "AOT miss %s (%s): direct call %.2f s", name, key,
            _time.monotonic() - t_direct,
        )
        _stats().record_compile(name)
        with _LOCK:
            if key not in _MEM:
                # same-process repeats reuse jit_fn's warm cache
                _MEM[key] = lambda *a: jit_fn(*a, **statics)
            if key in _PENDING or key in _FAILED:
                return out
            _PENDING.add(key)

        def _save():
            try:
                if os.environ.get("TPTPU_PROGRAM_AUDIT", "0") == "1":
                    # bank-admission contract audit (analysis/program.py):
                    # a program that bakes giant constants, leaks x64,
                    # or embeds host callbacks must never persist a blob
                    # — the violating executable would be served to every
                    # future process. Runs on this background thread, so
                    # the audit costs the foreground dispatch nothing;
                    # with the env unset the gate is one dict read.
                    from ..analysis.program import audit_jit_call

                    _stats().bump("programsAudited")
                    audit_rep = audit_jit_call(name, jit_fn, args, statics)
                    # ERROR findings only (baked constants, x64 leaks,
                    # host callbacks): warnings are reported, not
                    # refused — a weak-typed auxiliary output must not
                    # negative-cache the program out of the bank
                    bad = audit_rep.errors()
                    if bad:
                        _stats().bump("programAuditRejected")
                        log.warning(
                            "program audit refused bank admission of %s: "
                            "%s", name,
                            "; ".join(f.render() for f in bad),
                        )
                        with _LOCK:
                            _FAILED.add(key)
                        return
                from jax.experimental import serialize_executable as SE

                # .lower().compile() is a load from JAX's persistent
                # compilation cache where that is on
                # (compiler/cache.py) and a second compile of the same
                # computation where it is off. Lowering only needs avals,
                # so it is safe even when the direct call above DONATED
                # some of args.
                t0 = _time.monotonic()
                compiled = jit_fn.lower(*args, **statics).compile()
                payload, in_tree, out_tree = SE.serialize(compiled)
                device_ids = [
                    d.id
                    for d in compiled.runtime_executable().local_devices()
                ]
                blob = pickle.dumps(
                    (payload, in_tree, out_tree, device_ids)
                )
                tmp = path + f".tmp{os.getpid()}.{threading.get_ident()}"
                with open(tmp, "wb") as fh:
                    fh.write(blob)
                os.replace(tmp, path)
                log.info(
                    "AOT saved %s (%s, %.1f MB) in %.1f s", name, key,
                    len(blob) / 1e6, _time.monotonic() - t0,
                )
            except Exception as e:  # never break the fit for the cache
                log.info("AOT save of %s failed: %s", name, e)
                _stats().bump("savesFailed")
                with _LOCK:
                    _FAILED.add(key)
            finally:
                with _LOCK:
                    _PENDING.discard(key)

        th = threading.Thread(target=_save, daemon=True)
        with _LOCK:
            _THREADS.append(th)
        th.start()
        return out
    except DonatedArgsConsumed:
        # args are gone — the transparent direct-call fallback below would
        # crash on deleted buffers; let the caller-level retry recover
        raise
    except Exception as e:
        log.info("AOT cache bypassed for %s: %s", name, e)
        return jit_fn(*args, **statics)
