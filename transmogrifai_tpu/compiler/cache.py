"""Where compiled programs persist — the one resolver both caches use.

Two things are kept on disk between processes: JAX's own persistent
compilation cache (XLA and Mosaic compilations, keyed by JAX) and the
serialized-executable bank of ``utils/aot.py``. Both live under ONE
directory:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX has already read it; its cache
  stays there and nothing here touches ``jax_compilation_cache_dir``. The
  bank goes under the same directory.
* unset — ``<checkout>/.jax_cache`` (git-ignored), a path made from this
  file's location and nothing else: the directory is part of JAX's cache
  key, so a path that moves between runs never hits.

``TPTPU_COMPILE_CACHE`` moves the bank alone (the tests isolate it that
way); JAX's cache never follows it.

Both change with the source: the bank through its salt (``utils/aot.py``),
JAX's cache because ``enable_persistent_cache`` puts the op metadata into
its key. Neither hands back a program compiled from other source text.
"""
from __future__ import annotations

import os

_JAX_ENV = "JAX_COMPILATION_CACHE_DIR"
_BANK_ENV = "TPTPU_COMPILE_CACHE"


def cache_dir() -> str:
    """The directory JAX's persistent compilation cache uses."""
    placed = os.environ.get(_JAX_ENV)
    if placed:
        return placed
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".jax_cache")


def enable_persistent_cache() -> str:
    """Make sure JAX persists compilations under ``cache_dir()`` and return
    that directory. Called by every entry point that is about to compile
    (``Workflow.train``, ``score_function``, ``chip_smoke.py``,
    ``bench.py``); repeats are free. JAX's own switch
    (``JAX_ENABLE_COMPILATION_CACHE=false``) still turns the cache off."""
    import jax

    path = cache_dir()
    if not os.environ.get(_JAX_ENV):
        if jax.config.jax_compilation_cache_dir != path:
            jax.config.update("jax_compilation_cache_dir", path)
    # JAX leaves op metadata (scope names, source lines) out of its key by
    # default, so an edit that changes only metadata gets back the
    # executable compiled before it: a profile then shows the old names,
    # and on XLA:CPU such an executable, once the bank has serialized it,
    # fails to load ("Function ... not found": the instruction numbering
    # moved with the metadata). The bank's salt changes with the source;
    # with the metadata in its key JAX's cache does too.
    if not jax.config.jax_compilation_cache_include_metadata_in_key:
        jax.config.update(
            "jax_compilation_cache_include_metadata_in_key", True
        )
    return path


def bank_dir() -> str:
    """``<root>/execs/<backend>-<ndev>/`` for the executable bank, created
    on demand. Serialized executables are specific to the backend and to
    the number of devices they were compiled over."""
    import jax

    root = os.environ.get(_BANK_ENV) or cache_dir()
    path = os.path.join(
        root, "execs", f"{jax.default_backend()}-{len(jax.devices())}"
    )
    os.makedirs(path, exist_ok=True)
    return path


def entry_count() -> int:
    """Files under ``cache_dir()`` (JAX's entries and, unless the bank was
    moved, its blobs) — the before/after figure a cold-or-warm report
    prints."""
    return sum(len(files) for _root, _dirs, files in os.walk(cache_dir()))
