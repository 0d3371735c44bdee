"""The yardstick: everything the benchmark computes itself."""
import importlib
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def by_name(kind: str, name: str):
    """benchmarks/<kind>/<name>.py, found by file name: a driver, a
    per-layer reader, a family's check, a work count. An unknown name is
    an error that says which file is missing."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise SystemExit(f"no {kind} file for {name!r}: {path}")
    root = os.path.dirname(HERE)
    if root not in sys.path:
        sys.path.insert(0, root)
    return importlib.import_module(f"benchmarks.{kind}.{name}")
