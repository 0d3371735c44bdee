"""Bring-up guards that need no chip: the main path's Pallas kernels must
compile for a TPU v5e (``tools/aot_v5e.py`` runs the real Mosaic compiler
against a ``v5e:2x2`` topology description), and ``chip_smoke.py`` must walk
its whole control flow in ``--rehearsal`` mode, refuse to pass without a
TPU, and fail when a candidate family is excluded or a fused dispatch does
not happen. Every check runs in a subprocess: libtpu's initialisation, the
rehearsal's environment and its exit code stay out of the pytest process.
"""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _env(**extra):
    env = dict(os.environ)
    # the children decide their own platform and device count
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("TPTPU_COMPILE_CACHE", None)
    env.update(extra)
    return env


def _files(path):
    return {
        os.path.join(d, f) for d, _dirs, fs in os.walk(path) for f in fs
    }


def test_main_path_kernels_compile_for_v5e():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "aot_v5e.py")],
        capture_output=True, text=True, timeout=600, env=_env(),
    )
    if p.returncode == 77:
        pytest.skip(p.stdout.strip().splitlines()[-1])
    assert p.returncode == 0, p.stdout[-4000:] + p.stderr[-2000:]
    # the serve kernel was compiled at more than one tree tile
    assert "(13 tree tiles)" in p.stdout and "boost_chunk" in p.stdout
    # both families of objective: XGBoost's and Spark GBT's
    assert "spark:logloss" in p.stdout
    assert "ok   bin_column_stats" in p.stdout


def test_rehearsal_passes_and_keeps_every_cache_file_where_placed(tmp_path):
    placed = tmp_path / "placed"
    checkout_cache = os.path.join(ROOT, ".jax_cache")
    before = _files(checkout_cache)
    p = subprocess.run(
        [sys.executable, SMOKE, "--rehearsal"],
        capture_output=True, text=True, timeout=600, cwd=tmp_path,
        env=_env(
            JAX_COMPILATION_CACHE_DIR=str(placed),
            # conftest turns JAX's cache off for the test session
            JAX_ENABLE_COMPILATION_CACHE="true",
        ),
    )
    assert p.returncode == 0, p.stdout[-4000:] + p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True, "rehearsal": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    assert all(ln.startswith("rehearsal ") for ln in lines[:-1])
    assert not any("FAIL" in ln for ln in lines)
    assert any("service_device_batches: 0" in ln for ln in lines)
    # JAX's entries and the bank's blobs: all under the placed directory
    assert any("execs" in f for f in _files(placed))
    assert _files(checkout_cache) == before


def test_smoke_refuses_to_pass_without_a_tpu():
    t0 = time.monotonic()
    p = subprocess.run(
        [sys.executable, SMOKE], capture_output=True, text=True,
        timeout=120, env=_env(),
    )
    assert p.returncode not in (0, None)
    assert time.monotonic() - t0 < 60
    assert p.stdout.strip() == ""           # no result line
    assert "needs a TPU" in p.stderr


def test_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    p = subprocess.run(
        [sys.executable, "chip_smoke.py", "--rehearsal"],
        capture_output=True, text=True, timeout=120, cwd=tmp_path,
        env=_env(),
    )
    assert p.returncode not in (0, None)
    assert p.stdout.strip() == ""


def test_smoke_fails_on_an_excluded_family_and_a_missing_fused_dispatch(
    tmp_path,
):
    """One injected fault plan shows both: the forest family's sweep is
    made to fail (the selector excludes it and carries on, which is what
    would hide a kernel the compiler refused), and fused dispatch stands
    down under any fault plan (so serve batches go staged)."""
    driver = (
        "import sys; sys.path.insert(0, {root!r})\n"
        "from transmogrifai_tpu.resilience import faults\n"
        "faults.install(faults.FaultPlan().fail_candidate(\n"
        "    'RandomForestClassifier', times=99, transient=False))\n"
        "import chip_smoke\n"
        "sys.exit(chip_smoke.main(['--rehearsal']))\n"
    ).format(root=ROOT)
    p = subprocess.run(
        [sys.executable, "-c", driver], capture_output=True, text=True,
        timeout=600, env=_env(TPTPU_COMPILE_CACHE=str(tmp_path)),
    )
    assert p.returncode == 1, p.stdout[-4000:] + p.stderr[-2000:]
    assert "no family excluded (got ['RandomForestClassifier'])" in p.stderr
    assert "fused dispatches 0" in p.stderr
    assert '"ok"' not in p.stdout
