"""``run.py --rehearsal`` on the CPU: the contract's last line, every metric
of each cell by name, no device metric, and no CPU fallback without the
flag. Each case is one process, as a run on the chip is."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _child_env():
    """One CPU device (a one-chip cell has one chip; tests/conftest.py
    forces eight), no persistent compile cache."""
    flags = " ".join(
        f for f in os.environ.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    )
    return {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": flags,
            "JAX_ENABLE_COMPILATION_CACHE": "false"}


def _run(*argv, timeout=900):
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, *BENCH["command"][1].split("/")),
         *argv],
        cwd=ROOT, env=_child_env(), capture_output=True, text=True, timeout=timeout,
    )


def _reports(cell, section):
    e2e = [m["name"] for m in BENCH["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if section == "end_to_end":
        return e2e
    return [
        m for m in BENCH["per_layer"]
        if cell in m.get("workloads", [cell] if m["moves"] in e2e else [])
    ]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_the_contract_line(cell, trace):
    done = _run("--workload", cell, "--seed", "2147483777", "--seconds", "1",
                "--trace", str(trace), "--rehearsal")
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert KEYS <= set(line), line
    assert line["correct"] is True, done.stderr[-2000:]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert list(line)[-1] == "compared" and line["compared"]
    if trace == 0:
        assert set(line["metrics"]) == set(_reports(cell, "end_to_end"))
    else:
        wanted = _reports(cell, "per_layer")
        host = {m["name"] for m in wanted if m["source"] != "device_trace"
                and m["name"] != "peak_hbm_gib"}
        device = {m["name"] for m in wanted} - host
        assert host <= set(line["metrics"])
        assert not device & set(line["metrics"]), "device metric from a CPU run"
        assert "breakdown" not in line and "busy_s" not in line["device"]
    assert all(v["value"] == v["value"] for v in line["metrics"].values())


def test_no_tpu_and_no_rehearsal_exits_nonzero():
    done = _run("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                "--trace", "0", timeout=300)
    assert done.returncode != 0
    assert not done.stdout.strip()
