"""``bin_prepare_device_pct`` on records written by hand: the share of the
window's ``tree/thresholds`` spans that say ``route == "device"``, and
nothing where the program does not say."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks.lib import by_name  # noqa: E402


def _records(sweeps):
    """One ``selector/sweep`` root a sweep, each with the given
    ``tree/thresholds`` attribute dicts under a ``tree/bin_prepare``."""
    out, sid = [], 0
    for s, misses in enumerate(sweeps):
        root = sid = sid + 1
        t0 = 50.0 * s
        for m, args in enumerate(misses):
            prep, thr = sid + 1, sid + 2
            sid += 2
            rec = {"name": "tree/thresholds", "ts": t0 + 3.0 + m, "dur": 0.5,
                   "tid": 2, "id": thr, "parent": prep, "trace": root}
            if args:
                rec["args"] = args
            out.append(rec)
            out.append({"name": "tree/bin_prepare", "ts": t0 + 2.9 + m,
                        "dur": 0.8, "tid": 2, "id": prep, "parent": root,
                        "trace": root, "args": {"cache": "miss"}})
        out.append({"name": "selector/sweep", "ts": t0, "dur": 40.0, "tid": 1,
                    "id": root, "parent": None, "trace": root})
    return out


@pytest.fixture
def program(monkeypatch):
    from transmogrifai_tpu.telemetry import spans

    def install(records):
        monkeypatch.setattr(spans, "snapshot_events", lambda: list(records))
        monkeypatch.setattr(
            spans, "buffer_bounds", lambda: (65536, 64), raising=False)

    return install


DEVICE = {"rows": 1002701, "cols": 357, "bins": 32, "route": "device"}
HOST = {"rows": 600, "cols": 7, "bins": 32, "route": "host", "why": "small"}
UNSAID = {"rows": 1002701, "cols": 357, "bins": 32, "dtype": "float32"}


@pytest.mark.parametrize(
    "sweeps,window,expected",
    [
        ([[DEVICE]], 1, 100.0),
        # the window's sweeps only: the set-up's host miss is not counted
        ([[HOST], [DEVICE], [DEVICE]], 2, 100.0),
        ([[DEVICE, HOST]], 1, 50.0),
        ([[HOST], [{**HOST, "why": "nan"}]], 2, 0.0),
        # a program from before the attribute; one that says it only sometimes
        ([[UNSAID]], 1, None),
        ([[DEVICE, UNSAID]], 1, None),
        # a sweep whose every look-up hit the cache prepared nothing
        ([[]], 1, None),
    ],
)
def test_device_share_of_the_windows_bin_preparations(
    program, sweeps, window, expected
):
    program(_records(sweeps))
    reader = by_name("layer_metrics", "bin_prepare_device_pct")
    got = reader.read(None, [], {"window": {"sweeps": window}}, None)
    assert got == (None if expected is None else pytest.approx(expected))


def test_a_program_without_spans_reads_nothing(program):
    program([])
    reader = by_name("layer_metrics", "bin_prepare_device_pct")
    assert reader.read(None, [], {"window": {"sweeps": 1}}, None) is None


def test_the_metric_is_declared_for_both_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "bin_prepare_device_pct"]
    assert entry == {
        "name": "bin_prepare_device_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "tree fit", "moves": "sweep_s",
        "workloads": ["flagship_xgb.fit", "flagship_rf.fit"],
    }
    assert bench["per_layer"][-1] is entry, "appended, nothing moved"
