"""SPMD contract auditor (analysis/spmd.py, TPS0xx) — seeded
positive/negative corpus for every code, the jaxpr/HLO collective
census, the per-host collective-tape reconciler (parallel/guarded.py),
the CLI gate, and the <10s/<30s/<2%
performance pins."""
import json
import os
import textwrap
import time
from functools import partial

import numpy as np
import pytest

from transmogrifai_tpu.analysis import spmd as SP
from transmogrifai_tpu.analysis.findings import CODES
from transmogrifai_tpu.parallel import guarded as G

pytestmark = pytest.mark.analysis

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def scan(src: str, rel: str = "transmogrifai_tpu/parallel/corpus.py"):
    return SP.analyze_source(textwrap.dedent(src), rel)


def codes(report):
    return [f.code for f in report.findings]


@pytest.fixture
def taped():
    """Tracing on with clean tapes; always restored."""
    prev = G.set_tracing(True)
    G.reset_tapes()
    yield
    G.set_tracing(prev)
    G.reset_tapes()


# ==========================================================================
# registry hygiene
# ==========================================================================
def test_tps_codes_registered():
    for i in range(9):
        assert f"TPS00{i}" in CODES


def test_tps_suppression_directive():
    rep = scan("""
        def f(x, mesh):
            if process_index() == 0:
                pcolumn_stats(x, mesh)  # tps: disable=TPS001
    """)
    assert codes(rep) == []


# ==========================================================================
# TPS001 — collective-issue-order divergence
# ==========================================================================
def test_tps001_process_index_branch_positive():
    rep = scan("""
        def refit(x, mesh):
            if process_index() == 0:
                return pcolumn_stats(x, mesh)
            return None
    """)
    assert codes(rep) == ["TPS001"]


def test_tps001_failover_reentry_positive():
    """The PR-3 FailoverController re-entry shape: a retry loop whose
    exit depends on per-host timing re-issues the collective different
    numbers of times per host."""
    rep = scan("""
        def guarded_rerun(x, mesh, deadline):
            attempt = 0
            while True:
                start = monotonic()
                out = pxtx(x, mesh)
                took = monotonic() - start
                if took <= deadline:
                    return out
                attempt += 1
    """)
    assert "TPS001" in codes(rep)


def test_tps001_host_varying_loop_positive():
    rep = scan("""
        def per_block(blocks, mesh):
            mine = live_hosts()
            for h in mine:
                phistogram(blocks[h], 8, mesh)
    """)
    assert "TPS001" in codes(rep)


def test_tps001_barrier_fixed_twin_negative():
    """The fixed twin: the branch predicate is itself the result of an
    agreeing collective — every host computes the SAME flag, so the
    branch cannot diverge."""
    rep = scan("""
        def refit(x, flags, mesh):
            any_lost = psum(flags, "data")
            if any_lost:
                return pcolumn_stats(x, mesh)
            return None
    """)
    assert codes(rep) == []


def test_tps001_untainted_branch_negative():
    rep = scan("""
        def stats(x, mesh, want_hist):
            if want_hist:
                return phistogram(x, 8, mesh)
            return pcolumn_stats(x, mesh)
    """)
    assert codes(rep) == []


def test_tps001_assignment_clears_on_agreed_value():
    # reassigning a tainted name from an agreed source clears the taint
    rep = scan("""
        def f(x, mesh):
            n = process_index()
            n = psum(x, "data")
            if n > 0:
                pxtx(x, mesh)
    """)
    assert codes(rep) == []


# ==========================================================================
# TPS002 — unbound axis in a shard_map body
# ==========================================================================
KERNEL_TMPL = """
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    import jax

    DATA_AXIS = "data"

    @partial(
        shard_map, mesh=mesh, in_specs=(P(DATA_AXIS, None),),
        out_specs=P(), check_vma=False,
    )
    def body(xs):
        return jax.lax.psum(xs.sum(axis=0), {axis})
"""


def test_tps002_unbound_axis_positive():
    rep = scan(KERNEL_TMPL.format(axis='"model"'))
    assert codes(rep) == ["TPS002"]


def test_tps002_bound_axis_negative():
    rep = scan(KERNEL_TMPL.format(axis="DATA_AXIS"))
    assert codes(rep) == []


def test_tps002_unresolvable_axis_skipped():
    # an axis passed as a parameter (models/trees.py style) is not
    # statically judgeable — never guess
    rep = scan("""
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        import jax

        @partial(shard_map, mesh=mesh, in_specs=(P("data", None),),
                 out_specs=P(), check_vma=False)
        def body(xs, axis_name):
            return jax.lax.psum(xs, axis_name)
    """)
    assert codes(rep) == []


def test_tps002_repo_kernels_clean():
    for mod in ("reductions", "multihost", "ring", "segments"):
        path = os.path.join(REPO, "transmogrifai_tpu", "parallel",
                            f"{mod}.py")
        with open(path) as fh:
            rep = SP.analyze_source(
                fh.read(), f"transmogrifai_tpu/parallel/{mod}.py"
            )
        assert codes(rep) == [], (mod, [f.render() for f in rep.findings])


# ==========================================================================
# TPS003 — PartitionSpec rank/axis mismatch
# ==========================================================================
def test_tps003_axis_not_in_mesh_vocabulary_positive():
    rep = scan("""
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from transmogrifai_tpu.parallel.mesh import make_mesh
        import jax

        @partial(shard_map, mesh=make_mesh(8, 1),
                 in_specs=(P("dcn", None),), out_specs=P(),
                 check_vma=False)
        def body(xs):
            return xs.sum()
    """)
    assert "TPS003" in codes(rep)


def test_tps003_rank_mismatch_positive():
    rep = scan("""
        import jax
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P

        def place(mesh):
            x = np.zeros((16,), dtype=np.float32)
            return jax.device_put(x, NamedSharding(mesh, P("data", None)))
    """)
    assert "TPS003" in codes(rep)


def test_tps003_matching_rank_negative():
    rep = scan("""
        import jax
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P

        def place(mesh):
            x = np.zeros((16, 4), dtype=np.float32)
            return jax.device_put(x, NamedSharding(mesh, P("data", None)))
    """)
    assert codes(rep) == []


# ==========================================================================
# TPS004 — non-commutative / dtype-unstable guarded reduction
# ==========================================================================
def test_tps004_raw_moment_variance_positive():
    rep = scan("""
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        import jax

        @partial(shard_map, mesh=mesh, in_specs=(P("data", None),),
                 out_specs=P(), check_vma=False)
        def var_kernel(xs):
            sumsq = jax.lax.psum((xs * xs).sum(axis=0), "data")
            s = jax.lax.psum(xs.sum(axis=0), "data")
            return sumsq - s * s
    """)
    assert "TPS004" in codes(rep)


def test_tps004_f64_in_kernel_positive():
    rep = scan("""
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        import jax
        import jax.numpy as jnp

        @partial(shard_map, mesh=mesh, in_specs=(P("data", None),),
                 out_specs=P(), check_vma=False)
        def acc(xs):
            return jax.lax.psum(xs.astype(jnp.float64).sum(axis=0), "data")
    """)
    assert "TPS004" in codes(rep)


def test_tps004_centered_two_pass_negative():
    # the repo's own centered scheme: subtraction happens BEFORE the
    # reduce, on a replicated argument — commutative and stable
    rep = scan("""
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        import jax

        @partial(shard_map, mesh=mesh,
                 in_specs=(P("data", None), P()), out_specs=P(),
                 check_vma=False)
        def m2(xs, mean):
            c = xs - mean[None, :]
            return jax.lax.psum((c * c).sum(axis=0), "data")
    """)
    assert codes(rep) == []


# ==========================================================================
# TPS005 — collective under a lock
# ==========================================================================
def test_tps005_collective_under_lock_positive():
    rep = scan("""
        def refresh(x, mesh, lock, cache):
            with lock:
                cache["stats"] = pcolumn_stats(x, mesh)
    """)
    assert codes(rep) == ["TPS005"]


def test_tps005_snapshot_then_issue_negative():
    rep = scan("""
        def refresh(x, mesh, lock, cache):
            with lock:
                snapshot = dict(cache)
            stats = pcolumn_stats(x, mesh)
            with lock:
                cache["stats"] = stats
    """)
    assert codes(rep) == []


# ==========================================================================
# TPS007 — host-dependent shapes feeding placement
# ==========================================================================
def test_tps007_unpadded_host_block_positive():
    rep = scan("""
        def ingest(fetch, n, mesh):
            local = read_host_block(fetch, n, mesh)
            return make_global_array(local, mesh, n)
    """)
    assert codes(rep) == ["TPS007"]


def test_tps007_sliced_rows_positive():
    rep = scan("""
        def stats(x, n, mesh):
            sl = host_row_slice(n, mesh)
            return shard_rows(mesh, x[sl])
    """)
    assert codes(rep) == ["TPS007"]


def test_tps007_zero_block_copy_negative():
    # the repo's own pattern: the placed block comes from a fixed-shape
    # np.zeros buffer, the host rows are copied INTO it
    rep = scan("""
        import numpy as np

        def stats(x_local, chunk, f, mesh, padded):
            block = np.zeros((chunk, f + 1), dtype=np.float32)
            block[: len(x_local), :f] = x_local
            return make_global_array(block, mesh, padded)
    """)
    assert codes(rep) == []


def test_tps007_pad_then_place_negative():
    rep = scan("""
        import numpy as np

        def ingest(fetch, n, chunk, mesh):
            local = read_host_block(fetch, n, mesh)
            pad = np.zeros((chunk - local.shape[0],), dtype=np.float32)
            local = np.concatenate([local, pad], axis=0)
            return make_global_array(local, mesh, n)
    """)
    assert codes(rep) == []


# ==========================================================================
# the repo itself scans clean (baseline is empty on purpose)
# ==========================================================================
def test_repo_static_pass_clean_and_fast():
    t0 = time.perf_counter()
    rep = SP.analyze_paths(
        [os.path.join(REPO, p) for p in SP.DEFAULT_SPMD_PATHS], root=REPO
    )
    wall = time.perf_counter() - t0
    assert codes(rep) == [], [f.render() for f in rep.findings]
    # whole-repo static pass bound (acceptance pin)
    assert wall < 10.0, f"static pass took {wall:.2f}s"
    # the seam census names every guarded collective family
    seams = SP.seam_collective_census(
        [os.path.join(REPO, p) for p in SP.DEFAULT_SPMD_PATHS], root=REPO
    )
    assert set(seams) == {
        "pcolumn_stats", "pcentered_gram", "pxtx", "phistogram",
        "pcontingency", "global_column_stats", "ring_gram",
        "psegment_reduce",
    }


def test_spmd_baseline_committed_and_empty():
    with open(os.path.join(REPO, "spmd_baseline.json")) as fh:
        doc = json.load(fh)
    assert doc["findings"] == []  # clean tree: the bar starts at zero


# ==========================================================================
# IR leg: the static collective census + TPS006
# ==========================================================================
def test_collective_census_traces_all_kernels_under_30s():
    t0 = time.perf_counter()
    rep = SP.static_collective_census()
    wall = time.perf_counter() - t0
    assert wall < 30.0, f"IR census took {wall:.2f}s"
    assert codes(rep) == [], [f.render() for f in rep.findings]
    census = rep.data["collectiveCensus"]
    expected = {
        "pstats_pass1", "pstats_pass2", "pgram_sums", "pgram_centered",
        "pxtx", "phistogram", "pcontingency", "global_stats_pass1",
        "global_stats_pass2", "ring_gram", "psegment_sum", "psegment_max",
        "sweep_linear_sharded", "sweep_logistic_binary_sharded",
    }
    assert expected <= set(census), sorted(census)

    def prims(name):
        return {c["primitive"] for c in census[name]["collectives"]}

    # the stats kernel reduces with psum + pmin + pmax over the data axis
    assert prims("pstats_pass1") == {"psum", "pmin", "pmax"}
    assert all(
        c["axes"] == "data" for c in census["pstats_pass1"]["collectives"]
    )
    # the ring kernel's only collective is the neighbor permute
    assert prims("ring_gram") == {"ppermute"}
    assert census["ring_gram"]["hloKinds"] == ["collective_permute"]
    # the DCN kernels reduce over BOTH host and chip axes
    assert census["global_stats_pass1"]["collectives"][0]["axes"] == \
        "dcn,data"
    # the sharded sweep programs are lane-parallel by construction: every
    # lane owns its whole fit, so the pre-partition IR carries NO
    # collectives — an all_reduce appearing here would mean the layout
    # resharded behind our backs (TPS006)
    assert prims("sweep_linear_sharded") == set()
    assert census["sweep_linear_sharded"]["hloKinds"] == []
    assert prims("sweep_logistic_binary_sharded") == set()
    assert census["sweep_logistic_binary_sharded"]["hloKinds"] == []
    # every declared program's HLO reconciled (no TPS006 above); programs
    # with no declared collectives reconcile to an empty kind set
    assert all(
        v["hloKinds"] or not v["collectives"] for v in census.values()
    )


def test_tps006_hidden_hlo_collective_positive():
    rep = SP.reconcile_hlo_census(
        "rogue", {"psum"}, {"all_reduce", "all_gather"}
    )
    assert codes(rep) == ["TPS006"]
    assert "all_gather" in rep.findings[0].message


def test_tps006_declared_collectives_negative():
    rep = SP.reconcile_hlo_census(
        "stats", {"psum", "ppermute"},
        {"all_reduce", "collective_permute"},
    )
    assert codes(rep) == []


def test_hlo_kind_parsing_both_spellings():
    assert SP.hlo_collective_kinds("stablehlo.all_reduce ...") == \
        {"all_reduce"}
    assert SP.hlo_collective_kinds("%x = all-gather(...)") == {"all_gather"}


def test_jaxpr_collectives_helper():
    import jax

    from jax import shard_map
    from jax.sharding import AbstractMesh
    from jax.sharding import PartitionSpec as P

    mesh = AbstractMesh((4, 1), ("data", "model"))

    @partial(shard_map, mesh=mesh, in_specs=(P("data", None),),
             out_specs=P(), check_vma=False)
    def body(xs):
        return jax.lax.psum(xs.sum(axis=0), "data")

    closed = jax.jit(body).trace(
        jax.ShapeDtypeStruct((16, 3), np.float32)
    ).jaxpr
    cen = SP.jaxpr_collectives(closed)
    assert cen == [{"primitive": "psum", "axes": "data", "count": 1}]


# ==========================================================================
# dynamic leg: the collective tape + reconciler (TPS008)
# ==========================================================================
def _mesh8():
    from transmogrifai_tpu.parallel import make_mesh

    return make_mesh(n_data=8, n_model=1)


def test_zero_wrappers_when_tracing_off():
    G.set_tracing(False)
    G.reset_tapes()
    from transmogrifai_tpu.parallel import pcolumn_stats

    pcolumn_stats(np.ones((16, 3), np.float32), _mesh8())
    assert G.collective_tapes()["hosts"] == {}  # nothing recorded


def test_tapes_identical_across_hosts(taped, monkeypatch, rng):
    monkeypatch.setenv("TPTPU_SIM_HOSTS", "4")
    from transmogrifai_tpu.parallel import (
        pcolumn_stats,
        psegment_reduce,
        pxtx,
        ring_gram,
    )

    mesh = _mesh8()
    x = rng.normal(size=(32, 4)).astype(np.float32)
    pcolumn_stats(x, mesh)
    pxtx(x, mesh)
    ring_gram(x, mesh)
    psegment_reduce(
        np.ones(32, np.float32), np.zeros(32, np.int32), 2, mesh
    )
    tapes = G.collective_tapes()
    assert sorted(tapes["hosts"]) == ["0", "1", "2", "3"]
    ref = tapes["hosts"]["0"]
    assert [n for _s, n in ref] == [
        "pcolumn_stats", "pxtx", "ring_gram", "psegment_reduce"
    ]
    assert all(tapes["hosts"][h] == ref for h in "123")
    rep = SP.reconcile_collective_orders(
        tapes, SP.seam_collective_census(
            [os.path.join(REPO, p) for p in SP.DEFAULT_SPMD_PATHS],
            root=REPO,
        )
    )
    recon = rep.data["reconciliation"]
    assert recon["tapesAgree"] and recon["explained"], [
        f.render() for f in rep.findings
    ]
    assert recon["tapeLength"] == 4


def test_seeded_failover_freezes_lost_tape_as_prefix(taped, monkeypatch, rng):
    """The acceptance scenario: a host dies MID-SWEEP (injected during a
    collective), the controller fails over, survivors keep issuing — the
    lost host's tape must be a strict prefix and the reconciler stays
    clean."""
    monkeypatch.setenv("TPTPU_SIM_HOSTS", "4")
    from transmogrifai_tpu.parallel import pcolumn_stats, pxtx
    from transmogrifai_tpu.resilience import faults
    from transmogrifai_tpu.resilience.distributed import (
        FailoverController,
        HeartbeatConfig,
        HostLostError,
        installed_controller,
    )

    mesh = _mesh8()
    x = rng.normal(size=(32, 4)).astype(np.float32)
    ctrl = FailoverController(
        n_hosts=4, config=HeartbeatConfig(clock=lambda: 0.0)
    ).bind(mesh)
    plan = faults.FaultPlan().fail_host(1, collective="pxtx")
    with faults.installed(plan), installed_controller(ctrl):
        pcolumn_stats(x, mesh)
        degraded = mesh
        with pytest.raises(HostLostError) as exc:
            pxtx(x, mesh)
        degraded = ctrl.failover(exc.value) or mesh
        pxtx(x, degraded)
        pcolumn_stats(x, degraded)
    tapes = G.collective_tapes()
    assert tapes["lost"] == [1]
    survivor = tapes["hosts"]["0"]
    lost = tapes["hosts"]["1"]
    assert len(survivor) == 3 and len(lost) == 1
    assert lost == survivor[: len(lost)]
    rep = SP.reconcile_collective_orders(tapes)
    recon = rep.data["reconciliation"]
    assert recon["tapesAgree"] and recon["lostHosts"] == [1]
    assert not rep.findings


def test_tps008_divergent_tape_positive(taped):
    tapes = {
        "nHosts": 2, "lost": [],
        "hosts": {
            "0": [[0, "pxtx"], [1, "pcolumn_stats"]],
            "1": [[0, "pcolumn_stats"], [1, "pxtx"]],
        },
    }
    rep = SP.reconcile_collective_orders(tapes)
    assert "TPS008" in codes(rep)
    assert not rep.data["reconciliation"]["tapesAgree"]


def test_tps008_unexplained_collective_positive():
    tapes = {
        "nHosts": 2, "lost": [],
        "hosts": {"0": [[0, "rogue_gather"]], "1": [[0, "rogue_gather"]]},
    }
    rep = SP.reconcile_collective_orders(tapes, {"pxtx": ["a.py:1"]})
    assert codes(rep) == ["TPS008"]
    assert "rogue_gather" in rep.findings[0].message


def test_tps008_diverged_before_failover_positive():
    tapes = {
        "nHosts": 2, "lost": [1],
        "hosts": {
            "0": [[0, "pxtx"], [1, "pcolumn_stats"]],
            "1": [[0, "phistogram"]],
        },
    }
    rep = SP.reconcile_collective_orders(tapes)
    assert codes(rep) == ["TPS008"]
    assert "BEFORE" in rep.findings[0].message


def test_guard_retries_record_each_issue(taped, monkeypatch, rng):
    """The recorder sits BELOW the CollectiveGuard's retry loop: a
    straggler retry re-issues the collective, and real transports
    re-issue too — the tape must show every issue on every live host."""
    monkeypatch.setenv("TPTPU_SIM_HOSTS", "4")
    from transmogrifai_tpu.parallel import pxtx
    from transmogrifai_tpu.resilience import faults
    from transmogrifai_tpu.resilience.distributed import (
        FailoverController,
        HeartbeatConfig,
        installed_controller,
    )

    mesh = _mesh8()
    x = rng.normal(size=(32, 4)).astype(np.float32)
    cfg = HeartbeatConfig(
        clock=lambda: 0.0, min_deadline=1.0, min_samples=0,
    )
    ctrl = FailoverController(n_hosts=4, config=cfg).bind(mesh)
    plan = faults.FaultPlan().straggle_collective(
        "pxtx", delay=100.0, times=1
    )
    with faults.installed(plan), installed_controller(ctrl):
        pxtx(x, mesh)
    assert ctrl.guard.counters["collectivesRetried"] == 1
    tape = G.collective_tapes()["hosts"]["0"]
    assert [n for _s, n in tape] == ["pxtx", "pxtx"]  # issue + retry
    rep = SP.reconcile_collective_orders(G.collective_tapes())
    assert rep.data["reconciliation"]["tapesAgree"]


def test_tape_dump_load_roundtrip(taped, tmp_path, monkeypatch, rng):
    monkeypatch.setenv("TPTPU_SIM_HOSTS", "2")
    from transmogrifai_tpu.parallel import pcolumn_stats

    pcolumn_stats(rng.normal(size=(16, 3)).astype(np.float32), _mesh8())
    out = str(tmp_path / "tapes.json")
    G.dump_tapes(out)
    loaded = G.load_tapes(out)
    assert loaded == json.loads(json.dumps(G.collective_tapes()))
    assert loaded["hosts"]["0"][0][1] == "pcolumn_stats"


def test_tracing_overhead_under_two_percent(rng):
    """Acceptance guard, the PR-6/PR-10 absolute-cost pattern: price one
    traced seam crossing with a micro-benchmark, multiply by the seam
    crossings a stats-heavy train performs, and require the attributed
    tracing cost under 2%% of a measured reduction sweep (with an
    absolute floor — 2%% of a warm-cache run smaller than one dict
    append is a bound about luck, not tracing)."""
    N = 20_000
    payload = {"v": 0}

    def fn(a):
        payload["v"] += 1
        return a

    G.set_tracing(False)
    t0 = time.perf_counter()
    for _ in range(N):
        G.guarded_collective("probe", fn, 1)
    raw_wall = time.perf_counter() - t0

    prev = G.set_tracing(True)
    G.reset_tapes()
    try:
        t0 = time.perf_counter()
        for _ in range(N):
            G.guarded_collective("probe", fn, 1)
        traced_wall = time.perf_counter() - t0
    finally:
        G.set_tracing(False)
        G.reset_tapes()
    per_op = max(0.0, (traced_wall - raw_wall) / N)

    # a stats-heavy layer crosses the seam ~8x (stats, gram, xtx, hist,
    # contingency, ring, segments, global); price 50 layers' worth
    # against a real measured sweep with tracing off
    from transmogrifai_tpu.parallel import pcolumn_stats, pxtx

    mesh = _mesh8()
    x = rng.normal(size=(256, 8)).astype(np.float32)
    pcolumn_stats(x, mesh)  # warm the kernels
    pxtx(x, mesh)
    t0 = time.perf_counter()
    for _ in range(25):
        pcolumn_stats(x, mesh)
        pxtx(x, mesh)
    loop_wall = time.perf_counter() - t0

    attributed = 50 * 8 * per_op
    assert attributed < max(0.02 * loop_wall, 0.025), (
        f"tracing would attribute {attributed * 1e3:.2f}ms onto a "
        f"{loop_wall * 1e3:.1f}ms sweep ({per_op * 1e6:.2f}us/crossing)"
    )


# ==========================================================================
# package summary + CLI gate
# ==========================================================================
def test_package_summary_shape():
    SP.package_summary.cache_clear()
    s = SP.package_summary()
    assert s["findings"] == 0 and s["codes"] == {}
    assert "pcolumn_stats" in s["seamCollectives"]
    assert s["shardMapKernels"] >= 11


def test_cli_gate_clean_against_committed_baseline(monkeypatch, capsys):
    from transmogrifai_tpu.cli import run_lint

    monkeypatch.chdir(REPO)
    rc = run_lint(
        [], "lint_baseline.json", None,
        spmd=True, spmd_baseline="spmd_baseline.json",
    )
    out = capsys.readouterr().out
    assert rc == 0
    assert "TPS" in out and "spmd finding(s)" in out


def test_cli_gate_exit3_on_missing_baseline(monkeypatch, capsys):
    from transmogrifai_tpu.cli import BASELINE_ERROR_EXIT, run_lint

    monkeypatch.chdir(REPO)
    rc = run_lint(
        [], None, None, spmd=True, spmd_baseline="no_such_baseline.json",
    )
    assert rc == BASELINE_ERROR_EXIT


def test_cli_gate_exit1_on_seeded_positive(monkeypatch, capsys, tmp_path):
    from transmogrifai_tpu.cli import run_lint

    bad = tmp_path / "parallel"
    bad.mkdir()
    (bad / "corpus.py").write_text(textwrap.dedent("""
        def f(x, mesh):
            if process_index() == 0:
                pcolumn_stats(x, mesh)
    """))
    monkeypatch.chdir(tmp_path)
    rc = run_lint([str(bad)], None, None, spmd=True, root=str(tmp_path))
    out = capsys.readouterr().out
    assert rc == 1
    assert "TPS001" in out


def test_write_baseline_then_gate_clean(monkeypatch, capsys, tmp_path):
    from transmogrifai_tpu.cli import run_lint

    bad = tmp_path / "parallel"
    bad.mkdir()
    (bad / "corpus.py").write_text(textwrap.dedent("""
        def f(x, mesh, lock):
            with lock:
                pxtx(x, mesh)
    """))
    monkeypatch.chdir(tmp_path)
    base = str(tmp_path / "spmd_baseline.json")
    rc = run_lint(
        [str(bad)], None, None,
        write_spmd_baseline=base, root=str(tmp_path),
    )
    assert rc == 0
    rc = run_lint(
        [str(bad)], None, None, spmd=True, spmd_baseline=base,
        root=str(tmp_path),
    )
    capsys.readouterr()
    assert rc == 0  # accepted by the freshly-written baseline


# ==========================================================================
# artifact surface: the collectiveAudit envelope
# ==========================================================================
def test_validate_reports_accepts_collective_audit():
    import sys

    sys.path.insert(0, REPO)
    from bench import validate_bench_report

    doc = {
        "n_devices": 8, "rc": 0, "ok": True, "skipped": False,
        "tail": "ok",
        "collectiveAudit": {
            "tpsCodes": [], "clean": True, "tapesAgree": True,
        },
    }
    assert validate_bench_report(doc) == []
    doc["collectiveAudit"] = {"tpsCodes": "oops"}
    assert validate_bench_report(doc) != []


def test_validate_reports_accepts_old_multichip_artifacts():
    import sys

    sys.path.insert(0, REPO)
    from bench import validate_bench_report

    # additive envelope: every COMMITTED artifact (pre-collectiveAudit)
    # must stay valid forever
    for name in sorted(os.listdir(REPO)):
        if name.startswith("MULTICHIP_") and name.endswith(".json"):
            with open(os.path.join(REPO, name)) as fh:
                assert validate_bench_report(json.load(fh)) == [], name


def test_summary_json_carries_spmd_summary(monkeypatch):
    # the workflow surface reads the cached package summary — assert the
    # wiring exists without paying a full train here (the train-level
    # shape is covered by the workflow suites)
    from transmogrifai_tpu.workflow import workflow as W

    src = open(W.__file__).read()
    assert 'analysis["spmd"]' in src
    s = SP.package_summary()
    assert set(s) == {
        "findings", "codes", "seamCollectives", "shardMapKernels"
    }
