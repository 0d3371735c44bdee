"""Multi-host execution: the DCN-spanning distributed backend.

Reference substrate: Apache Spark driver⇄executor RPC + shuffle + XGBoost's
Rabit tracker (SURVEY.md §5.8). TPU-native replacement:

  * control plane — `jax.distributed.initialize` (one process per host),
    after which `jax.devices()` spans every host's chips;
  * data plane — a global `Mesh` whose leading axis factors (dcn, ici):
    collectives between chips on one host ride ICI, cross-host hops ride
    DCN. `shard_map`/`pjit` programs written against
    transmogrifai_tpu.parallel run unchanged — XLA routes `psum` over the
    hierarchy;
  * ingest — each host reads only its row block (`host_row_slice`), then
    `make_global_array` assembles a globally-sharded array from per-host
    locals without gathering anywhere.

The monoid discipline (every estimator = map rows → commutative reduce)
means nothing else changes for multi-host: the same `pcolumn_stats`/`pxtx`/
`phistogram` reductions are correct whatever the mesh spans — that is WHY
the reference's Spark shuffle maps onto plain psum (SURVEY.md §2.6).

Row layout contract (shared by every helper here): the global row count is
padded up to a multiple of the TOTAL device count; host h owns the padded
block [h·chunk, (h+1)·chunk) with chunk = padded // n_hosts; padding rows
live at the global tail and are excluded from statistics via a validity
column, exactly like parallel.reductions.
"""
from __future__ import annotations

import logging
import os
from functools import lru_cache, partial

import numpy as np

from .mesh import DATA_AXIS, MODEL_AXIS

log = logging.getLogger(__name__)

#: DCN (cross-host) mesh axis name — leading so cross-host traffic is the
#: outermost collective dimension
DCN_AXIS = "dcn"


def initialize_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    auto: bool = False,
) -> None:
    """Bring up the cross-host control plane (idempotent).

    Explicit arguments win; otherwise JAX_COORDINATOR_ADDRESS /
    JAX_NUM_PROCESSES / JAX_PROCESS_ID are read from the environment. With
    ``auto=True`` and nothing configured, `jax.distributed.initialize()` is
    called bare so Cloud TPU pod metadata auto-detection can kick in (do
    NOT set auto on single-machine setups — bare initialize errors there).
    Single-process configurations without ``auto`` no-op.
    """
    import jax

    if coordinator_address is None:
        coordinator_address = os.environ.get("JAX_COORDINATOR_ADDRESS")
    if num_processes is None:
        env = os.environ.get("JAX_NUM_PROCESSES")
        num_processes = int(env) if env else None
    if process_id is None:
        env = os.environ.get("JAX_PROCESS_ID")
        process_id = int(env) if env else None

    configured = coordinator_address is not None or (
        num_processes is not None and num_processes > 1
    )
    if not configured and not auto:
        return
    try:
        if configured:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
            )
        else:
            jax.distributed.initialize()  # Cloud TPU pod auto-detection
    except RuntimeError as e:  # already initialized
        if "already" not in str(e).lower():
            raise


def make_multihost_mesh(n_model: int = 1):
    """A ("dcn", "data", "model") mesh over every device of every host.

    Chips within one host form the ("data", "model") submesh (ICI); the
    leading "dcn" axis spans hosts. Use `dcn_data_spec()` to shard rows over
    BOTH host and chip axes; `psum` over ("dcn", "data") reduces globally.
    """
    import jax
    from jax.sharding import Mesh

    devices = np.asarray(jax.devices())
    n_hosts = jax.process_count()
    per_host = len(devices) // n_hosts
    if per_host * n_hosts != len(devices):
        raise RuntimeError(
            f"uneven device counts: {len(devices)} devices / {n_hosts} hosts"
        )
    n_data = per_host // n_model
    if n_data * n_model != per_host:
        raise ValueError(
            f"n_model={n_model} does not divide per-host device count {per_host}"
        )
    return Mesh(
        devices.reshape(n_hosts, n_data, n_model),
        (DCN_AXIS, DATA_AXIS, MODEL_AXIS),
    )


def dcn_data_spec(*trailing):
    """PartitionSpec sharding rows over (dcn, data) jointly."""
    from jax.sharding import PartitionSpec as P

    return P((DCN_AXIS, DATA_AXIS), *trailing)


def _total_devices(mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in mesh.axis_names]))


def padded_rows(num_rows: int, mesh) -> int:
    """num_rows rounded up to a multiple of the mesh's total device count
    (the global row axis must divide evenly for (dcn, data) sharding)."""
    t = _total_devices(mesh)
    return (num_rows + t - 1) // t * t


def host_row_slice(num_rows: int, mesh=None) -> slice:
    """The half-open range of REAL rows this host should read.

    Hosts own equal blocks of the PADDED row space (chunk = padded //
    n_hosts, consistent with `make_global_array`'s (dcn, data) sharding);
    the returned slice is that block clipped to the real rows — trailing
    hosts may own fewer (or zero) real rows, with the remainder of their
    block being padding.
    """
    import jax

    n_hosts = jax.process_count()
    pid = jax.process_index()
    if mesh is not None:
        chunk = padded_rows(num_rows, mesh) // n_hosts
    else:
        chunk = (num_rows + n_hosts - 1) // n_hosts
    return slice(min(pid * chunk, num_rows), min((pid + 1) * chunk, num_rows))


def read_host_block(
    fetch, num_rows: int, mesh=None, retry_policy=None
) -> np.ndarray:
    """This host's real-row block via ``fetch(slice)``, behind the PR-1
    ``RetryPolicy`` — parity with readers/streaming.py chunk fetches, which
    already retried while per-host ingest did not. Transient errors
    (flaky NFS, object-store hiccups, injected ``fail_chunk_read`` faults)
    back off and retry; fatal ones fail immediately."""
    from ..resilience import faults
    from ..resilience.retry import default_io_policy

    sl = host_row_slice(num_rows, mesh)
    token = f"host-block[{sl.start}:{sl.stop})"

    def attempt():
        plan = faults.active()
        if plan is not None:
            plan.on_stream_chunk(token)
        return fetch(sl)

    policy = retry_policy or default_io_policy()
    rows, attempts = policy.call(attempt)
    if attempts > 1:
        log.warning("host ingest %s fetched after %d attempts", token, attempts)
    return np.asarray(rows)


def ingest_global_array(fetch, num_rows: int, mesh, retry_policy=None):
    """The resilient per-host ingest path: ``host_row_slice`` → retried
    ``fetch`` → zero-pad to this host's block → ``make_global_array``.
    ``fetch(slice)`` returns this host's REAL rows; trailing hosts whose
    block is partly padding get the remainder zero-filled here (padding
    rows are excluded from statistics via the validity column, as
    everywhere in parallel.reductions)."""
    import jax

    if mesh is None:
        raise ValueError(
            "ingest_global_array requires a mesh (the global array's "
            "sharding); single-device callers can use read_host_block "
            "directly — their block is all the real rows"
        )
    local = read_host_block(fetch, num_rows, mesh, retry_policy)
    padded = padded_rows(num_rows, mesh)
    chunk = padded // jax.process_count()
    if local.shape[0] > chunk:
        raise ValueError(
            f"fetch returned {local.shape[0]} rows, more than this host's "
            f"{chunk}-row block"
        )
    if local.shape[0] < chunk:
        pad = np.zeros(
            (chunk - local.shape[0],) + local.shape[1:], dtype=local.dtype
        )
        local = np.concatenate([local, pad], axis=0)
    return make_global_array(local, mesh, padded)


def make_global_array(local_rows: np.ndarray, mesh, num_rows: int):
    """Assemble a globally row-sharded array from this host's row block.

    ``num_rows`` must be a multiple of the mesh's total device count (use
    `padded_rows`); ``local_rows`` must be this host's full block
    (num_rows // n_hosts rows). No host ever holds the global array.
    """
    import jax

    t = _total_devices(mesh)
    if num_rows % t != 0:
        raise ValueError(
            f"num_rows={num_rows} must be a multiple of the total device "
            f"count {t} — pad first (parallel.multihost.padded_rows)"
        )
    n_hosts = jax.process_count()
    chunk = num_rows // n_hosts
    if local_rows.shape[0] != chunk:
        raise ValueError(
            f"local block has {local_rows.shape[0]} rows, expected "
            f"{chunk} (= padded num_rows // n_hosts)"
        )
    return jax.make_array_from_process_local_data(
        jax.sharding.NamedSharding(
            mesh, dcn_data_spec(*([None] * (local_rows.ndim - 1)))
        ),
        local_rows,
        global_shape=(num_rows, *local_rows.shape[1:]),
    )


# jitted kernels are built once per mesh (see parallel.reductions — a fresh
# closure + jit per call would retrace and recompile on every stats call)
@lru_cache(maxsize=None)
def _global_stats_kernels(mesh):
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    axes = (DCN_AXIS, DATA_AXIS)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(dcn_data_spec(None),),
        out_specs=(P(), P()),
        check_vma=False,
    )
    def pass1(xs):
        v = xs[:, -1:]
        cnt = jax.lax.psum(v.sum(), axes)
        s = jax.lax.psum((xs[:, :-1] * v).sum(axis=0), axes)
        return cnt, s

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(dcn_data_spec(None), P()),
        out_specs=P(),
        check_vma=False,
    )
    def pass2(xs, mean):
        v = xs[:, -1:]
        c = (xs[:, :-1] - mean[None, :]) * v
        return jax.lax.psum((c * c).sum(axis=0), axes)

    return jax.jit(pass1), jax.jit(pass2)


def global_column_stats(x_local: np.ndarray, mesh, num_rows: int) -> dict:
    """Per-column count/mean/var across hosts: per-host row blocks in,
    global statistics out.

    ``x_local`` is this host's REAL rows (`host_row_slice(num_rows, mesh)`);
    padding to the sharded block size plus the validity column are handled
    here, and the variance uses the same two-pass centered-M2 scheme as
    `parallel.reductions.pcolumn_stats` (raw-moment variance cancels
    catastrophically in float32). Cross-host traffic is one psum of the
    per-column partials per pass — never the data. Runs behind the active
    CollectiveGuard when a FailoverController is installed.
    """
    from .guarded import guarded_collective

    return guarded_collective(
        "global_column_stats", _global_column_stats, x_local, mesh, num_rows
    )


def program_trace_specs():
    """Register the DCN-spanning stats kernels with the program auditor:
    traced over a device-free 2x4 ("dcn", "data") AbstractMesh — two
    hosts of four chips — so the TPJ IR lints and the TPS collective
    census inspect the exact cross-host programs without a pod."""
    import jax
    from jax.sharding import AbstractMesh

    mesh = AbstractMesh((2, 4, 1), (DCN_AXIS, DATA_AXIS, MODEL_AXIS))
    total = 1
    for name in mesh.axis_names:
        total *= int(mesh.shape[name])
    f = 4

    def mat(b, cols):
        return jax.ShapeDtypeStruct((b * total, cols), np.float32)

    pass1, pass2 = _global_stats_kernels(mesh)
    mean = jax.ShapeDtypeStruct((f,), np.float32)
    return [
        dict(
            name="global_stats_pass1", fn=pass1, buckets=(8, 16),
            build=lambda b: ((mat(b, f + 1),), {}),
        ),
        dict(
            name="global_stats_pass2", fn=pass2, buckets=(8, 16),
            build=lambda b: ((mat(b, f + 1), mean), {}),
        ),
    ]


def _global_column_stats(x_local: np.ndarray, mesh, num_rows: int) -> dict:
    import jax

    n_hosts = jax.process_count()
    padded = padded_rows(num_rows, mesh)
    chunk = padded // n_hosts
    x_local = np.asarray(x_local, dtype=np.float32)
    f = x_local.shape[1]
    block = np.zeros((chunk, f + 1), dtype=np.float32)
    block[: len(x_local), :f] = x_local
    block[: len(x_local), f] = 1.0  # validity — padding rows stay 0

    xg = make_global_array(block, mesh, padded)
    pass1, pass2 = _global_stats_kernels(mesh)
    cnt, s = pass1(xg)
    cnt_f = float(np.asarray(cnt))
    mean = np.asarray(s, dtype=np.float64) / max(cnt_f, 1.0)
    m2 = np.asarray(pass2(xg, mean.astype(np.float32)), dtype=np.float64)
    return {
        "count": cnt_f,
        "mean": mean,
        "var": m2 / max(cnt_f, 1.0),
    }
