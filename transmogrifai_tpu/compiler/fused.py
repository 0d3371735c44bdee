"""Fused end-to-end on-device scoring graph (ROADMAP item 1).

The staged serving loop crosses the host↔device boundary per stage family:
vectorizers featurize on HOST into the fusion plane, the plane uploads,
the predictor dispatches, predictions download. For steady-state batches
the boundary is a large share of the batch, so this module compiles the
fitted serving plan — numeric coercion, pivot scatter, dense-plane
assembly, feature removal, and model predict — into ONE donated, bucketed
XLA dispatch:

* **ingest** stays host-side and shrinks to codecs: numeric value/mask
  arrays and the CSR text-interning kernels' code arrays
  (``ops.categorical._pivot_codes`` — string → vocab code, once per
  DISTINCT value). Those small arrays are the ONLY upload, counted as one
  host→device crossing on the runtime transfer census;
* **the fused program** rebuilds every member's block on device (impute +
  null-track, one-hot scatter from codes), concatenates the plane,
  applies the SanityChecker's keep-index gathers, and runs the model
  family's device predict — returning the predictor's CORE array (GLM
  margins/logits, tree margin stacks). The core is the only download
  (render); the host epilogue (`predictions_from_core`) is the same numpy
  code the staged path runs, so tree predictions are bit-identical and
  GLMs differ only by f32-on-device arithmetic (<= 1e-6);
* **explain lanes ride the same dispatch**: ``explain=k`` batches trace
  base core + ``[lanes × N, width]`` perturbation cores in one program
  (group column masks zero slices in-graph), so explain-enabled serving
  still crosses the boundary exactly twice per batch (ingest up, render
  down);
* **identity & banking**: programs are keyed by a structural fingerprint
  (member families, widths, predictor family) — model ARRAYS are traced
  arguments, so same-shaped models share executables — and dispatch rides
  ``utils.aot.aot_call`` (names ``fused_serve`` / ``fused_serve_explain``,
  listed in ``compiler.warmup.SCORE_PROGRAMS``), i.e. the same
  mesh-fingerprinted persistent bank and warmup DAG as every other
  serving program;
* **fail-soft**: any plan shape this module cannot prove fuseable raises
  :class:`Unfuseable` at build, and any dispatch-time error degrades the
  batch to the staged loop — both counted (``fusedFallbacks`` on
  compileStats, TPX008 in the plan audit) and evented. ``TPTPU_FUSED=0``
  opts out entirely.

The donated ingest argument is consumed by XLA on every path; run() is
written so the ingest name is never read after the dispatch — the TPX003
AST check in ``analysis/plan_audit.py`` scans this module for exactly
that bug class whenever a fused plan is audited.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import logging
import threading
from typing import Any, Callable, Sequence

import numpy as np

log = logging.getLogger(__name__)

__all__ = [
    "FusedServingProgram",
    "MemberPlan",
    "PredictorPlan",
    "Unfuseable",
    "build_fused_plan",
]


class Unfuseable(Exception):
    """The fitted plan cannot be compiled into the fused graph; the
    message names the first unfuseable stage/shape (surfaced as TPX008)."""


@dataclasses.dataclass
class MemberPlan:
    """One combiner member's device twin: host ``ingest`` (codecs /
    interning only), traced ``kernel`` rebuilding the member's dense block
    on device, and its fit-static ``params`` arrays. ``quant`` is the
    builder's hint to the quantized-plane pass (``build_fused_plan(...,
    quantize=True)``): ``kind="numeric"`` members carry fit ranges so the
    value upload can shrink to uint8 codes + an in-graph dequant, and
    ``kind="codes"`` members advertise their code range so the int32
    upload can narrow to int8/int16. ``None`` means the member always
    ships as built."""

    stage: Any
    width: int
    up_bytes_per_row: float
    ingest: Callable[[list], dict]          # host: cols -> np arrays
    kernel: Callable[[dict, dict], Any]     # traced: (ingest, params) -> block
    params: dict
    dummy: Callable[[int], dict]            # n -> ShapeDtype-correct zeros
    descriptor: str = ""
    quant: dict | None = None

    @property
    def output_name(self) -> str:
        return self.stage.output_name


@dataclasses.dataclass
class PredictorPlan:
    """The model family's device core: ``core(plane, params)`` traced into
    the fused program, ``epilogue(core_np)`` the HOST numpy tail shared
    with the staged path (``predictions_from_core``)."""

    stage: Any
    in_dim: int | None
    params: dict
    core: Callable[[Any, dict], Any]
    epilogue: Callable[[np.ndarray], tuple]
    descriptor: str = ""


class _Spec:
    """Hashable-by-identity static argument of the fused jit: the traced
    member kernels + predictor core. ``str()`` is the structural
    fingerprint so the persistent-bank key is stable across processes."""

    __slots__ = ("kernels", "core", "fingerprint")

    def __init__(self, kernels, core, fingerprint):
        self.kernels = kernels
        self.core = core
        self.fingerprint = fingerprint

    def __repr__(self) -> str:  # the aot_call static-key contribution
        return f"FusedSpec({self.fingerprint})"


# --------------------------------------------------------------------------
# the traced programs (module level so donating() can build jit twins)
# --------------------------------------------------------------------------
def _assemble_plane(ingest, params, spec):
    import jax.numpy as jnp

    blocks = [
        k(ing, p)
        for k, ing, p in zip(spec.kernels, ingest, params["members"])
    ]
    plane = blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks, axis=1)
    for idx in params["gathers"]:
        plane = plane[:, idx]
    return plane


def _fused_eval(ingest, params, *, spec):
    """ingest codecs -> plane -> predictor core. ONE dispatch."""
    plane = _assemble_plane(ingest, params, spec)
    return spec.core(plane, params["predictor"])


def _fused_eval_explain(ingest, params, masks, *, spec):
    """Base core + LOCO perturbation-lane cores in the SAME dispatch:
    lane g is the plane with the columns of ``masks[g]`` zeroed in-graph
    (``jnp.where`` — exact zeros, matching the staged sweep)."""
    import jax.numpy as jnp

    plane = _assemble_plane(ingest, params, spec)
    core = spec.core(plane, params["predictor"])
    lanes = masks.shape[0]
    n, width = plane.shape
    lane_planes = jnp.where(
        masks[:, None, :] > 0, jnp.float32(0.0), plane[None, :, :]
    ).reshape(lanes * n, width)
    lane_core = spec.core(lane_planes, params["predictor"])
    return core, lane_core


_JIT_LOCK = threading.Lock()
_JIT: dict[str, Any] = {}


def _plain_jit(name: str, fn) -> Any:
    import jax

    with _JIT_LOCK:
        got = _JIT.get(name)
        if got is None:
            got = _JIT[name] = jax.jit(  # tp: disable=TPL003 — cached
                fn, static_argnames=("spec",)
            )
    return got


# --------------------------------------------------------------------------
# plan compilation
# --------------------------------------------------------------------------
def build_fused_plan(
    plan: Sequence,
    raw_features,
    result_names: Sequence[str],
    fusion=None,
    quantize: bool = False,
) -> "FusedServingProgram":
    """Compile the fitted serving ``plan`` into a :class:`FusedServingProgram`
    or raise :class:`Unfuseable` naming the obstruction.

    Fuseable shape: host prefix stages feeding a single dense
    ``VectorsCombiner`` plane (every member exposing ``fused_member_spec``),
    an optional chain of ``FeatureRemovalModel`` gathers, and ONE terminal
    predictor exposing ``fused_predict_spec``. ``fusion`` (the closure's
    FusionPlanner) cross-checks learned widths when it has any.

    ``quantize=True`` rewrites eligible members onto the quantized plane
    (``featurize/quantize.py``): numeric value columns upload as uint8
    codes with a traced reps-table dequant ahead of the member kernel —
    bin-aligned against a tree predictor's ``fused_bin_thresholds`` (bit
    identical), affine over the fit ranges otherwise — and code-typed
    members narrow their int32 codes to the smallest integer dtype. A
    member that cannot be quantized keeps its f32 plane; the program
    still builds."""
    from ..models.base import PredictorModel
    from ..ops.combiner import VectorsCombiner
    from ..prep.derived_filter import FeatureRemovalModel

    plan = list(plan)
    predictors = [t for t in plan if isinstance(t, PredictorModel)]
    if len(predictors) != 1:
        raise Unfuseable(
            f"plan has {len(predictors)} predictor stages (need exactly 1)"
        )
    predictor = predictors[0]
    if plan[-1] is not predictor:
        raise Unfuseable("predictor is not the terminal stage of the plan")

    by_output = {t.output_name: t for t in plan}
    chain: list = []
    cur = by_output.get(predictor.input_names[-1]) if predictor.input_names \
        else None
    while isinstance(cur, FeatureRemovalModel):
        chain.append(cur)
        cur = by_output.get(cur.input_names[-1])
    if not isinstance(cur, VectorsCombiner):
        raise Unfuseable(
            "predictor feature plane is not a VectorsCombiner output "
            f"(found {type(cur).__name__})"
        )
    combiner = cur
    chain.reverse()

    members: list[MemberPlan] = []
    for nm in combiner.input_names:
        t = by_output.get(nm)
        spec_fn = getattr(t, "fused_member_spec", None)
        if t is None or spec_fn is None:
            raise Unfuseable(
                f"combiner member '{nm}' "
                f"({type(t).__name__ if t else 'raw'}) has no fused kernel"
            )
        members.append(spec_fn())  # may itself raise Unfuseable
    if not members:
        raise Unfuseable("combiner has no members")

    covered = {m.output_name for m in members}
    covered.add(combiner.output_name)
    covered.update(c.output_name for c in chain)
    covered.add(predictor.output_name)
    fused_stages = [t for t in plan if t.output_name in covered]
    prefix = [t for t in plan if t.output_name not in covered]
    for t in prefix:
        bad = [nm for nm in (t.input_names or ()) if nm in covered]
        if bad:
            raise Unfuseable(
                f"host stage '{t.output_name}' consumes fused "
                f"intermediate(s) {bad}"
            )
    for nm in result_names:
        if nm in covered and nm != predictor.output_name:
            raise Unfuseable(
                f"result feature '{nm}' is a fused intermediate — only the "
                "prediction leaves the device"
            )

    # widths: provable from the member specs alone; the FusionPlanner's
    # learned/primed widths cross-check them when present
    if fusion is not None:
        for m in members:
            learned = getattr(fusion, "widths", {}).get(
                getattr(m.stage, "uid", None)
            )
            if learned is not None and int(learned) != int(m.width):
                raise Unfuseable(
                    f"member '{m.output_name}' width {m.width} disagrees "
                    f"with the fusion planner's learned width {learned}"
                )
    plane_width = int(sum(m.width for m in members))
    gathers: list[np.ndarray] = []
    width = plane_width
    for c in chain:
        idx = c.fused_gather_indices()
        if idx is None:
            continue
        idx = np.asarray(idx, dtype=np.int32)
        if idx.size and (idx.min() < 0 or idx.max() >= width):
            raise Unfuseable(
                f"feature removal '{c.output_name}' keeps indices outside "
                f"[0, {width})"
            )
        gathers.append(idx)
        width = int(idx.size)

    pp_fn = getattr(predictor, "fused_predict_spec", None)
    if pp_fn is None:
        raise Unfuseable(
            f"model family {type(predictor).__name__} has no fused device "
            "predict"
        )
    pspec = pp_fn()  # may raise Unfuseable
    if pspec.in_dim is not None and int(pspec.in_dim) != width:
        raise Unfuseable(
            f"predictor expects width {pspec.in_dim}, fused plane is "
            f"{width}"
        )

    quant_plans: dict[str, Any] = {}
    quantized_members: list[str] = []
    if quantize:
        # map plane columns through the composed gather chain to the
        # predictor's input positions — a tree predictor's per-input
        # thresholds then give exact bin-aligned codes for the value
        # columns that survive the feature removals
        composed = np.arange(plane_width)
        for idx in gathers:
            composed = composed[idx]
        plane_to_pred = {int(p): k for k, p in enumerate(composed)}
        thr_fn = getattr(predictor, "fused_bin_thresholds", None)
        pred_thr = thr_fn() if thr_fn is not None else None
        out_members: list[MemberPlan] = []
        off = 0
        for m in members:
            kind = (m.quant or {}).get("kind")
            if kind == "numeric":
                new_m, qp = _quantize_numeric_member(
                    m, off, plane_to_pred, pred_thr
                )
                if qp is not None:
                    quant_plans[m.output_name] = qp
                    quantized_members.append(m.output_name)
                out_members.append(new_m)
            elif kind == "codes":
                new_m, changed = _shrink_codes_member(m)
                if changed:
                    quantized_members.append(m.output_name)
                out_members.append(new_m)
            else:
                out_members.append(m)
            off += m.width
        members = out_members

    descriptor = "|".join(
        [m.descriptor or f"{type(m.stage).__name__}:{m.width}"
         for m in members]
        + [f"gather:{g.size}" for g in gathers]
        + [pspec.descriptor or type(predictor).__name__]
    )
    fingerprint = hashlib.sha1(descriptor.encode()).hexdigest()[:16]
    return FusedServingProgram(
        members=members,
        prefix=prefix,
        fused_stages=fused_stages,
        combiner=combiner,
        chain=chain,
        predictor=predictor,
        pspec=pspec,
        gathers=tuple(gathers),
        plane_width=plane_width,
        width=width,
        fingerprint=fingerprint,
        quant_plans=quant_plans,
        quantized_members=tuple(quantized_members),
    )


def _quantize_numeric_member(member, offset, plane_to_pred, pred_thr):
    """Rewrite one numeric member onto uint8 codes + in-graph dequant.
    Per value column (plane col = offset + j·stride): bin-aligned codes
    when the gather chain maps it onto a predictor input with thresholds,
    affine over the fit range otherwise; a column the gathers DROP decodes
    to an exact constant (nothing downstream reads it). Returns
    ``(member, None)`` unchanged when any column has neither thresholds
    nor a fit range — partial members would split the upload for no win."""
    from ..featurize.quantize import ColumnQuant, QuantPlan, dequantize

    hint = member.quant
    n_feats = int(hint["n_feats"])
    track_nulls = bool(hint["track_nulls"])
    ranges = hint.get("ranges")
    stride = 2 if track_nulls else 1
    cols: list = []
    for j in range(n_feats):
        k = plane_to_pred.get(offset + j * stride)
        cq = None
        if k is not None and pred_thr is not None and k < pred_thr.shape[0]:
            cq = ColumnQuant.bins(pred_thr[k])
        if cq is None and ranges is not None:
            cq = ColumnQuant.affine(float(ranges[j][0]), float(ranges[j][1]))
        if cq is None and k is None:
            cq = ColumnQuant.affine(0.0, 0.0)
        if cq is None:
            return member, None
        cols.append(cq)
    qplan = QuantPlan(cols)
    orig_ingest = member.ingest
    orig_kernel = member.kernel
    orig_dummy = member.dummy

    def ingest(raw_cols: list) -> dict:
        d = orig_ingest(raw_cols)
        return {"codes": qplan.encode(d["vals"]), "mask": d["mask"]}

    def kernel(ing: dict, p: dict):
        vals = dequantize(ing["codes"], p["qreps"])
        return orig_kernel({"vals": vals, "mask": ing["mask"]}, p)

    def dummy(n: int) -> dict:
        d = orig_dummy(n)
        return {
            "codes": np.zeros(d["vals"].shape, dtype=np.uint8),
            "mask": d["mask"],
        }

    return dataclasses.replace(
        member,
        # 1 B code + 1 B mask per feature (was 4 + 1)
        up_bytes_per_row=float(n_feats * 2),
        ingest=ingest, kernel=kernel,
        params={**member.params, "qreps": qplan.reps_table()},
        dummy=dummy,
        descriptor=member.descriptor + ":" + qplan.descriptor(),
        quant=None,
    ), qplan


def _shrink_codes_member(member):
    """Narrow a code-typed member's int32 upload to the smallest integer
    dtype its advertised code range fits (the kernel widens back to int32
    before the original kernel runs, so the trace is unchanged past the
    cast). Returns ``(member, False)`` when int32 is already required."""
    import jax.numpy as jnp

    hint = member.quant
    lo = int(hint.get("min_code", 0))
    hi = int(hint["max_code"])
    if -128 <= lo and hi <= 127:
        dt = np.int8
    elif -32768 <= lo and hi <= 32767:
        dt = np.int16
    else:
        return member, False
    itemsize = int(np.dtype(dt).itemsize)
    codes_per_row = int(hint["codes_per_row"])
    orig_ingest = member.ingest
    orig_kernel = member.kernel
    orig_dummy = member.dummy

    def ingest(raw_cols: list) -> dict:
        d = orig_ingest(raw_cols)
        d["codes"] = d["codes"].astype(dt)
        return d

    def kernel(ing: dict, p: dict):
        ing = dict(ing)
        ing["codes"] = ing["codes"].astype(jnp.int32)
        return orig_kernel(ing, p)

    def dummy(n: int) -> dict:
        d = orig_dummy(n)
        d["codes"] = d["codes"].astype(dt)
        return d

    return dataclasses.replace(
        member,
        up_bytes_per_row=float(
            member.up_bytes_per_row - codes_per_row * (4 - itemsize)
        ),
        ingest=ingest, kernel=kernel, dummy=dummy,
        descriptor=member.descriptor + f":qi{8 * itemsize}",
        quant=None,
    ), True


class FusedServingProgram:
    """A compiled fused serving plan. Thread-safe: the only mutable state
    (device-resident params) is built once under a lock."""

    def __init__(
        self, members, prefix, fused_stages, combiner, chain, predictor,
        pspec, gathers, plane_width, width, fingerprint,
        quant_plans=None, quantized_members=(),
    ):
        self.members = members
        self.prefix = prefix
        self.fused_stages = fused_stages
        self.combiner = combiner
        self.chain = chain
        self.predictor = predictor
        self.pspec = pspec
        self.gathers = gathers
        self.plane_width = plane_width
        self.width = width
        self.fingerprint = fingerprint
        #: member output -> featurize.quantize.QuantPlan (numeric members
        #: rewritten onto uint8 codes); code-narrowed members appear in
        #: quantized_members without a plan
        self.quant_plans = dict(quant_plans or {})
        self.quantized_members = tuple(quantized_members)
        self.quantized = bool(self.quantized_members)
        self.covered = frozenset(t.output_name for t in fused_stages)
        self.up_bytes_per_row = float(
            sum(m.up_bytes_per_row for m in members)
        )
        self._spec = _Spec(
            kernels=tuple(m.kernel for m in members),
            core=pspec.core,
            fingerprint=fingerprint,
        )
        self._params_host = {
            "members": tuple(m.params for m in members),
            "gathers": self.gathers,
            "predictor": pspec.params,
        }
        self._params_dev = None
        self._params_lock = threading.Lock()
        # core shape per row via abstract evaluation — no compile, no data
        import jax

        aval = jax.eval_shape(
            functools.partial(_fused_eval, spec=self._spec),
            tuple(m.dummy(4) for m in members),
            self._params_host,
        )
        per_row = 1
        for d in aval.shape[1:]:
            per_row *= int(d)
        self.core_dtype = np.dtype(aval.dtype)
        self.down_bytes_per_row = float(per_row * self.core_dtype.itemsize)

    # ------------------------------------------------------------- reporting
    @property
    def static_widths(self) -> dict[str, int]:
        out = {m.output_name: int(m.width) for m in self.members}
        out[self.combiner.output_name] = self.plane_width
        w = self.plane_width
        gi = 0
        for c in self.chain:
            if c.fused_gather_indices() is not None:
                w = int(self.gathers[gi].size)
                gi += 1
            out[c.output_name] = w
        out[self.predictor.output_name] = 1
        return out

    @property
    def predictor_input_meta(self):
        """Fit-static VectorMetadata of the plane the predictor consumes
        (what explain groups by)."""
        from ..analysis.plan_audit import _meta_of

        producer = self.chain[-1] if self.chain else self.combiner
        return _meta_of(producer)

    def describe(self) -> dict[str, Any]:
        out = {
            "fingerprint": self.fingerprint,
            "members": [
                {"stage": m.stage.operation_name, "output": m.output_name,
                 "width": int(m.width)}
                for m in self.members
            ],
            "planeWidth": self.plane_width,
            "predictorWidth": self.width,
            "gathers": [int(g.size) for g in self.gathers],
            "upBytesPerRow": self.up_bytes_per_row,
            "downBytesPerRow": self.down_bytes_per_row,
            "coveredStages": sorted(self.covered),
            "hostPrefixStages": [t.output_name for t in self.prefix],
            "quantized": self.quantized,
        }
        if self.quantized:
            out["quantizedMembers"] = list(self.quantized_members)
            # per-column max reconstruction error ledger (0.0 for
            # bin-aligned / constant columns — predictions unaffected)
            out["quantError"] = {
                nm: qp.errors() for nm, qp in self.quant_plans.items()
            }
            out["quantPlans"] = {
                nm: qp.to_json() for nm, qp in self.quant_plans.items()
            }
        return out

    # ------------------------------------------------------------- dispatch
    def _device_params(self):
        import jax

        from ..telemetry import runlog as _runlog
        from ..telemetry import spans as _tspans

        with self._params_lock:
            if self._params_dev is None:
                # one-time model-constant upload (fills, weights, tree
                # stacks) — counted once, at program bring-up. Leaves
                # that are ALREADY device arrays (a tree model's _dev
                # cache) transfer nothing under device_put and must not
                # inflate the census
                nbytes = sum(
                    int(getattr(a, "nbytes", 0))
                    for a in jax.tree_util.tree_leaves(self._params_host)
                    if not isinstance(a, jax.Array)
                )
                t0 = _tspans.clock()
                self._params_dev = jax.device_put(self._params_host)
                _runlog.record_upload(nbytes, _tspans.clock() - t0)
            return self._params_dev

    def run(
        self, cols: dict, b: int, n: int, lane_masks: np.ndarray | None = None
    ) -> tuple[np.ndarray, np.ndarray | None, dict]:
        """Execute the fused program over already-built raw columns
        (``b`` bucketed rows, ``n`` real). Returns ``(core, lane_core,
        info)`` — host numpy arrays; callers apply the shared epilogue.

        Census contract: exactly ONE host→device crossing here (the
        donated ingest upload; model params counted once at bring-up) and
        ONE device→host crossing (the core download at render —
        ``down_bytes_per_row × n`` by the same real-rows convention as the
        staged census).
        """
        import jax

        from . import stats as cstats
        from ..telemetry import runlog as _runlog
        from ..telemetry import spans as _tspans

        params = self._device_params()
        ingest = tuple(
            m.ingest([cols[nm] for nm in m.stage.input_names])
            for m in self.members
        )
        # the ingest arrays' sizes are fully determined by the member
        # specs — the analytic per-row figure times the bucketed rows IS
        # sum(leaf.nbytes), without a per-batch pytree walk. Explain lane
        # masks upload with the ingest and count in the SAME crossing:
        # the census contract is one recorded h2d per batch, and the
        # masks are part of that ingest, not a second boundary trip
        up_bytes = int(round(self.up_bytes_per_row * b))
        lanes = 0
        masks = None
        if lane_masks is not None:
            lanes = int(lane_masks.shape[0])
            masks = np.asarray(lane_masks, dtype=np.float32)
            up_bytes += int(masks.nbytes)
        t0 = _tspans.clock()
        ingest = jax.device_put(ingest)
        if masks is not None:
            masks = jax.device_put(masks)
        _runlog.record_upload(up_bytes, _tspans.clock() - t0)
        t1 = _tspans.clock()
        if masks is None:
            core = self._dispatch_base(ingest, params)
            lane_core = None
        else:
            core, lane_core = self._dispatch_explain(ingest, params, masks)
        del ingest  # DONATED — consumed by the dispatch, never read again
        t2 = _tspans.clock()
        core = np.asarray(core)
        down_bytes = int(round(self.down_bytes_per_row * n))
        if lane_core is not None:
            lane_core = np.asarray(lane_core)
            down_bytes += int(round(self.down_bytes_per_row * n * lanes))
        dl = _tspans.clock() - t2
        _runlog.record_download(down_bytes, dl)
        cstats.stats().record_fused(lanes=lanes)
        return core, lane_core, {
            "upBytes": up_bytes,
            "downBytes": down_bytes,
            "dispatchSeconds": (t2 - t1) + dl,
            "lanes": lanes,
        }

    def build_error(self, b: int) -> Exception | None:
        """Lower and compile the base program for a ``b``-row bucket over
        placeholder ingest; the exception if the compiler refuses it,
        else None. A refusal is deterministic — the caller reports it
        once instead of retrying a dispatch that can never succeed."""
        ingest = tuple(m.dummy(b) for m in self.members)
        try:
            _plain_jit("fused_serve", _fused_eval).lower(
                ingest, self._params_host, spec=self._spec
            ).compile()
        except Exception as e:  # whatever tracing/lowering/XLA raised
            return e
        return None

    def _dispatch_base(self, ingest, params):
        """ONE donated dispatch; ``ingest`` is consumed — the TPX003 AST
        check scans this function for a read-after-donate."""
        from ..utils.aot import aot_call
        from .dispatch import donating

        call = donating(
            "fused_serve", _plain_jit("fused_serve", _fused_eval),
            (0,), static_argnames=("spec",),
        )
        statics = {"spec": self._spec}
        return aot_call("fused_serve", call, (ingest, params), statics)

    def _dispatch_explain(self, ingest, params, masks):
        """Base + explain lanes in ONE donated dispatch (see
        ``_dispatch_base`` for the donation contract)."""
        from ..utils.aot import aot_call
        from .dispatch import donating

        call = donating(
            "fused_serve_explain",
            _plain_jit("fused_serve_explain", _fused_eval_explain),
            (0,), static_argnames=("spec",),
        )
        statics = {"spec": self._spec}
        return aot_call("fused_serve_explain", call, (ingest, params, masks), statics)

    def epilogue(self, core: np.ndarray) -> tuple:
        """The HOST numpy tail mapping the downloaded core to
        ``(prediction, probability, raw)`` — the same
        ``predictions_from_core`` the staged path runs, pinning parity."""
        return self.pspec.epilogue(core)


# --------------------------------------------------------------------------
# member-plan builders (called by the stage classes' fused_member_spec)
# --------------------------------------------------------------------------
def numeric_member(
    stage, fills: np.ndarray, track_nulls: bool, ranges=None
) -> MemberPlan:
    """Impute + null-track on device. Host ingest = f32 values + validity
    mask; ``where(mask, value, fill)`` matches the staged
    ``_impute_block`` bit for bit once both land in the f32 plane.
    ``ranges`` (per-column fit-time [lo, hi]) rides the quant hint so a
    quantized build can shrink the value upload to uint8 codes."""
    fills = np.asarray(fills, dtype=np.float32)
    n_feats = int(fills.shape[0])
    width = n_feats * (2 if track_nulls else 1)

    def ingest(cols: list) -> dict:
        vals = np.stack(
            [np.asarray(c.values, dtype=np.float32) for c in cols], axis=1
        )
        mask = np.stack(
            [np.asarray(c.mask, dtype=bool) for c in cols], axis=1
        )
        return {"vals": vals, "mask": mask}

    def kernel(ing: dict, p: dict):
        import jax.numpy as jnp

        vals = jnp.where(ing["mask"], ing["vals"], p["fills"][None, :])
        if not track_nulls:
            return vals
        nulls = (~ing["mask"]).astype(jnp.float32)
        # staged layout interleaves [value, null] per feature
        return jnp.stack([vals, nulls], axis=2).reshape(
            vals.shape[0], width
        )

    def dummy(n: int) -> dict:
        return {
            "vals": np.zeros((n, n_feats), dtype=np.float32),
            "mask": np.zeros((n, n_feats), dtype=bool),
        }

    return MemberPlan(
        stage=stage, width=width,
        up_bytes_per_row=float(n_feats * (4 + 1)),
        ingest=ingest, kernel=kernel, params={"fills": fills}, dummy=dummy,
        descriptor=(
            f"numeric:{n_feats}:{'nulls' if track_nulls else 'plain'}"
        ),
        quant={
            "kind": "numeric", "n_feats": n_feats,
            "track_nulls": track_nulls, "ranges": ranges,
        },
    )


def passthrough_member(stage, n_feats: int) -> MemberPlan:
    """RealNN passthrough columns (no nulls possible)."""

    def ingest(cols: list) -> dict:
        return {
            "vals": np.stack(
                [np.asarray(c.values, dtype=np.float32) for c in cols],
                axis=1,
            )
        }

    def kernel(ing: dict, p: dict):
        return ing["vals"]

    def dummy(n: int) -> dict:
        return {"vals": np.zeros((n, n_feats), dtype=np.float32)}

    return MemberPlan(
        stage=stage, width=n_feats, up_bytes_per_row=float(4 * n_feats),
        ingest=ingest, kernel=kernel, params={}, dummy=dummy,
        descriptor=f"passthrough:{n_feats}",
    )


def onehot_member(stage, vocabs, track_nulls, clean_text) -> MemberPlan:
    """Pivot one-hot rebuilt as a device scatter over interned codes: the
    host CSR text-interning kernels resolve each DISTINCT raw value to a
    vocab code once (``_pivot_codes``: -1 null, -2 OTHER, >=0 vocab); the
    kernel maps codes to [vocab..., OTHER(, null)] columns exactly as the
    staged ``pivot_block``. Set-valued pivots (member counts > 1) are not
    fuseable — the caller's build raises before constructing this."""
    from ..ops.categorical import _pivot_codes

    widths = [
        len(v) + 1 + (1 if track_nulls else 0) for v in vocabs
    ]
    indexes = [{v: i for i, v in enumerate(vocab)} for vocab in vocabs]
    total = int(sum(widths))
    n_feats = len(vocabs)

    def ingest(cols: list) -> dict:
        from ..types.columns import TextColumn

        codes = np.empty((len(cols[0]), n_feats), dtype=np.int32)
        for j, (c, index) in enumerate(zip(cols, indexes)):
            if not isinstance(c, TextColumn):
                raise Unfuseable(
                    f"pivot member expected a text column, got "
                    f"{type(c).__name__}"
                )
            codes[:, j] = _pivot_codes(c.to_list(), index, clean_text)
        return {"codes": codes}

    def kernel(ing: dict, p: dict):
        import jax.numpy as jnp

        blocks = []
        for j, vocab in enumerate(vocabs):
            w = widths[j]
            other_col = len(vocab)
            null_col = other_col + 1 if track_nulls else -1
            codes = ing["codes"][:, j]
            col_idx = jnp.where(
                codes >= 0, codes,
                jnp.where(codes == -2, other_col, null_col),
            )
            blocks.append(
                (col_idx[:, None] == jnp.arange(w)[None, :]).astype(
                    jnp.float32
                )
            )
        return blocks[0] if len(blocks) == 1 else jnp.concatenate(
            blocks, axis=1
        )

    def dummy(n: int) -> dict:
        return {"codes": np.zeros((n, n_feats), dtype=np.int32)}

    return MemberPlan(
        stage=stage, width=total, up_bytes_per_row=float(4 * n_feats),
        ingest=ingest, kernel=kernel, params={}, dummy=dummy,
        descriptor=(
            "onehot:" + ",".join(map(str, widths))
            + (":nulls" if track_nulls else "")
        ),
        quant={
            "kind": "codes", "min_code": -2,
            "max_code": max(len(v) for v in vocabs) - 1,
            "codes_per_row": n_feats,
        },
    )


def hashed_text_member(
    stage, methods, num_hashes: int, track_nulls: bool, binary_freq: bool,
    to_lowercase: bool, min_token_length: int, seed: int,
) -> MemberPlan:
    """HashingTF text planes rebuilt as a device scatter (leg of ROADMAP
    item 1 that previously raised :class:`Unfuseable` and forced text
    flows back to the staged loop). The host side stays a codec — tokenize
    + murmur3 yields at most ``TPTPU_TEXT_FUSED_TOKENS`` (default 16)
    DISTINCT hash buckets per row per slot as int32 codes with f32
    occurrence weights — and the kernel scatters them into the
    ``num_hashes``-wide block in-graph, exactly like the OneHot code
    path. Binary term frequency applies ``> 0`` after the scatter so
    duplicate-bucket collisions match the staged set semantics; rows with
    more distinct buckets than the cap raise at ingest, which the serving
    seam counts as a dispatch fallback (the batch degrades, the program
    stays). ``Pivot`` slots are not handled here — the SmartText wrapper
    composes those separately or refuses."""
    import os

    from ..ops import text as _text_ops

    hash_slots = [
        i for i, m in enumerate(methods) if m == _text_ops.HASH
    ]
    if not hash_slots:
        raise Unfuseable("smart-text member has no hashed slots")
    if any(m == _text_ops.PIVOT for m in methods):
        raise Unfuseable(
            "smart-text member mixes Pivot and Hash slots — not fuseable"
        )
    n_slots = len(methods)
    n_hash = len(hash_slots)
    k_cap = int(os.environ.get("TPTPU_TEXT_FUSED_TOKENS", "16"))
    widths = [
        (num_hashes if m == _text_ops.HASH else 0)
        + (1 if track_nulls else 0)
        for m in methods
    ]
    total = int(sum(widths))
    if total <= 0:
        raise Unfuseable("smart-text member has zero fused width")

    def _slot_codes(values, n: int):
        """One slot's (codes [n, k_cap] int32, weights [n, k_cap] f32,
        null flags [n] uint8). Sentinel code ``num_hashes`` routes to a
        dump column sliced off after the scatter."""
        from .. import native as _native
        from ..utils import text as _text_util

        texts, rows_idx = _text_ops._partition_nulls(values)
        nulls = np.ones(n, dtype=np.uint8)
        nulls[rows_idx] = 0
        coo = None
        if texts:
            coo = _native.tokenize_hash_coo(
                texts, rows_idx, num_hashes, seed=seed, binary=binary_freq,
                to_lowercase=to_lowercase, min_token_length=min_token_length,
                prefix="",
            )
        if coo is not None:
            rows, hcols = coo
            rows = np.asarray(rows, dtype=np.int64)
            hcols = np.asarray(hcols, dtype=np.int64)
        else:
            r_parts, c_parts = [], []
            for raw, row in zip(texts, rows_idx):
                toks = _text_util.tokenize(
                    raw, to_lowercase=to_lowercase,
                    min_token_length=min_token_length,
                )
                if not toks:
                    continue
                h = _native.murmur3_batch(toks, seed=seed)
                j = (h % np.uint32(num_hashes)).astype(np.int64)
                if binary_freq:
                    j = np.unique(j)
                r_parts.append(np.full(j.shape[0], row, dtype=np.int64))
                c_parts.append(j)
            rows = (
                np.concatenate(r_parts) if r_parts
                else np.zeros(0, dtype=np.int64)
            )
            hcols = (
                np.concatenate(c_parts) if c_parts
                else np.zeros(0, dtype=np.int64)
            )
        codes = np.full((n, k_cap), num_hashes, dtype=np.int32)
        weights = np.zeros((n, k_cap), dtype=np.float32)
        if rows.size:
            # collapse duplicate (row, bucket) pairs to one slot with an
            # occurrence count; rank-within-row via the sorted row runs
            pair = rows * np.int64(num_hashes) + hcols
            uniq, counts = np.unique(pair, return_counts=True)
            ur = uniq // np.int64(num_hashes)
            uc = uniq % np.int64(num_hashes)
            pos = np.arange(uniq.size) - np.searchsorted(ur, ur)
            k_max = int(pos.max()) + 1
            if k_max > k_cap:
                raise Unfuseable(
                    f"text row needs {k_max} distinct hash buckets "
                    f"(> TPTPU_TEXT_FUSED_TOKENS={k_cap})"
                )
            codes[ur, pos] = uc.astype(np.int32)
            weights[ur, pos] = counts.astype(np.float32)
        return codes, weights, nulls

    def ingest(cols: list) -> dict:
        from ..types.columns import TextColumn

        n = len(cols[0])
        raw = [
            c.values if isinstance(c, TextColumn) else c.to_list()
            for c in cols
        ]
        codes = np.empty((n, n_hash, k_cap), dtype=np.int32)
        weights = np.empty((n, n_hash, k_cap), dtype=np.float32)
        nulls = np.zeros((n, n_slots), dtype=np.uint8)
        hs = 0
        for s in range(n_slots):
            if methods[s] == _text_ops.HASH:
                codes[:, hs], weights[:, hs], nulls[:, s] = _slot_codes(
                    raw[s], n
                )
                hs += 1
            else:  # Ignore: null indicator only
                _, rows_idx = _text_ops._partition_nulls(raw[s])
                nulls[:, s] = 1
                nulls[rows_idx, s] = 0
        out = {"codes": codes, "weights": weights}
        if track_nulls:
            out["nulls"] = nulls
        return out

    def kernel(ing: dict, p: dict):
        import jax.numpy as jnp

        n = ing["codes"].shape[0]
        rows = jnp.arange(n)[:, None]
        blocks = []
        hs = 0
        for s in range(n_slots):
            if methods[s] == _text_ops.HASH:
                acc = jnp.zeros((n, num_hashes + 1), jnp.float32).at[
                    rows, ing["codes"][:, hs, :]
                ].add(ing["weights"][:, hs, :])
                block = acc[:, :num_hashes]
                if binary_freq:
                    block = (block > 0).astype(jnp.float32)
                blocks.append(block)
                hs += 1
            if track_nulls:
                blocks.append(ing["nulls"][:, s:s + 1].astype(jnp.float32))
        return blocks[0] if len(blocks) == 1 else jnp.concatenate(
            blocks, axis=1
        )

    def dummy(n: int) -> dict:
        out = {
            "codes": np.full((n, n_hash, k_cap), num_hashes, np.int32),
            "weights": np.zeros((n, n_hash, k_cap), np.float32),
        }
        if track_nulls:
            out["nulls"] = np.zeros((n, n_slots), np.uint8)
        return out

    return MemberPlan(
        stage=stage, width=total,
        up_bytes_per_row=float(
            n_hash * k_cap * 8 + (n_slots if track_nulls else 0)
        ),
        ingest=ingest, kernel=kernel, params={}, dummy=dummy,
        descriptor=(
            f"hashtext:{num_hashes}x{n_hash}:k{k_cap}"
            + (":bin" if binary_freq else "")
            + (":nulls" if track_nulls else "")
        ),
        quant={
            "kind": "codes", "min_code": 0, "max_code": num_hashes,
            "codes_per_row": n_hash * k_cap,
        },
    )


# --------------------------------------------------------------------------
# compiled-program contract audit (analysis/program.py, TPJ0xx)
# --------------------------------------------------------------------------
def _trace_members():
    """Synthetic member plans for auditing the fused BUILDERS without a
    fitted plan: one numeric member (3 features, null-tracked) + one
    pivot member (vocab of 3) — the two kernel families every fitted
    fused program composes. The fitted program itself is audited by
    ``analysis.program.audit_fused_program`` with its real params."""
    import types as _types

    num_stage = _types.SimpleNamespace(
        output_name="trace_num", input_names=("a", "b", "c"),
        operation_name="TraceNumeric", uid="trace_num",
    )
    oh_stage = _types.SimpleNamespace(
        output_name="trace_oh", input_names=("p",),
        operation_name="TraceOneHot", uid="trace_oh",
    )
    m1 = numeric_member(num_stage, np.zeros(3, np.float32), True)
    m2 = onehot_member(oh_stage, [("x", "y", "z")], True, False)
    return m1, m2


def _trace_build(n: int, explain: bool = False):
    m1, m2 = _trace_members()
    width = int(m1.width + m2.width)
    spec = _Spec(
        kernels=(m1.kernel, m2.kernel),
        core=lambda plane, p: plane @ p["w"] + p["b"],
        fingerprint="trace",
    )
    params = {
        "members": (m1.params, m2.params),
        "gathers": (),
        "predictor": {
            "w": np.zeros((width,), np.float32), "b": np.float32(0.0),
        },
    }
    ingest = (m1.dummy(n), m2.dummy(n))
    if explain:
        masks = np.zeros((4, width), np.float32)
        return (ingest, params, masks), {"spec": spec}
    return (ingest, params), {"spec": spec}


def program_trace_specs():
    """The fused serving builders over representative synthetic members,
    bucketed on the BATCH axis (the scoring closure's pow2 row buckets)."""
    return [
        dict(
            name="fused_serve",
            fn=_fused_eval, base_fn=_fused_eval,
            build=lambda n: _trace_build(n),
            buckets=(8, 16),
            donate_argnums=(0,), static_argnames=("spec",),
            scoring=True,
        ),
        dict(
            name="fused_serve_explain",
            fn=_fused_eval_explain, base_fn=_fused_eval_explain,
            build=lambda n: _trace_build(n, explain=True),
            buckets=(8, 16),
            donate_argnums=(0,), static_argnames=("spec",),
            scoring=True,
        ),
    ]
