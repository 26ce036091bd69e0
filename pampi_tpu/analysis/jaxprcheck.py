"""Jaxpr contract checker: trace every solver family's chunk under the
dispatch matrix and statically assert the program-shape contracts.

What one trace proves (no device execution — `jax.make_jaxpr` only):

  launch counts   the chunk lowers to EXACTLY the number of `pallas_call`s
                  the `resolve_fuse_phases` / p-fold dispatch decision
                  implies (fused = 2, + 1 when the solve is folded onto
                  the shared padded layout, 0 on the jnp chain; fft
                  contributes none) — the launch-amortization property
                  the fused kernels exist for.
  host callbacks  no `*_callback` primitive unless a PAMPI_DEBUG /
                  PAMPI_VERBOSE / PAMPI_CHECK flag was armed at trace
                  time — a stray `jax.debug.print` in a hot loop costs a
                  host sync per step.
  dtype policy    every float intermediate is the compute dtype, the
                  time-accumulator dtype, or f32 (the in-band metrics
                  precision) — a silent promotion off the `precision.py`
                  contract doubles memory traffic before any test sees a
                  numeric difference.
  metrics arity   `initial_state()` arity == chunk invars/outvars, with
                  telemetry off AND on (the PR 3 contract every
                  measurement tool leans on).
  trace identity  the flag-off jaxpr hash matches the committed
                  `CONTRACTS.json` baseline (regenerate with
                  `tools/lint.py --update`); drift fails with a primitive
                  -histogram diff of the offending eqns. Hashes are
                  compared only when the baseline's environment (jax
                  version, x64, backend) matches — a toolchain bump
                  regenerates, it does not silently pass.

The config matrix spans the dispatch dimensions: jnp/fused ×
single-device/distributed × plain/obstacle/ragged × explicit/folded p
layout. Knobs are FORCED (never `auto`) so the expected launch counts are
platform-independent wherever the kernel family is (fft solves carry no
kernel; forced fusion and the forced checkerboard fold build the same
program on CPU and TPU); paths whose solve dispatch is genuinely
platform-dependent pin their count through the env-keyed baseline
instead.

Shared helpers (`count_prim`, `trace_chunk`, `assert_offpath_identity`)
are THE home of the jaxpr pins the test suite previously hand-rolled per
file (tests/test_telemetry.py, tests/test_faultinject.py,
tests/test_ns*_fused.py import from here).
"""

from __future__ import annotations

import hashlib
import inspect
import re
from dataclasses import dataclass

from .astlint import Violation

RULE_LAUNCH = "launch-count"
RULE_CALLBACK = "host-callback"
RULE_DTYPE = "dtype-promotion"
RULE_ARITY = "metrics-arity"
RULE_HASH = "trace-drift"

BASELINE_VERSION = 1


# ---------------------------------------------------------------------------
# jaxpr walkers (shared with the test suite)
# ---------------------------------------------------------------------------

def iter_eqns(jaxpr):
    """Every eqn of a jaxpr, recursing into sub-jaxprs (while/cond/pjit/
    pallas bodies)."""
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            vals = v if isinstance(v, (tuple, list)) else (v,)
            for x in vals:
                if type(x).__name__ == "ClosedJaxpr":
                    yield from iter_eqns(x.jaxpr)
                elif type(x).__name__ == "Jaxpr":
                    yield from iter_eqns(x)


def count_prim(jaxpr, name: str) -> int:
    """Occurrences of a primitive anywhere in the program (the pin the
    fused-kernel launch-count tests assert on)."""
    return sum(1 for e in iter_eqns(jaxpr) if e.primitive.name == name)


def prim_histogram(jaxpr) -> dict[str, int]:
    hist: dict[str, int] = {}
    for e in iter_eqns(jaxpr):
        hist[e.primitive.name] = hist.get(e.primitive.name, 0) + 1
    return hist


def host_callbacks(jaxpr) -> list[str]:
    """Primitive names of host-callback eqns (debug_print from
    jax.debug.print, debug_callback, io_callback, pure_callback, legacy
    outside_call)."""
    return [
        e.primitive.name
        for e in iter_eqns(jaxpr)
        if "callback" in e.primitive.name
        or e.primitive.name in ("debug_print", "outside_call")
    ]


def float_dtypes(jaxpr) -> set[str]:
    """Every floating dtype appearing on an eqn output anywhere.
    jnp.issubdtype, not np: the ml_dtypes extension floats (bfloat16)
    are NOT np.floating subtypes, so an np-based check is blind to
    exactly the dtypes the mixed-precision work introduces."""
    import jax.numpy as jnp

    out = set()
    for e in iter_eqns(jaxpr):
        for v in e.outvars:
            aval = getattr(v, "aval", None)
            dt = getattr(aval, "dtype", None)
            if dt is not None and jnp.issubdtype(dt, jnp.floating):
                out.add(str(dt))
    return out


# `<kernel> at <file>:<line>` source info printed with pallas_call params:
# the line number is SOURCE metadata, not program structure — an edit
# that merely shifts a kernel def down the file must not read as trace
# drift (found in round 20: every fused-config hash churned on a
# pure-addition kernel change with zero primitive deltas)
_SRC_INFO_RE = re.compile(r" at [^\s]+:\d+")


def jaxpr_hash(closed) -> str:
    """sha256 of the pretty-printed program with source-location
    metadata stripped — the trace-identity token. Stable within one
    (jax version, x64, backend) environment; the baseline stores that
    environment and hashes are only compared when it matches."""
    return hashlib.sha256(
        _SRC_INFO_RE.sub("", str(closed)).encode()).hexdigest()


def diff_histograms(old: dict, new: dict) -> list[str]:
    """Primitive-count deltas, the drift diagnostic: which eqns appeared/
    vanished."""
    lines = []
    for name in sorted(set(old) | set(new)):
        a, b = old.get(name, 0), new.get(name, 0)
        if a != b:
            lines.append(f"{name}: {a} -> {b} ({b - a:+d})")
    return lines


# ---------------------------------------------------------------------------
# chunk tracing
# ---------------------------------------------------------------------------

def chunk_callable(solver):
    """The traced chunk entry point, uniformly across families: the
    distributed solvers expose the shard_map'ed `_chunk_sm`; the
    single-device ones rebuild via `_build_chunk()` (same builder the
    production `_chunk_fn` wraps)."""
    if hasattr(solver, "_chunk_sm"):
        return solver._chunk_sm
    return solver._build_chunk()


def trace_chunk(solver):
    """ClosedJaxpr of the solver's chunk at its own initial_state arity."""
    import jax

    return jax.make_jaxpr(chunk_callable(solver))(*solver.initial_state())


def chunk_signature(solver, jaxpr=None) -> dict:
    """The contract-relevant shape of a chunk program."""
    jx = trace_chunk(solver) if jaxpr is None else jaxpr
    return {
        "outvars": len(jx.jaxpr.outvars),
        "invars": len(jx.jaxpr.invars),
        "pallas_calls": count_prim(jx.jaxpr, "pallas_call"),
        "callbacks": host_callbacks(jx.jaxpr),
        "state_arity": len(solver.initial_state()),
        "hash": jaxpr_hash(jx),
        "prims": prim_histogram(jx.jaxpr),
    }


def assert_offpath_identity(make_solver, expect_outvars: int = 5):
    """THE flag-off identity pin, shared by the telemetry and
    fault-injection suites: two independent builds trace byte-identically,
    with the expected plain arity and no sentinel ops. Returns
    (second solver, its ClosedJaxpr) for follow-on pins."""
    a = make_solver()
    jx_a = trace_chunk(a)
    b = make_solver()
    jx_b = trace_chunk(b)
    assert str(jx_a) == str(jx_b), "flag-off build is not deterministic"
    assert len(jx_a.jaxpr.outvars) == expect_outvars, (
        f"flag-off chunk arity {len(jx_a.jaxpr.outvars)} != "
        f"{expect_outvars}"
    )
    assert "is_finite" not in str(jx_a), (
        "flag-off chunk contains sentinel ops"
    )
    return b, jx_b


# ---------------------------------------------------------------------------
# the dispatch-matrix configs
# ---------------------------------------------------------------------------

@dataclass
class ChunkConfig:
    """One traced build of the dispatch matrix. The launch-count contract
    comes in three strengths:

    - `expected_pallas` set: a platform-independent static pin (fft
      solves, forced fusion).
    - `derive=True`: the expected count is DERIVED from the recorded
      dispatch decisions — 2 for a `pallas_fused` phase decision, +1 for
      a folded p layout, +1 for a solve whose dispatch record starts with
      "pallas" (`solve_key`), +1 for an overlapped schedule
      (`overlap_key`: the PRE kernel runs as interior + boundary
      halves). This is the per-decision contract: whatever the
      dispatcher chose, the trace must contain exactly the kernels that
      choice implies.
    - neither: only the env-keyed baseline pins the count (single-device
      solve paths that record no dispatch decision).

    `dispatch_keys` are recorded into the baseline and diffed on drift.

    `fleet` > 0 wraps the built solver in a `fleet/batch.BatchedSolver`
    of that many identical lanes: the traced chunk is the VMAPPED fleet
    program (ROADMAP item 3) — the same launch/census/resharding
    contracts then pin the batched trace (a vmapped chunk must lower to
    the same pallas launches and census the same collectives as the
    dispatch decisions imply, with zero resharding collectives)."""

    name: str
    family: str
    params: dict
    dims: tuple | None = None
    expected_pallas: int | None = None
    derive: bool = False
    phases_key: str = ""
    fold_key: str = ""
    solve_key: str = ""
    overlap_key: str = ""
    # the fused-V-cycle dispatch key (ISSUE 16): its record carries the
    # launch census verbatim — "pallas_*_cycle (launches=N, ...)" — and
    # the derived budget adds exactly that N (2 for the solo DOWN/UP
    # pair, 1 for the one-launch class cycle)
    mg_key: str = ""
    dispatch_keys: tuple = ()
    fleet: int = 0
    # serving-v2 batched variants (all imply `fleet`): mixed per-lane te
    # (the te-carried chunk), a shape-class padded batch (grid extents
    # per-lane data), the scenario axis sharded over the device mesh
    fleet_te: bool = False
    fleet_class: bool = False
    fleet_mesh: bool = False
    # precision-flow contract strength (analysis/preccheck.py):
    # `oracle` pins jnp f64 parity-oracle purity — zero sub-f64 float
    # compute anywhere in the trace; `advisory` traces the config and
    # pins its precision census in the baseline but REPORTS the
    # precision rule findings instead of gating on them (the forced-
    # bf16 scouts that price the future mixed-precision lanes)
    oracle: bool = False
    advisory: bool = False
    notes: str = ""

    def build(self):
        from ..utils.params import Parameter

        param = Parameter(**self.params)
        if self.dims is None:
            if self.family == "ns2d":
                from ..models.ns2d import NS2DSolver

                solver = NS2DSolver(param)
            else:
                from ..models.ns3d import NS3DSolver

                solver = NS3DSolver(param)
        else:
            from ..parallel.comm import CartComm

            comm = CartComm(ndims=len(self.dims), dims=self.dims,
                            tiers=param.tpu_mesh_tiers)
            if self.family == "ns2d_dist":
                from ..models.ns2d_dist import NS2DDistSolver

                solver = NS2DDistSolver(param, comm)
            else:
                from ..models.ns3d_dist import NS3DDistSolver

                solver = NS3DDistSolver(param, comm)
        if self.fleet:
            from ..fleet.batch import BatchedSolver

            params = [param] * self.fleet
            if self.fleet_te:
                # mixed end times: BatchedSolver auto-arms the per-lane
                # te carry (the te-arg chunk) — the serving-v2 trace
                params = [param.replace(te=param.te * (i + 1))
                          for i in range(self.fleet)]
            if self.fleet_class:
                from ..fleet.shapeclass import (
                    Class3DSolver,
                    ClassSolver,
                    class_grid,
                )

                if self.family == "ns3d":
                    grid = class_grid((param.imax, param.jmax,
                                       param.kmax))
                    solver = Class3DSolver(param, ic=grid[0], jc=grid[1],
                                           kc=grid[2])
                    other = param.replace(imax=param.imax + 2,
                                          jmax=param.jmax + 1)
                else:
                    grid = class_grid((param.imax, param.jmax))
                    solver = ClassSolver(param, ic=grid[0], jc=grid[1])
                    other = param.replace(imax=param.imax - 2,
                                          jmax=param.jmax - 4)
                if self.fleet >= 2:
                    # mixed GRIDS share the class compile: the second
                    # lane is a different grid riding the same program
                    params = [param, other] + [param] * (self.fleet - 2)
            mesh = None
            if self.fleet_mesh:
                import jax

                mesh = list(jax.devices())
            return BatchedSolver(solver, params,
                                 [f"lane{i}" for i in range(self.fleet)],
                                 family=self.family, mesh=mesh)
        return solver


_B2 = dict(name="dcavity", imax=16, jmax=16, re=10.0, te=0.02, tau=0.5,
           itermax=10, eps=1e-4, omg=1.7, gamma=0.9)
_B3 = dict(name="dcavity3d", imax=8, jmax=8, kmax=8, re=10.0, te=0.02,
           tau=0.5, itermax=8, eps=1e-4, omg=1.7, gamma=0.9)
_OBS = dict(name="canal_obstacle", imax=24, jmax=12, re=10.0, te=0.02,
            tau=0.5, itermax=10, eps=1e-3, omg=1.7, gamma=0.9,
            bcLeft=3, bcRight=3, obstacles="0.3,0.3,0.6,0.6")


def standard_configs() -> list[ChunkConfig]:
    """The dispatch matrix: jnp/fused × single/dist × plain/obstacle/
    ragged × explicit/folded p layout × serial/overlapped exchange
    schedule. Grids are 16²/8³ — each config is one trace, no compile."""
    return [
        ChunkConfig(
            "ns2d_jnp", "ns2d",
            dict(_B2, tpu_fuse_phases="off", tpu_solver="fft"),
            expected_pallas=0, dispatch_keys=("ns2d_phases",),
            oracle=True,
            notes="jnp phase chain + fft solve: zero kernels by contract"),
        ChunkConfig(
            "ns2d_fused_fft", "ns2d",
            dict(_B2, tpu_fuse_phases="on", tpu_solver="fft"),
            expected_pallas=2, dispatch_keys=("ns2d_phases",),
            notes="fused phases bracket an fft solve: PRE + POST only"),
        ChunkConfig(
            "ns2d_fused_fold", "ns2d",
            dict(_B2, tpu_fuse_phases="on", tpu_solver="sor",
                 tpu_sor_layout="checkerboard", tpu_sor_inner=1),
            derive=True, phases_key="ns2d_phases",
            fold_key="ns2d_p_layout",
            dispatch_keys=("ns2d_phases", "ns2d_p_layout"),
            notes="p-layout fold: PRE + tblock solve + POST, no layout "
                  "passes between them"),
        ChunkConfig(
            "ns2d_obstacle_fused", "ns2d",
            dict(_OBS, tpu_fuse_phases="on", tpu_solver="sor"),
            expected_pallas=None, dispatch_keys=("ns2d_phases",),
            notes="single-device obstacle solve records no dispatch "
                  "decision and is platform-dependent: baseline-pinned"),
        ChunkConfig(
            "ns2d_dist_jnp", "ns2d_dist",
            dict(_B2, tpu_fuse_phases="off", tpu_solver="sor",
                 tpu_sor_layout="checkerboard"),
            dims=(2, 2), derive=True, phases_key="ns2d_dist_phases",
            solve_key="ns2d_dist", overlap_key="overlap_ns2d_dist",
            dispatch_keys=("ns2d_dist_phases", "ns2d_dist",
                           "overlap_ns2d_dist"), oracle=True),
        ChunkConfig(
            "ns2d_dist_fused", "ns2d_dist",
            dict(_B2, tpu_fuse_phases="on", tpu_solver="sor",
                 tpu_sor_layout="checkerboard"),
            dims=(2, 2), derive=True, phases_key="ns2d_dist_phases",
            solve_key="ns2d_dist", overlap_key="overlap_ns2d_dist",
            dispatch_keys=("ns2d_dist_phases", "ns2d_dist",
                           "overlap_ns2d_dist"),
            notes="fused dist: PRE + POST per shard + whatever the solve "
                  "dispatch chose"),
        ChunkConfig(
            "ns2d_dist_overlap", "ns2d_dist",
            dict(_B2, tpu_fuse_phases="on", tpu_overlap="on",
                 tpu_solver="sor", tpu_sor_layout="checkerboard"),
            dims=(2, 2), derive=True, phases_key="ns2d_dist_phases",
            solve_key="ns2d_dist", overlap_key="overlap_ns2d_dist",
            dispatch_keys=("ns2d_dist_phases", "ns2d_dist",
                           "overlap_ns2d_dist"),
            notes="double-buffered overlap: interior + boundary PRE "
                  "halves, the step N+1 deep exchange posted after POST "
                  "(ppermutes feed only the loop carry)"),
        ChunkConfig(
            "ns2d_dist_overlap_split", "ns2d_dist",
            dict(_B2, tpu_fuse_phases="on", tpu_overlap="on",
                 tpu_overlap_restrict="on", tpu_solver="sor"),
            dims=(2, 2), derive=True, phases_key="ns2d_dist_phases",
            solve_key="ns2d_dist", overlap_key="overlap_ns2d_dist",
            dispatch_keys=("ns2d_dist_phases", "ns2d_dist",
                           "overlap_ns2d_dist", "overlap_grid_ns2d_dist",
                           "sweep_split_ns2d_dist"),
            notes="the full item-3 schedule: grid-restricted PRE halves "
                  "(forced — degenerate single-band at this shard size) "
                  "+ jnp RB-SOR with SPLIT sweeps (per-colour depth-1 "
                  "exchange posted behind the interior update)"),
        ChunkConfig(
            "ns2d_dist_tiered", "ns2d_dist",
            dict(_B2, tpu_fuse_phases="on", tpu_solver="sor",
                 tpu_sor_layout="checkerboard", tpu_mesh_tiers="i=dcn"),
            dims=(2, 2), derive=True, phases_key="ns2d_dist_phases",
            solve_key="ns2d_dist", overlap_key="overlap_ns2d_dist",
            dispatch_keys=("ns2d_dist_phases", "ns2d_dist",
                           "overlap_ns2d_dist"),
            notes="hierarchical mesh tiers: the i axis declared DCN — "
                  "its strips post first in every persistent exchange "
                  "and the census breaks traffic out per tier "
                  "(dcn/ici); same collectives, same bytes"),
        ChunkConfig(
            "ns2d_dist_ragged_fused", "ns2d_dist",
            dict(_B2, imax=18, jmax=18, tpu_fuse_phases="on",
                 tpu_solver="sor", tpu_sor_layout="checkerboard"),
            dims=(4, 2), derive=True, phases_key="ns2d_dist_phases",
            solve_key="ns2d_dist", overlap_key="overlap_ns2d_dist",
            dispatch_keys=("ns2d_dist_phases", "ns2d_dist",
                           "overlap_ns2d_dist"),
            notes="ragged shards ride the same kernels at uneven bounds"),
        ChunkConfig(
            "ns2d_dist_obstacle_fused", "ns2d_dist",
            dict(_OBS, tpu_fuse_phases="on", tpu_solver="sor",
                 tpu_sor_layout="checkerboard"),
            dims=(2, 2), derive=True, phases_key="ns2d_dist_phases",
            solve_key="obstacle_dist", overlap_key="overlap_ns2d_dist",
            dispatch_keys=("ns2d_dist_phases", "ns2d_dist",
                           "obstacle_dist", "overlap_ns2d_dist"),
            notes="dist obstacle flags compose via call-time flag blocks"),
        ChunkConfig(
            "ns3d_jnp", "ns3d",
            dict(_B3, tpu_fuse_phases="off", tpu_solver="fft"),
            expected_pallas=0, dispatch_keys=("ns3d_phases",),
            oracle=True),
        ChunkConfig(
            "ns3d_fused_fft", "ns3d",
            dict(_B3, tpu_fuse_phases="on", tpu_solver="fft"),
            expected_pallas=2, dispatch_keys=("ns3d_phases",)),
        ChunkConfig(
            "ns3d_dist_fused", "ns3d_dist",
            dict(_B3, tpu_fuse_phases="on", tpu_solver="sor"),
            dims=(2, 2, 2), derive=True, phases_key="ns3d_dist_phases",
            solve_key="ns3d_dist", overlap_key="overlap_ns3d_dist",
            dispatch_keys=("ns3d_dist_phases", "ns3d_dist",
                           "overlap_ns3d_dist")),
        ChunkConfig(
            "ns3d_dist_overlap", "ns3d_dist",
            dict(_B3, tpu_fuse_phases="on", tpu_overlap="on",
                 tpu_solver="sor"),
            dims=(2, 2, 2), derive=True, phases_key="ns3d_dist_phases",
            solve_key="ns3d_dist", overlap_key="overlap_ns3d_dist",
            dispatch_keys=("ns3d_dist_phases", "ns3d_dist",
                           "overlap_ns3d_dist"),
            notes="the 3-D overlapped schedule (4-cell shards: interior "
                  "region empty, boundary half covers the block — "
                  "degenerate but schedule-correct)"),
        # the scenario-fleet batched programs (ROADMAP item 3): the
        # vmapped chunk must keep the solo chunk's launch counts (vmap
        # adds a batch grid dim, never a second launch), census the same
        # collectives as its solo twin, and introduce zero resharding
        # collectives — the contracts that make vmap-batching a safe
        # serving default rather than a hope
        ChunkConfig(
            "ns2d_fleet_jnp", "ns2d",
            dict(_B2, tpu_fuse_phases="off", tpu_solver="fft"),
            expected_pallas=0, dispatch_keys=("ns2d_phases",), fleet=3,
            oracle=True,
            notes="3-lane vmapped jnp+fft chunk: still zero kernels"),
        ChunkConfig(
            "ns2d_fleet_fused", "ns2d",
            dict(_B2, tpu_fuse_phases="on", tpu_solver="fft"),
            expected_pallas=2, dispatch_keys=("ns2d_phases",), fleet=3,
            notes="3-lane vmapped fused chunk: PRE + POST exactly, the "
                  "batch rides the kernels' leading grid axis"),
        ChunkConfig(
            "ns2d_dist_fleet", "ns2d_dist",
            dict(_B2, tpu_fuse_phases="off", tpu_solver="sor",
                 tpu_sor_layout="checkerboard"),
            dims=(2, 2), derive=True, phases_key="ns2d_dist_phases",
            solve_key="ns2d_dist", overlap_key="overlap_ns2d_dist",
            dispatch_keys=("ns2d_dist_phases", "ns2d_dist",
                           "overlap_ns2d_dist"), fleet=2,
            notes="2-lane vmapped dist chunk: identical collective "
                  "counts to the solo dist trace (lanes ride the "
                  "messages, never add messages), named scopes intact"),
        # serving v2 (ISSUE 14): the continuous-batching / shape-class /
        # fleet-over-mesh programs — pure additions, the PR 9 fleet
        # configs above keep their baked-te traces (hashes unchanged)
        ChunkConfig(
            "ns2d_fleet_te", "ns2d",
            dict(_B2, tpu_fuse_phases="off", tpu_solver="fft"),
            expected_pallas=0, dispatch_keys=("ns2d_phases",), fleet=3,
            fleet_te=True,
            notes="mixed per-lane te: the end time rides the batched "
                  "carry as an (N,) vector and each lane's while-cond "
                  "reads its own — still zero kernels on jnp+fft"),
        ChunkConfig(
            "ns2d_fleet_class", "ns2d",
            dict(_B2, tpu_fuse_phases="off", tpu_solver="sor",
                 tpu_mesh="1"),
            expected_pallas=0, dispatch_keys=(), fleet=2,
            fleet_class=True,
            notes="shape-class padded batch (fleet/shapeclass.py): two "
                  "DIFFERENT grids ride one 16x16-class program whose "
                  "extents are per-lane data — all-jnp masked chain, "
                  "zero kernels, dead pad cells masked from every "
                  "reduction"),
        ChunkConfig(
            "ns2d_fleet_mesh", "ns2d",
            dict(_B2, tpu_fuse_phases="off", tpu_solver="fft"),
            expected_pallas=0, dispatch_keys=("ns2d_phases",), fleet=8,
            fleet_mesh=True,
            notes="fleet-over-mesh: 8 lanes NamedSharding-sharded over "
                  "the 8-device lint mesh — the traced program is the "
                  "identical vmapped chunk (shardings live at the jit "
                  "boundary), so the census must stay collective-free "
                  "(the zero-resharding serving contract)"),
        # serving v3 (ISSUE 15): the class chunk rides the PRODUCTION
        # kernels — fused PRE/POST at call-time extents plus the padded-
        # class tblock solve. Pure additions; the serving-v2 jnp class
        # config above keeps its byte-identical trace (hash unchanged).
        ChunkConfig(
            "ns2d_fleet_class_fused", "ns2d",
            dict(_B2, tpu_fuse_phases="on", tpu_solver="sor",
                 tpu_mesh="1"),
            derive=True, phases_key="ns2d_class_phases",
            solve_key="ns2d_class_solve",
            dispatch_keys=("ns2d_class_phases", "ns2d_class_solve"),
            fleet=2, fleet_class=True,
            notes="the fused class chunk: PRE + padded-class solve + "
                  "POST — exactly three launches per step, extents as "
                  "per-lane SMEM scalars, two DIFFERENT grids on one "
                  "compile"),
        ChunkConfig(
            "ns3d_fleet_class", "ns3d",
            dict(_B3, tpu_fuse_phases="off", tpu_solver="sor",
                 tpu_mesh="1"),
            expected_pallas=0, dispatch_keys=("ns3d_class_phases",),
            fleet=2, fleet_class=True,
            notes="3-D class rungs (serving v3): the masked jnp chain "
                  "over ragged3d's select machinery — zero kernels, "
                  "kmax joins the per-lane data"),
        ChunkConfig(
            "ns3d_fleet_class_fused", "ns3d",
            dict(_B3, tpu_fuse_phases="on", tpu_solver="sor",
                 tpu_mesh="1"),
            derive=True, phases_key="ns3d_class_phases",
            dispatch_keys=("ns3d_class_phases",),
            fleet=2, fleet_class=True,
            notes="the 3-D fused class chunk: dynamic-extent PRE + POST "
                  "around the masked jnp class solve — exactly two "
                  "launches per step"),
        # the fused V-cycle (ISSUE 16): one dynamic-extent cycle kernel
        # pair per cycle (DOWN: smooth+residual+restrict, UP: prolong+
        # neumann+post-smooth), the jnp bottom between them. Grids here
        # are the SMALLEST that yield a multi-level plan at the default
        # budgets (the fused cycle refuses single-level plans), so the
        # launches=2 census is exercised for real, not vacuously.
        ChunkConfig(
            "ns2d_mg_fused", "ns2d",
            dict(_B2, imax=512, jmax=256, tpu_fuse_phases="off",
                 tpu_solver="mg", tpu_mg_fused="on"),
            derive=True, phases_key="ns2d_phases", mg_key="mg2d_fused",
            dispatch_keys=("ns2d_phases", "mg2d_fused"),
            notes="the fused 2-D V-cycle: jnp phase chain + exactly the "
                  "DOWN/UP kernel pair the mg2d_fused census records — "
                  "512x256 is the smallest plain grid with a 2-level "
                  "plan at the default DCT-bottom budget"),
        ChunkConfig(
            "ns2d_obstacle_mg_fused", "ns2d",
            dict(_OBS, imax=64, jmax=64, tpu_fuse_phases="off",
                 tpu_solver="mg", tpu_mg_fused="on"),
            derive=True, phases_key="ns2d_phases",
            mg_key="mg2d_obstacle_fused",
            dispatch_keys=("ns2d_phases", "mg2d_obstacle_fused"),
            notes="the fused obstacle V-cycle: rediscretized "
                  "eps-coefficient operator per level, masks in the "
                  "kernel, dense exact bottom (64^2 -> 32^2 = exactly "
                  "the dense-bottom budget)"),
        ChunkConfig(
            "ns3d_mg_fused", "ns3d",
            dict(_B3, imax=64, jmax=64, kmax=64, tpu_fuse_phases="off",
                 tpu_solver="mg", tpu_mg_fused="on"),
            derive=True, phases_key="ns3d_phases", mg_key="mg3d_fused",
            dispatch_keys=("ns3d_phases", "mg3d_fused"),
            notes="the fused 3-D V-cycle: the same DOWN/UP pair over "
                  "volume planes (64^3 -> 32^3 two-level plan)"),
        ChunkConfig(
            "ns2d_dist_mg_agg", "ns2d_dist",
            dict(_B2, imax=256, jmax=258, tpu_fuse_phases="off",
                 tpu_solver="mg", tpu_mg_fused="on"),
            dims=(2, 2), expected_pallas=None,
            dispatch_keys=("ns2d_dist_phases", "mg_dist",
                           "mg_dist_fused", "mg_dist_agg"),
            notes="coarse-level aggregation below the shard floor: the "
                  "odd local extent (jl=129) stops the shard ladder at "
                  "one over-budget level, so tpu_mg_fused on continues "
                  "the hierarchy with the replicated global mini-V-cycle "
                  "(mg_dist_agg census; the gather is the declared "
                  "mg_aggregate boundary) — baseline-pinned"),
        ChunkConfig(
            "ns2d_fleet_class_mg", "ns2d",
            dict(_B2, tpu_fuse_phases="on", tpu_solver="mg",
                 tpu_mg_fused="on", tpu_mesh="1"),
            derive=True, phases_key="ns2d_class_phases",
            mg_key="mg_class_fused",
            dispatch_keys=("ns2d_class_phases", "mg_class_fused"),
            fleet=2, fleet_class=True,
            notes="the mg class lane: the whole V-cycle is ONE "
                  "whole-cycle kernel (in-kernel smoothed bottom), so "
                  "the chunk is jnp phases + exactly one launch — two "
                  "DIFFERENT grids ride the same class program via the "
                  "traced-scalar level plan"),
        # K-step fused chunks (ISSUE 17): tpu_chunk_fuse=<K> is forced,
        # so the scan-wrapped chunks trace on CPU. The launch contracts
        # are the SAME counts as the K=1 twins — the scan body traces
        # ONCE, which is the whole point: the static launches-per-step
        # is count/K, derived from the "scan (K=...)" dispatch record
        # and pinned < 3 in check_config.
        ChunkConfig(
            "ns2d_fused_fft_k4", "ns2d",
            dict(_B2, tpu_fuse_phases="on", tpu_solver="fft",
                 tpu_chunk_fuse="4"),
            expected_pallas=2,
            dispatch_keys=("ns2d_phases", "ns2d_chunk_fuse"),
            notes="K=4 scan chunk: still PRE + POST exactly — 0.5 "
                  "launches/step"),
        ChunkConfig(
            "ns3d_fused_fft_k4", "ns3d",
            dict(_B3, tpu_fuse_phases="on", tpu_solver="fft",
                 tpu_chunk_fuse="4"),
            expected_pallas=2,
            dispatch_keys=("ns3d_phases", "ns3d_chunk_fuse"),
            notes="the 3-D K=4 scan chunk: PRE + POST exactly"),
        ChunkConfig(
            "ns2d_dist_fused_k4", "ns2d_dist",
            dict(_B2, tpu_fuse_phases="on", tpu_solver="sor",
                 tpu_sor_layout="checkerboard", tpu_chunk_fuse="4"),
            dims=(2, 2), derive=True, phases_key="ns2d_dist_phases",
            solve_key="ns2d_dist", overlap_key="overlap_ns2d_dist",
            dispatch_keys=("ns2d_dist_phases", "ns2d_dist",
                           "overlap_ns2d_dist", "ns2d_dist_chunk_fuse"),
            notes="the K=4 dist scan keeps the K=1 launch budget"),
        ChunkConfig(
            "ns2d_dist_ragged_k4", "ns2d_dist",
            dict(_B2, imax=18, jmax=18, tpu_fuse_phases="on",
                 tpu_solver="sor", tpu_sor_layout="checkerboard",
                 tpu_chunk_fuse="4"),
            dims=(4, 2), derive=True, phases_key="ns2d_dist_phases",
            solve_key="ns2d_dist", overlap_key="overlap_ns2d_dist",
            dispatch_keys=("ns2d_dist_phases", "ns2d_dist",
                           "overlap_ns2d_dist", "ns2d_dist_chunk_fuse"),
            notes="ragged shards ride the K-scan at uneven bounds"),
        ChunkConfig(
            "ns2d_dist_obstacle_k4", "ns2d_dist",
            dict(_OBS, tpu_fuse_phases="on", tpu_solver="sor",
                 tpu_sor_layout="checkerboard", tpu_chunk_fuse="4"),
            dims=(2, 2), derive=True, phases_key="ns2d_dist_phases",
            solve_key="obstacle_dist", overlap_key="overlap_ns2d_dist",
            dispatch_keys=("ns2d_dist_phases", "ns2d_dist",
                           "obstacle_dist", "overlap_ns2d_dist",
                           "ns2d_dist_chunk_fuse"),
            notes="dist obstacle flag blocks compose under the K-scan"),
        ChunkConfig(
            "ns2d_dist_depth", "ns2d_dist",
            dict(_B2, tpu_fuse_phases="on", tpu_solver="sor",
                 tpu_sor_layout="checkerboard", tpu_mesh_tiers="i=dcn",
                 tpu_chunk_fuse="4", tpu_exchange_depth="i=4"),
            dims=(2, 2), derive=True, phases_key="ns2d_dist_phases",
            solve_key="ns2d_dist", overlap_key="overlap_ns2d_dist",
            dispatch_keys=("ns2d_dist_phases", "ns2d_dist",
                           "overlap_ns2d_dist", "ns2d_dist_chunk_fuse",
                           "ns2d_dist_exchange_depth"),
            notes="per-tier exchange depth: the dcn i axis captures ONE "
                  "depth-4 strip pair per 4-step block (commcheck "
                  "census pins 1 slow exchange per H steps; relaxed "
                  "parity, explicit opt-in)"),
        ChunkConfig(
            "ns3d_dist_fused_k4", "ns3d_dist",
            dict(_B3, tpu_fuse_phases="on", tpu_solver="sor",
                 tpu_chunk_fuse="4"),
            dims=(2, 2, 2), derive=True, phases_key="ns3d_dist_phases",
            solve_key="ns3d_dist", overlap_key="overlap_ns3d_dist",
            dispatch_keys=("ns3d_dist_phases", "ns3d_dist",
                           "overlap_ns3d_dist", "ns3d_dist_chunk_fuse"),
            notes="the 3-D K=4 dist scan keeps the K=1 launch budget"),
        # advisory bf16 scouts (ISSUE 20): tpu_dtype=bf16 FORCED onto
        # the NS2D/NS3D SOR paths before the mixed-precision knob
        # exists. Advisory = the precision rule findings (implicit
        # downcasts, f32 residual accumulations, the bf16 eps floor —
        # ~0.125 at 16², far above eps=1e-4, deliberately) are REPORTED
        # by the prec pass, not gated; the cast/reduction census IS
        # pinned in the baseline, so the future bf16 lanes land against
        # a priced contract, not a blank slate.
        ChunkConfig(
            "ns2d_bf16_sor", "ns2d",
            dict(_B2, tpu_fuse_phases="off", tpu_solver="sor",
                 tpu_dtype="bf16"),
            expected_pallas=0,
            dispatch_keys=("ns2d_phases", "ns2d_dtype"),
            advisory=True,
            notes="the jnp rb chain at forced bf16: zero kernels, the "
                  "residual accumulates at f32 (sor.py) and every "
                  "f64->bf16 entry cast shows up in the census"),
        ChunkConfig(
            "ns2d_bf16_fused", "ns2d",
            dict(_B2, tpu_fuse_phases="on", tpu_solver="sor",
                 tpu_sor_layout="checkerboard", tpu_dtype="bf16"),
            expected_pallas=None,
            dispatch_keys=("ns2d_phases", "ns2d_p_layout", "ns2d_dtype"),
            advisory=True,
            notes="the fused bf16 chunk (PRE + tblock solve + POST): "
                  "baseline-pinned launches, the kernels' f32 residual "
                  "accumulation (sor_pallas.py) joins the census"),
        ChunkConfig(
            "ns3d_bf16_sor", "ns3d",
            dict(_B3, tpu_fuse_phases="off", tpu_solver="sor",
                 tpu_dtype="bf16"),
            expected_pallas=0,
            dispatch_keys=("ns3d_phases", "ns3d_dtype"),
            advisory=True,
            notes="the 3-D jnp solve at forced bf16: the volume twin of "
                  "the 2-D scout (f32 residual home: ns3d.py)"),
    ]


def chunk_fuse_k(decisions: dict) -> int:
    """The K a traced chunk actually fused, read off its chunk_fuse
    dispatch record. Only a "scan (K=...)" record counts — every
    refusal spelling ("historical (...)") means the chunk advances one
    step per body and the per-step launch math divides by 1."""
    for dkey, dval in decisions.items():
        if not dkey.endswith("chunk_fuse"):
            continue
        sval = str(dval or "")
        km = re.search(r"scan \(K=(\d+)", sval)
        if km:
            return int(km.group(1))
    return 1


def expected_launches(cfg: ChunkConfig, decisions: dict):
    """The launch budget a build's recorded dispatch decisions imply (see
    ChunkConfig). Returns (count, how) — count None when only the
    baseline pins this config."""
    if cfg.expected_pallas is not None:
        return cfg.expected_pallas, "static"
    if not cfg.derive:
        return None, "baseline"
    n = 0
    if (decisions.get(cfg.phases_key) or "").startswith("pallas_fused"):
        n += 2
    if (decisions.get(cfg.fold_key) or "").startswith("folded"):
        n += 1
    if (decisions.get(cfg.solve_key) or "").startswith("pallas"):
        n += 1
    if (decisions.get(cfg.overlap_key) or "").startswith("overlap"):
        n += 1  # the PRE kernel runs twice: interior + boundary halves
    mg = decisions.get(cfg.mg_key) or ""
    if mg.startswith("pallas"):
        # the fused cycle's record IS the budget: "launches=N" names how
        # many pallas_calls one V-cycle costs (2 solo, 1 class lane)
        lm = re.search(r"launches=(\d+)", mg)
        n += int(lm.group(1)) if lm else 1
    return n, "derived"


# ---------------------------------------------------------------------------
# the shared trace matrix
# ---------------------------------------------------------------------------

@dataclass
class TracedConfig:
    """One built-and-traced config of the matrix: the solver, its chunk
    ClosedJaxpr, and the dispatch decisions recorded DURING the build
    (dispatch.last is a last-write register, so they must be captured
    before the next config builds). The jaxpr, comm and pallas passes all
    analyze this one object — tracing the matrix once per lint run, not
    once per pass."""

    cfg: ChunkConfig
    solver: object
    jaxpr: object
    decisions: dict


def trace_config(cfg: ChunkConfig) -> TracedConfig:
    from ..utils import dispatch

    solver = cfg.build()
    jx = trace_chunk(solver)
    return TracedConfig(
        cfg, solver, jx, {k: dispatch.last(k) for k in cfg.dispatch_keys})


def trace_matrix(configs=None) -> list[TracedConfig]:
    return [trace_config(cfg)
            for cfg in (standard_configs() if configs is None else configs)]


# ---------------------------------------------------------------------------
# the checks
# ---------------------------------------------------------------------------

def environment() -> dict:
    import jax

    return {
        "jax": jax.__version__,
        "x64": bool(jax.config.jax_enable_x64),
        "backend": jax.default_backend(),
        "devices": len(jax.devices()),
    }


def _anchor(family: str) -> tuple[str, int]:
    import importlib

    mod = importlib.import_module(f"pampi_tpu.models.{family}")
    try:
        return inspect.getsourcefile(mod), 1
    except TypeError:
        return f"pampi_tpu/models/{family}.py", 1


def _forbidden_floats(solver, jaxpr) -> set[str]:
    """Float dtypes outside the precision contract: compute dtype, the
    time-accumulator dtype, f32 (metrics / index math)."""
    import jax
    import jax.numpy as jnp

    allowed = {
        str(jnp.dtype(solver.dtype)),
        "float64" if jax.config.jax_enable_x64 else "float32",
        "float32",
    }
    return float_dtypes(jaxpr.jaxpr) - allowed


def check_config(cfg: ChunkConfig, baseline: dict | None,
                 env_matches: bool,
                 traced: TracedConfig | None = None) -> tuple[list, dict]:
    """Build + trace one config (or reuse a `trace_matrix` entry), check
    the live contracts, and compare against its baseline entry (hash only
    when the environment matches). Returns (violations, fresh baseline
    entry)."""
    path, line = _anchor(cfg.family)
    if traced is None:
        traced = trace_config(cfg)
    solver, jx, decisions = traced.solver, traced.jaxpr, traced.decisions
    sig = chunk_signature(solver, jx)
    entry = {
        "hash": sig["hash"],
        "outvars": sig["outvars"],
        "pallas_calls": sig["pallas_calls"],
        "eqns": sum(sig["prims"].values()),
        "prims": sig["prims"],
        "dispatch": decisions,
    }
    vs: list[Violation] = []

    def emit(rule, msg):
        vs.append(Violation(path, line, rule, f"{cfg.name}: {msg}"))

    # launch count per dispatch decision
    expected, how = expected_launches(cfg, decisions)
    entry["expected_pallas"] = expected
    if expected is not None and sig["pallas_calls"] != expected:
        emit(RULE_LAUNCH,
             f"chunk lowers to {sig['pallas_calls']} pallas_call(s), the "
             f"{how} contract says {expected} "
             f"(dispatch: {decisions}; {cfg.notes})")
    # the fused-cycle launch ceiling (ISSUE 16): any dispatch decision
    # advertising a per-cycle launch census must stay within the budget
    # the amortization argument rests on — 2 solo (DOWN + UP), 1 on the
    # class lane, 3 the hard ceiling
    for dkey, dval in decisions.items():
        lm = re.search(r"launches=(\d+)", str(dval or ""))
        if lm and int(lm.group(1)) > 3:
            emit(RULE_LAUNCH,
                 f"dispatch {dkey} = {dval!r} advertises "
                 f"{lm.group(1)} launches/cycle — the fused-cycle "
                 "contract pins <= 3")
    # launches-per-step (ISSUE 17): a K-fused chunk's scan body traces
    # ONCE, so the static pallas count covers K steps. The per-step
    # ratio is the serving-regime launch metric (bench.py threads it as
    # `launches_per_step`) and is pinned < 3 for any config that traced
    # with K >= 2 — a K-scan that still multiplies launches per step
    # has lost the whole point of fusing across the step boundary.
    kf = chunk_fuse_k(decisions)
    if kf >= 2:
        lps = sig["pallas_calls"] / kf
        entry["launches_per_step"] = lps
        if lps >= 3:
            emit(RULE_LAUNCH,
                 f"K={kf} chunk lowers to {sig['pallas_calls']} pallas "
                 f"launch(es) = {lps:.2f}/step — the K-fusion contract "
                 "pins < 3 launches per step")
    # host callbacks only behind armed flags
    from ..utils import flags as _flags

    if not (_flags.debug() or _flags.verbose() or _flags.check()):
        if sig["callbacks"]:
            emit(RULE_CALLBACK,
                 f"chunk contains host callbacks {sig['callbacks']} with "
                 "no PAMPI_DEBUG/PAMPI_VERBOSE/PAMPI_CHECK armed — each "
                 "costs a host sync per step")
    # dtype policy
    bad = _forbidden_floats(solver, jx)
    if bad:
        emit(RULE_DTYPE,
             f"float dtypes {sorted(bad)} off the precision contract "
             f"(compute dtype {solver.dtype.__name__ if hasattr(solver.dtype, '__name__') else solver.dtype})")
    # metrics arity: initial_state drives every tool's chunk call
    if sig["state_arity"] != sig["invars"] \
            or sig["state_arity"] != sig["outvars"]:
        emit(RULE_ARITY,
             f"initial_state() arity {sig['state_arity']} vs chunk "
             f"invars {sig['invars']} / outvars {sig['outvars']}")
    # baseline comparison — env-gated throughout: launch counts on
    # baseline-only paths depend on toolchain probe outcomes just like
    # the hash does (a mismatched jax reports environment drift once,
    # it does not fail per config)
    if baseline is not None and env_matches:
        if baseline.get("pallas_calls") != sig["pallas_calls"]:
            emit(RULE_LAUNCH,
                 f"pallas_call count drifted from the baseline: "
                 f"{baseline.get('pallas_calls')} -> "
                 f"{sig['pallas_calls']} (tools/lint.py --update if "
                 "intended)")
        if baseline.get("hash") != sig["hash"]:
            diff = diff_histograms(baseline.get("prims", {}), sig["prims"])
            base_disp = baseline.get("dispatch", {})
            ddiff = [f"{k}: {base_disp.get(k)!r} -> {v!r}"
                     for k, v in decisions.items()
                     if base_disp.get(k) != v]
            emit(RULE_HASH,
                 "flag-off trace drifted from CONTRACTS.json; offending "
                 "eqns (primitive-count deltas): "
                 + ("; ".join(diff) if diff else
                    "none — op parameters/ordering changed")
                 + (f"; dispatch: {'; '.join(ddiff)}" if ddiff else "")
                 + " (tools/lint.py --update if intended)")
    return vs, entry


def run(baseline: dict | None = None, configs=None,
        update: bool = False, traced=None) -> tuple[list[Violation], dict]:
    """Check every config. Returns (violations, fresh baseline dict) —
    the driver writes the latter on --update. A missing baseline (or a
    missing config entry) is only an error when not updating. `traced`
    (a `trace_matrix` result) short-circuits the per-config builds so
    several passes can share one matrix."""
    if traced is not None:
        configs = [t.cfg for t in traced]
    configs = standard_configs() if configs is None else configs
    by_name = {t.cfg.name: t for t in traced} if traced else {}
    env = environment()
    base_env = (baseline or {}).get("env")
    env_matches = base_env == env
    base_cfgs = (baseline or {}).get("configs", {})
    vs: list[Violation] = []
    fresh = {"version": BASELINE_VERSION, "env": env, "configs": {}}
    if baseline is not None and not env_matches and not update:
        vs.append(Violation(
            "CONTRACTS.json", 1, RULE_HASH,
            f"baseline environment {base_env} != current {env}: trace-"
            "hash identity not comparable (structural contracts still "
            "checked; regenerate the baseline on this toolchain with "
            "tools/lint.py --update)"))
    for cfg in configs:
        entry = base_cfgs.get(cfg.name)
        if entry is None and baseline is not None and not update:
            vs.append(Violation(
                "CONTRACTS.json", 1, RULE_HASH,
                f"{cfg.name}: no baseline entry (tools/lint.py --update)"))
        cfg_vs, fresh_entry = check_config(
            cfg, None if update else entry, env_matches,
            traced=by_name.get(cfg.name))
        vs += cfg_vs
        fresh["configs"][cfg.name] = fresh_entry
    return vs, fresh
