"""Dispatch probe: a per-process record of which execution path each solver
actually selected (pallas kernel vs jnp twin, layout, CA depth).

Tests assert on it (the distributed solvers must hit the Pallas path when
eligible — VERDICT round 2 item 1), and `__graft_entry__.dryrun_multichip`
prints it so the driver artifact shows the dispatch decision."""

from __future__ import annotations

_RECORD: dict[str, str] = {}


def record(key: str, value: str) -> None:
    _RECORD[key] = value
    # stream the decision to the flight recorder (no-op when PAMPI_TELEMETRY
    # is unset) — dryrun artifacts and the run report show every dispatch
    from . import telemetry

    telemetry.emit("dispatch", key=key, value=value)


def last(key: str) -> str | None:
    return _RECORD.get(key)


def snapshot() -> dict[str, str]:
    return dict(_RECORD)


def reset() -> None:
    """Forget every decision: a driver that builds several runs in one
    process (chip_smoke.py) reads each run's snapshot on its own."""
    _RECORD.clear()


def probe_failed(what: str, exc: Exception) -> bool:
    """The kernel-family probes' shared failure path (ops/sor_pallas,
    sor3d_pallas, ns2d_fused, ns3d_fused, mg_fused). On a TPU backend a
    family that does not compile or run is an error, raised with the
    compiler's message: dropping every caller to the jnp chain would let
    a run "succeed" on the chip with no Pallas kernel in it. Off-TPU (a
    probe forced there) the family is reported unavailable: returns
    False after a warning."""
    import warnings

    import jax

    if jax.default_backend() == "tpu":
        raise RuntimeError(
            f"{what} failed its on-chip probe: {type(exc).__name__}: {exc}"
        ) from exc
    warnings.warn(f"{what} unavailable ({type(exc).__name__}); the "
                  "jnp path runs instead", stacklevel=3)
    return False


def resolve_solver(param, obstacles: bool, ragged: bool = False):
    """`tpu_solver auto` -> the measured-best solver for the run's
    structure (VERDICT r4 item 4: the solver-selection knowledge lived only
    in BASELINE.md prose — a user typing `mg` on a plain 4096² grid got the
    worst solver with no warning). Returns the param with a concrete
    solver; every model resolves through here FIRST, so the downstream
    solver checks (fft-refuses-obstacles, ragged-refuses-mg/fft) see only
    concrete values. The default stays `sor` (reference-trajectory parity);
    `auto` is opt-in. Decision matrix (BASELINE.md measured rows):

    - ragged distributed runs -> sor (mg/fft structurally refuse the
      pad-with-mask decomposition; the flag-masked SOR kernel composes)
    - obstacles -> mg (dense exact bottom, converged solves: 6.9x the
      capped-SOR step in 2-D at 2048x512, results/obsdist_mg2048.json;
      4.8x in 3-D at 96³, results/obstacle_mg3d_96.json — round 4's
      '3-D mg 9x slower' was a cross-session measurement artifact, the
      same-session decomposition shows 4 cycles x 2.3 ms/cycle)
    - plain constant-coefficient grids -> fft (exact DCT direct solve in
      one application: 6.9 vs 12.7 ms/step at dcavity 4096², 146x at
      NS-3D 128³)
    """
    if param.tpu_solver != "auto":
        return param
    if ragged:
        choice, why = "sor", "ragged decomposition (mg/fft unsupported)"
    elif obstacles:
        choice, why = "mg", "obstacles: dense-bottom MG, converged solves"
    else:
        choice, why = "fft", "plain grid: exact DCT direct solve"
    record("solver_auto", f"{choice} ({why})")
    return param.replace(tpu_solver=choice)


def resolve_fuse_phases(param, backend: str, dtype, probe, key: str,
                        why_not: str | None = None) -> bool:
    """`tpu_fuse_phases` -> whether this build dispatches the fused NS
    step-phase kernels (ops/ns2d_fused.py / ns3d_fused.py), extending the
    measured `auto` matrix to the phase chain: the round-5 north-star
    decomposition showed the ~40-launch jnp chain at 6.4 ms/step vs a
    ~0.8 ms HBM floor, so on TPU fusing is the measured-best choice
    wherever the kernels exist. Decision recorded under `key` (dryrun
    artifacts, tests assert on it).

    backend is the model's retry-protocol backend: "jnp" (the pallas-retry
    fallback) always disables fusion — that IS the retry's contract.
    `why_not` marks structurally ineligible builds (shard extents smaller
    than the deep halo — ragged, distributed-obstacle and 3-D-obstacle
    builds fuse since PR 2); `probe` is the kernel-family one-time smoke
    test ("on" skips it: the interpret-mode force used by parity tests and
    dryruns)."""
    import jax
    import jax.numpy as jnp

    knob = param.tpu_fuse_phases
    if knob not in ("auto", "on", "off"):
        raise ValueError(
            f"tpu_fuse_phases must be auto|on|off, got {knob!r}"
        )
    if knob == "off":
        record(key, "jnp (tpu_fuse_phases off)")
        return False
    if backend == "jnp":
        record(key, "jnp (retry fallback backend)")
        return False
    if why_not is not None:
        record(key, f"jnp ({why_not})")
        return False
    if knob == "on":
        record(key, "pallas_fused (forced)")
        return True
    if jax.default_backend() != "tpu":
        record(key, "jnp (no TPU)")
        return False
    if jnp.dtype(dtype).itemsize > 4:
        record(key, "jnp (dtype not Mosaic-lowerable)")
        return False
    if not probe():
        record(key, "jnp (probe failed)")
        return False
    record(key, "pallas_fused")
    return True


def resolve_mg_fused(knob: str, backend: str, dtype, key: str,
                     why_not: str | None = None, probe=None) -> bool:
    """`tpu_mg_fused` -> whether this MG build dispatches the fused
    V-cycle kernels (ops/mg_fused.py: the whole restrict→smooth→prolong
    chain as two dynamic-extent Pallas launches per cycle) instead of the
    per-level smoother-launch ladder. Decision recorded under `key`
    ("mg2d_fused", "mg3d_fused", "mg2d_obstacle_fused", ... — the factory
    re-records with the launch/level census once the kernels are built).

    Same contract as resolve_fuse_phases: "off" and the retry-fallback
    backend are hard offs; `why_not` marks structurally ineligible plans
    (single-level, VMEM-infeasible stacks, distributed builds — those
    get the coarse-aggregation seam instead); "on" forces dispatch before
    the backend checks (the interpret-mode force the parity tests and the
    CPU smoke drive use); `probe` is the kernel-family one-time smoke."""
    import jax
    import jax.numpy as jnp

    if knob not in ("auto", "on", "off"):
        raise ValueError(f"tpu_mg_fused must be auto|on|off, got {knob!r}")
    if knob == "off":
        record(key, "jnp (tpu_mg_fused off)")
        return False
    if backend == "jnp":
        record(key, "jnp (retry fallback backend)")
        return False
    if why_not is not None:
        record(key, f"jnp ({why_not})")
        return False
    if knob == "on":
        record(key, "pallas_fused_cycle (forced)")
        return True
    if jax.default_backend() != "tpu":
        record(key, "jnp (no TPU)")
        return False
    if jnp.dtype(dtype).itemsize > 4:
        record(key, "jnp (dtype not Mosaic-lowerable)")
        return False
    from ..ops.mg_fused import TPU_BLOCKER

    if TPU_BLOCKER:
        record(key, f"jnp ({TPU_BLOCKER})")
        return False
    if probe is not None and not probe():
        record(key, "jnp (probe failed)")
        return False
    record(key, "pallas_fused_cycle")
    return True


def resolve_overlap(param, key: str, why_not: str | None = None) -> bool:
    """`tpu_overlap` -> whether this dist build dispatches the
    double-buffered comm/compute-overlap schedule (parallel/overlap.py:
    interior/boundary PRE split, the step N+1 deep exchange posted after
    step N's POST) instead of the serial exchange-then-compute step.
    Decision recorded under `key` ("overlap_ns2d_dist" /
    "overlap_ns3d_dist" — the dryrun snapshot and tests assert on it).

    `why_not` marks structurally ineligible builds: the overlap schedule
    rides the fused deep-halo step (a jnp phase chain has per-phase
    exchanges that cannot be posted early without redundant halo
    recompute), and PAMPI_FAULTS field-fault builds keep the serial
    schedule (the in-step fault write would postdate the posted
    exchange). `off` must reproduce the serial schedule bitwise — the
    jaxpr-hash identity contract vs CONTRACTS.json."""
    import jax

    knob = param.tpu_overlap
    if knob not in ("auto", "on", "off"):
        raise ValueError(
            f"tpu_overlap must be auto|on|off, got {knob!r}"
        )
    if knob == "off":
        record(key, "serial (tpu_overlap off)")
        return False
    if why_not is not None:
        record(key, f"serial ({why_not})")
        return False
    if knob == "on":
        record(key, "overlap (forced)")
        return True
    if jax.default_backend() != "tpu":
        record(key, "serial (no TPU)")
        return False
    record(key, "overlap")
    return True


def resolve_overlap_restrict(param, key: str, plan,
                             why_not: str | None = None) -> bool:
    """`tpu_overlap_restrict` -> whether the overlapped PRE halves run
    GRID-RESTRICTED (parallel/overlap.region_plan: the interior half's
    Pallas grid bands over the interior core only, the boundary half
    over the OVERLAP_RIM bands) instead of two full write-gated sweeps.
    Decision recorded under `key` ("overlap_grid_<family>") with the
    swept-cell accounting, so the dryrun snapshot shows the ~2x-PRE-HBM
    question answered per build.

    `plan` is the region plan (None = the interior region is empty —
    boundary-everywhere, nothing to restrict). `auto` restricts exactly
    when the plan's summed banded cells beat the two full sweeps at this
    shard geometry; tiny shards keep the full halves (banding cannot
    win below a few row blocks). `on` forces the restricted plan
    (structural tests / smoke); `off` keeps the PR 8 full halves."""
    knob = param.tpu_overlap_restrict
    if knob not in ("auto", "on", "off"):
        raise ValueError(
            f"tpu_overlap_restrict must be auto|on|off, got {knob!r}"
        )
    if knob == "off":
        record(key, "full (tpu_overlap_restrict off)")
        return False
    if why_not is not None:
        record(key, f"full ({why_not})")
        return False
    if plan is None:
        record(key, "full (interior region empty: boundary-everywhere)")
        return False
    cells, full = plan["cells"], plan["cells_full"]
    if knob == "on":
        record(key, f"restricted (forced; {cells} vs {full} cells)")
        return True
    if plan["win"]:
        record(key, f"restricted (grid plan wins: {cells} vs {full} "
                    "cells)")
        return True
    record(key, f"full (banding cannot win at this shard geometry: "
                f"{cells} vs {full} cells)")
    return False


def resolve_class(key: str, grid, why_not: str | None) -> bool:
    """Shape-class eligibility of ONE request, recorded per bucket like
    `tpu_overlap`/`fleet_<bucket>` (ISSUE 15 satellite): `key` is
    `class_<bucket>` — the CLASS bucket's label when eligible, the
    exact-shape bucket's when not — `grid` the padded class rungs, and
    `why_not` the `fleet/shapeclass.class_eligible` refusal string. A
    tenant silently landing on the exact-shape bucket is then visible in
    the dispatch snapshot and the telemetry report. Returns whether the
    request rides a class bucket."""
    if why_not is not None:
        record(key, f"exact ({why_not})")
        return False
    record(key, f"class (padded {'x'.join(str(g) for g in grid)})")
    return True


def resolve_fleet(param, n_scenarios: int, dist: bool, key: str) -> str:
    """`tpu_fleet` -> how the fleet scheduler executes one bucket of
    same-signature scenario requests (pampi_tpu/fleet/scheduler.py).
    Returns "vmap" (the batched driver: one vmapped chunk advances every
    lane), "pjit" (whole-mesh per scenario, sequential, reusing the
    bucket's compiled program) or "solo" (every request its own solver —
    the historical path and the drift-check oracle). Decision recorded
    under `key` (one `fleet_<bucket>` key per bucket — the fleet summary
    and tests assert on it).

    `auto` policy: vmap for single-device buckets with more than one
    scenario (scenario-parallelism is embarrassingly parallel — the
    batch rides one program at near-100% efficiency); MESH — the fleet
    v2 middle mode: the vmapped chunk's scenario axis sharded across a
    device-mesh axis via NamedSharding — when a multi-device host can
    split the lanes evenly (a v5e-8 serves 8 single-chip lanes in true
    parallel, zero collectives between lanes); pjit for distributed
    buckets (vmapping a shard_map'ed chunk multiplies per-device live
    state by the lane count — whole-mesh sequential keeps the memory
    bound while still amortizing the compile) and for 1-scenario
    buckets (a size-1 batch axis buys nothing)."""
    import jax

    knob = param.tpu_fleet
    if knob not in ("auto", "vmap", "mesh", "pjit", "solo"):
        raise ValueError(
            f"tpu_fleet must be auto|vmap|mesh|pjit|solo, got {knob!r}"
        )
    if knob == "solo":
        record(key, "solo (tpu_fleet solo)")
        return "solo"
    if knob == "mesh":
        if dist:
            raise ValueError(
                "tpu_fleet mesh shards the SCENARIO axis — a "
                "distributed bucket already shards its grids; use "
                "auto/pjit")
        n_dev = len(jax.devices())
        if n_scenarios % max(1, n_dev) != 0:
            raise ValueError(
                f"tpu_fleet mesh needs lanes ({n_scenarios}) divisible "
                f"by devices ({n_dev})")
        record(key, f"mesh (forced; {n_scenarios} lanes over "
                    f"{n_dev} devices)")
        return "mesh"
    if knob in ("vmap", "pjit"):
        record(key, f"{knob} (forced)")
        return knob
    if dist:
        record(key, "pjit (dist bucket: whole-mesh per scenario)")
        return "pjit"
    if n_scenarios <= 1:
        record(key, "pjit (single-scenario bucket)")
        return "pjit"
    n_dev = len(jax.devices())
    if (n_dev > 1 and n_scenarios % n_dev == 0
            and jax.default_backend() != "cpu"):
        # real accelerators only: a CPU "mesh" is virtual host devices
        # sharing one core — sharding lanes across it serializes them
        # with partitioning overhead on top (measured ~10x the vmap
        # warm rate on this container), so auto keeps vmap there and
        # `tpu_fleet mesh` remains the forced/test mode
        record(key, f"mesh (scenario axis over {n_dev} devices, "
                    f"{n_scenarios // n_dev} lanes each)")
        return "mesh"
    record(key, f"vmap (same-trace bucket of {n_scenarios})")
    return "vmap"


def resolve_coord(param, key: str) -> str:
    """`tpu_coord` -> whether this run's drive loop rides the chunk-
    boundary agreement protocol (parallel/coordinator.py). Returns
    "multihost" (real cross-process allgather transport), "solo" (the
    1-rank coordinator — protocol path exercised without a launch) or
    "none" (the exact historical uncoordinated loop). Decision recorded
    under `key` ("coord_<family>") like every other knob.

    `auto` policy: coordinate exactly when there is more than one OS
    process — that is when a rank-local retry would desynchronize
    collectives (the PR 4 ban this protocol lifts). `off` restores the
    ban: multi-process runs get transient_budget=0 and any fault kills
    the job cleanly."""
    import jax

    knob = param.tpu_coord
    if knob not in ("auto", "on", "off"):
        raise ValueError(f"tpu_coord must be auto|on|off, got {knob!r}")
    if knob == "off":
        record(key, "uncoordinated (tpu_coord off)")
        return "none"
    nprocs = jax.process_count()
    if nprocs > 1:
        record(key, f"coordinated ({nprocs} processes)")
        return "multihost"
    if knob == "on":
        record(key, "coordinated (forced, 1 process)")
        return "solo"
    record(key, "uncoordinated (single process)")
    return "none"


_CHUNK_FUSE_K = 4  # the auto/forced K: divides both model chunks (64, 32)


def resolve_chunk_fuse(param, key: str, chunk: int,
                       why_not: str | None = None) -> int:
    """`tpu_chunk_fuse` -> the number of steps one trip of the chunk
    while-loop advances (ISSUE 17). K == 1 is EXACTLY the historical
    chunk (the builders keep the old body verbatim — the jaxpr-hash
    identity contract); K >= 2 wraps K gated steps in one `lax.scan`
    whose body traces ONCE, so the static launches-per-step is the
    K=1 launch count divided by K. Decision recorded under `key`
    ("<family>_chunk_fuse") in a form jaxprcheck parses ("K=<n>").

    `why_not` marks structurally ineligible builds (the overlapped
    schedule carries its own cross-step pipeline; K must divide the
    chunk so nt stays exact at every boundary). `auto` fuses on TPU
    only — off-TPU the historical trace is kept bitwise, so the
    committed CONTRACTS.json hashes stay valid."""
    import jax

    knob = param.tpu_chunk_fuse
    if knob == "off":
        record(key, "historical (tpu_chunk_fuse off)")
        return 1
    if knob not in ("auto", "on"):
        try:
            k = int(knob)
        except ValueError:
            raise ValueError(
                f"tpu_chunk_fuse must be auto|on|off|<int>, got {knob!r}"
            ) from None
        if k < 1:
            raise ValueError(
                f"tpu_chunk_fuse K must be >= 1, got {k}")
    else:
        k = _CHUNK_FUSE_K
    if why_not is not None:
        record(key, f"historical ({why_not})")
        return 1
    if k == 1:
        record(key, "historical (K=1)")
        return 1
    if chunk % k != 0:
        record(key, f"historical (K={k} does not divide chunk {chunk})")
        return 1
    if knob == "on":
        record(key, f"scan (K={k}, forced)")
        return k
    if knob == "auto" and jax.default_backend() != "tpu":
        record(key, "historical (no TPU)")
        return 1
    record(key, f"scan (K={k})")
    return k


def resolve_exchange_depth(param, key: str, k: int, tiers: dict,
                           axis_names, shard_extents, min_depth: int,
                           why_not: str | None = None) -> dict:
    """`tpu_exchange_depth` -> the per-tier depth map {axis: H} for the
    fused dist step's u/v exchanges (ISSUE 17): the mapped DCN axis
    ships ONE depth-H strip per H fused scan steps while every other
    axis keeps its fresh per-step exchange. Returns {} (no depth
    scheduling) unless the build is eligible; refusals are recorded
    under `key` ("<family>_exchange_depth") with the reason.

    This is a RELAXED-parity trade (bounded staleness on the slow-tier
    rim), so `auto` NEVER silently enables it — the map only arms on an
    explicit "axis=H". Eligibility: K-step fusion active with H | K,
    H >= the fused step's own deep-halo depth (`min_depth`), the axis
    present, declared dcn-tier and actually partitioned, and the shard
    extent on it >= H (the owned strip must cover the fat halo)."""
    knob = param.tpu_exchange_depth
    if knob in ("auto", "off"):
        record(key, f"per-step (tpu_exchange_depth {knob})")
        return {}
    try:
        ax, hs = knob.split("=")
        ax, h = ax.strip(), int(hs)
    except ValueError:
        raise ValueError(
            f"tpu_exchange_depth must be auto|off|<axis>=<H>, got "
            f"{knob!r}") from None
    if h < 1:
        raise ValueError(f"tpu_exchange_depth H must be >= 1, got {h}")
    if why_not is not None:
        record(key, f"per-step ({why_not})")
        return {}
    if k < 2:
        record(key, "per-step (needs tpu_chunk_fuse K >= 2)")
        return {}
    if k % h != 0:
        record(key, f"per-step (H={h} does not divide K={k})")
        return {}
    if h < min_depth:
        record(key, f"per-step (H={h} < deep halo {min_depth})")
        return {}
    if ax not in axis_names:
        record(key, f"per-step (no axis {ax!r} on this mesh)")
        return {}
    i = list(axis_names).index(ax)
    if shard_extents[i] < h:
        record(key, f"per-step (shard extent {shard_extents[i]} on "
                    f"{ax!r} < H={h})")
        return {}
    if tiers.get(ax, "ici") != "dcn":
        record(key, f"per-step (axis {ax!r} is not dcn-tier)")
        return {}
    record(key, f"depth ({ax}={h}: 1 {ax}-exchange per {h} steps)")
    return {ax: h}
