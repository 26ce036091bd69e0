"""Run-time configuration: the `.par` key-value file format.

Capability parity with the reference's L2 config layer (`parameter.{h,c}` in
assignments 4/5/6; see /root/reference/assignment-6/src/parameter.c:15-126):
`#` starts a comment, first whitespace token is the key, second is the value,
keys are matched by *prefix* (the reference uses `strncmp(tok, key, strlen(key))`,
so a token `imaxFoo` still sets `imax` — we keep that tolerance), unknown keys
are silently ignored, and every known key has a default.

The parameter set is the union of all assignments:
  A4  {xlength ylength imax jmax itermax eps omg}
  A5 += {re tau gamma dt te gx gy name bcLeft/Right/Bottom/Top u_init v_init p_init}
  A6 += {zlength kmax gz bcFront bcBack w_init}
plus framework-only keys (prefixed `tpu_`) controlling the TPU execution:
  tpu_mesh   "PJxPI" / "PKxPJxPI" device-mesh shape, "auto" (factorize like
             MPI_Dims_create, ref assignment-5/ex5-nazifkar/src/solver.c:445),
             or "1" (force single-device)
  tpu_dtype  "float32" | "float64" | "bfloat16"
"""

from __future__ import annotations

import dataclasses
import sys
from dataclasses import dataclass


@dataclass
class Parameter:
    # geometry
    xlength: float = 1.0
    ylength: float = 1.0
    zlength: float = 1.0
    imax: int = 100
    jmax: int = 100
    kmax: int = 50
    # pressure iteration
    itermax: int = 1000
    eps: float = 0.0001
    omg: float = 1.7
    rho: float = 0.99  # framework-reserve key (not in the reference schema)
    # flow
    re: float = 100.0
    tau: float = 0.5
    gamma: float = 0.9
    dt: float = 0.02
    te: float = 10.0
    gx: float = 0.0
    gy: float = 0.0
    gz: float = 0.0
    name: str = "poisson"
    bcLeft: int = 1
    bcRight: int = 1
    bcBottom: int = 1
    bcTop: int = 1
    bcFront: int = 1
    bcBack: int = 1
    u_init: float = 0.0
    v_init: float = 0.0
    w_init: float = 0.0
    p_init: float = 0.0
    # obstacle geometry (ops/obstacle.py; the reference's canal is an empty
    # channel — this drives the flag-masked channel-with-obstacle config):
    # semicolon-separated rectangles "x0,y0,x1,y1;..." in physical coords
    obstacles: str = ""
    # framework-only (TPU execution controls; not in the reference)
    tpu_mesh: str = "auto"
    tpu_dtype: str = "float64"
    # temporal-blocking depth of the pallas SOR kernel: red-black iterations
    # fused per HBM sweep; convergence is checked every tpu_sor_inner
    # iterations, so a solve may overshoot by up to tpu_sor_inner-1
    # iterations (jnp paths always step singly). Default 4 keeps overshoot
    # small for CONVERGING solves (a 5-iteration solve at n=16 would run
    # 16); itermax-CAPPED workloads want 16 — measured 12.7 vs 21.3 ms/step
    # at dcavity 4096² (round-3 depth sweep, quarters kernel; bench.py uses
    # n_inner=16 for the same reason).
    tpu_sor_inner: int = 4
    # pallas SOR layout (single-device AND per-shard distributed):
    #   "auto"         quarter (2-D) / octant (3-D) decomposition when
    #                  eligible (even extents — ~3× the checkerboard kernel
    #                  at 4096² f32 on v5e; per-cell arithmetic
    #                  ulp-equivalent, ops/sor_quarters.py/sor_octants.py),
    #                  else checkerboard. The distributed solvers dispatch
    #                  the same kernels per shard between CA exchanges
    #                  (parallel/quarters_dist.py, octants_dist.py).
    #   "checkerboard" the masked kernel (per-cell trajectory numerically
    #                  IDENTICAL to the jnp reference path). In DISTRIBUTED
    #                  context it also FORCES the per-shard masked kernel
    #                  (ops/sor_obsdist; interpret off-TPU) for obstacle
    #                  and ragged runs — the dryrun/test force mode, since
    #                  that kernel IS the dist masked-checkerboard layout
    #   "quarters"/"octants"  force the compressed layout (error when
    #                  ineligible; off-TPU runs the interpret kernel/twin)
    tpu_sor_layout: str = "auto"
    # communication-avoiding depth of the DISTRIBUTED red-black solve
    # (parallel/stencil2d.ca_rb_iters): n exact iterations computed locally
    # per depth-2n halo exchange; convergence is checked every n iterations
    # (same overshoot semantics as tpu_sor_inner). n is clamped so 2n never
    # exceeds a shard extent; 1 keeps today's per-iteration trajectory
    # granularity while still halving the message count. The distributed
    # quarters/octants kernel paths use max(tpu_ca_inner, tpu_sor_inner).
    tpu_ca_inner: int = 1
    # pressure/elliptic solver:
    #   "sor"  the reference's algorithm (default; trajectory parity)
    #   "sor_lex"  the reference's LEXICOGRAPHIC sweep ordering as an
    #          oracle (NS-2D + Poisson): capped solves then follow the C
    #          binary's exact iterate sequence — the C-vs-framework field
    #          comparison mode (tools/northstar.py match4096); jnp-only
    #   "mg"   geometric multigrid V-cycles with an exact DCT bottom solve
    #          (ops/multigrid.py) — O(1) cycles; same eps-residual stopping
    #          contract, `it` counts cycles; single-device or on a mesh
    #   "fft"  direct DCT-diagonalization solve (ops/dctpoisson.py, MXU
    #          matmuls; collective matmuls + psum_scatter on a mesh) —
    #          exact in ONE application, `it` reports 1
    # fft does not support obstacle flag fields; mg does (2-D and 3-D,
    # single-device AND distributed — per-level rediscretized
    # eps-coefficient operators with an exact dense bottom)
    #   "auto" picks the measured-best solver for the run's structure
    #          (utils/dispatch.resolve_solver: plain -> fft; obstacles ->
    #          mg; ragged -> sor) and records the decision under the
    #          "solver_auto" dispatch key. The default stays "sor" for
    #          reference-trajectory parity.
    tpu_solver: str = "sor"
    # fused step-phase kernels (ops/ns2d_fused.py, ns3d_fused.py): the
    # non-solve NS timestep phases (BCs + special BC + computeFG + RHS +
    # adaptUV + CFL max) collapse from the ~40-launch jnp chain into two
    # Pallas HBM sweeps bracketing the pressure solve — the round-5
    # north-star decomposition measured that chain at 6.4 ms/step vs a
    # ~0.8 ms HBM floor at dcavity 4096² (results/northstar_dcavity4096.json).
    #   "auto" fuse when eligible: real TPU + Mosaic dtype + one-time probe
    #          + VMEM-feasible geometry; plain and (2-D single-device)
    #          obstacle runs fuse, distributed divisible plain runs fuse
    #          per shard, ragged / dist-obstacle / 3-D-obstacle keep the
    #          jnp chain (utils/dispatch.resolve_fuse_phases records every
    #          decision under the "*_phases" keys)
    #   "on"   force (interpret off-TPU — the parity-test mode)
    #   "off"  always the jnp phase chain
    # Numerics: BC/select/max phases bitwise-identical; F/G/RHS/projection
    # ulp-equivalent (same formula functions, compiler fma differences only
    # — the quarters-layout precedent).
    tpu_fuse_phases: str = "auto"
    # comm/compute overlap (distributed fused paths only): the step-level
    # deep-halo exchange for step N+1 is posted right after step N's POST
    # kernel and carried as a DOUBLE-BUFFERED pair of deep blocks; the
    # fused PRE splits into an interior half (provably independent of the
    # exchange — the traced program carries no path from the ppermutes to
    # it) and a boundary half that consumes the buffered exchange, merged
    # by the global-gated interior mask (parallel/overlap.py). CFL dt
    # comes from the POST kernel's carried |u|/|v|(/|w|) maxima (max is
    # exact under any reduction order, so the trajectory equals the
    # serial schedule's — parity test-pinned).
    #   "auto" overlap when eligible: a real TPU + the fused deep-halo
    #          step dispatched (jnp paths and PAMPI_FAULTS field-fault
    #          builds keep the serial schedule;
    #          utils/dispatch.resolve_overlap records every decision
    #          under the "overlap_ns2d_dist"/"overlap_ns3d_dist" keys)
    #   "on"   force (interpret kernels off-TPU — the parity-test mode)
    #   "off"  the serial schedule (bitwise the historical program —
    #          jaxpr-hash identity vs CONTRACTS.json)
    tpu_overlap: str = "auto"
    # grid restriction of the overlapped PRE halves (parallel/overlap.py
    # region plan + ops/ns*_fused region grids): instead of two full
    # write-gated sweeps, the interior half's Pallas grid covers only the
    # row blocks of the interior core and the boundary half only the
    # OVERLAP_RIM (edge row bands + narrow column strips on partitioned
    # column axes) — the ~2x PRE HBM traffic of the PR 8 split drops back
    # toward 1x once PRE is bandwidth-bound.
    #   "auto" restrict when the overlapped schedule is dispatched AND the
    #          restricted plan's summed grid cells beat the two full
    #          sweeps at this shard geometry (tiny shards keep the full
    #          write-gated halves — banding cannot win below a few row
    #          blocks); decision recorded under the
    #          "overlap_grid_<family>" dispatch keys with the call count
    #   "on"   force the restricted plan whenever the overlap schedule
    #          runs (the structural-test/smoke mode)
    #   "off"  always the two full write-gated halves (the PR 8 program)
    tpu_overlap_restrict: str = "auto"
    # mesh-tier map for hierarchical halo exchange (parallel/comm
    # ExchangeSchedule): "auto" = every axis one tier (today's single-
    # slice meshes — exchange order and traces bitwise-unchanged), or a
    # comma list "axis=tier" over ici|dcn, e.g. "k=dcn,j=ici,i=ici" for a
    # multi-slice pod whose k axis crosses the DCN. DCN-tier strips are
    # posted FIRST (deepest/earliest — they have the most latency to
    # hide), ICI strips last, in every persistent ExchangeSchedule; the
    # comm census and the BENCH plane break traffic out per tier
    # (dcn_exchange_bytes).
    tpu_mesh_tiers: str = "auto"
    # residual-adaptive solve budget (ROADMAP item 1's last open bullet):
    # 0 (default) keeps the static itermax cap. N > 0 lets the previous
    # step's (res, it) shrink the NEXT step's sweep budget inside the
    # chunk loop: a solve that converged in `it` sweeps caps the next at
    # it + N (the slack); a capped solve restores the full itermax. The
    # budget rides the chunk carry (external arity unchanged, resets per
    # chunk dispatch); dist SOR paths only (mg counts cycles, fft does
    # not iterate) — the decision is recorded under the
    # "itermax_adaptive_<family>" dispatch keys and the per-step `it`
    # telemetry shows the budget taking effect.
    tpu_itermax_adaptive: int = 0
    # scenario-fleet dispatch (pampi_tpu/fleet/): how a bucket of
    # same-signature requests is executed by the fleet scheduler
    # (utils/dispatch.resolve_fleet records every decision under the
    # per-bucket `fleet_<bucket>` keys).
    #   "auto"  vmap-batch single-device buckets with >1 scenario (one
    #           compiled program advances every lane; a diverged lane is
    #           frozen by the in-band sentinel, batchmates continue);
    #           distributed buckets and 1-scenario buckets run pjit:
    #           each scenario occupies the whole mesh sequentially,
    #           reusing the bucket's one compiled program
    #   "auto" additionally picks "mesh" (below) when a multi-device
    #           host can split the lanes evenly
    #   "vmap"  force the batched driver (dist buckets too — vmap over
    #           the shard_map'ed chunk; the parity-test mode)
    #   "mesh"  fleet-over-mesh (serving v2): the vmapped chunk's
    #           scenario axis sharded across a device-mesh axis via
    #           NamedSharding — N single-chip lanes in true parallel,
    #           zero collectives between lanes (commcheck's
    #           zero-resharding ban pins it); lanes must divide the
    #           device count
    #   "pjit"  force whole-mesh-per-scenario with executable reuse
    #   "solo"  the historical path: every request builds and runs its
    #           own solver (no template reuse; the oracle mode the
    #           fleet-smoke drift check compares against)
    # Serving v2 (fleet/serve.py): `te` is per-lane (carried in the
    # batched chunk state), so mixed end times share one compile; the
    # scheduler's shape classes and continuous lane pool are daemon/
    # constructor knobs, not .par keys — see README "Fleet serving".
    tpu_fleet: str = "auto"
    # MG stall detector (tpu_solver mg only): a V-cycle whose residual
    # changed less than this RELATIVE tolerance is treated as floored and
    # the solve returns early (ops/multigrid.MG_STALL_RTOL rationale). Set 0
    # to disable and burn itermax like the reference's capped solves do.
    tpu_mg_stall_rtol: float = 1e-4
    # fused MG cycle (tpu_solver mg only): auto|on|off. On eligible plans
    # the whole V-cycle runs as TWO dynamic-extent Pallas launches (DOWN:
    # smooth+restrict all levels, UP: prolong+smooth; ops/mg_fused.py)
    # with the exact direct bottom solve between them, instead of the
    # per-level smoother-launch ladder. "on" also enables the coarse-level
    # continuation in the distributed MG bottoms (gather below the shard
    # floor and keep coarsening globally — "mg_aggregate" seam) and the
    # FFT-preconditioned coarse application for over-budget obstacle
    # bottoms. "auto" dispatches the fused cycle on TPU only and keeps the
    # historical distributed bottoms; "off" is bitwise the historical
    # ladder. Decisions recorded via utils/dispatch ("mg2d_fused", ...).
    tpu_mg_fused: str = "auto"
    # capped-solve flat path (models/poisson.make_solver_fn flat=True,
    # tpu_solver sor only): the pressure solve runs EXACTLY
    # ceil(itermax/n_inner) kernel trips under fori_loop instead of the
    # res-gated while. BITWISE identical on configs whose solves always
    # hit itermax (the north-star cavity, the reference's canal configs);
    # converging configs overdrive to the cap (extra sweeps only lower
    # the residual). MEASURED neutral at 4096² (19.01 vs 19.04 ms/step,
    # interleaved A/B, round 5): the loop TRIP overhead, not the residual
    # gating, is the per-trip cost — kept as the structural option it is,
    # not a speed claim. 0 = off (default).
    tpu_flat_solve: int = 0
    # time-loop dispatch pipelining (models/_driver.drive_chunks): up to
    # this many chunk dispatches queued BEYOND the one the host is
    # confirming (so lookahead+1 states in flight), hiding the per-chunk
    # host<->device round trip (on the retired remote-chip setup of round
    # 5: 19.4 -> 17.7 ms/step at dcavity 4096^2; not measured since). 0 restores
    # dispatch-then-sync. Progress/checkpoint hooks see every chunk, just
    # this many chunks late. Cost: lookahead extra state copies on device.
    tpu_lookahead: int = 2
    # device steps per chunk dispatch (0 = the model default: 64 2-D, 32
    # 3-D). An escape hatch for programs the TPU runtime mishandles when
    # the step is wrapped in a multi-trip chunk loop (observed: 4096^2 f64
    # sor_lex crashes the TPU worker at any chunk > 1 — scan-in-while f64
    # at size — while tpu_chunk 1 runs; f32 production runs keep 64).
    tpu_chunk: int = 0
    # K-step fused chunks (ISSUE 17): auto|on|off|<int K>. When K >= 2
    # each trip of the chunk while-loop advances K steps inside ONE
    # `lax.scan` (the residual-adaptive itermax cap and the CFL/dt
    # scalars ride the scan carry; steps past te run a frozen identity
    # branch), so dispatch/carry-reshuffle overhead amortizes over K and
    # the static launches-per-step drops below 3. External chunk arity is
    # UNCHANGED — checkpoints, ring recovery, the coordinator fault word
    # and the fleet's BatchedSolver see the same state tuple. "off" (and
    # any resolution to K=1) is bitwise the historical chunk (jaxpr-hash
    # pinned in CONTRACTS.json); "auto" fuses K=4 on TPU only; "on"
    # forces K=4 anywhere (the CPU smoke/parity shape); an integer forces
    # that K (must divide the chunk length). Decisions recorded via
    # utils/dispatch ("<family>_chunk_fuse").
    tpu_chunk_fuse: str = "auto"
    # per-tier exchange depth (ISSUE 17): "axis=H" (e.g. "i=4") ships
    # depth-H halo strips on that DCN-tier axis so ONE slow exchange
    # covers H fused scan steps, while ICI axes keep fresh depth-1/deep
    # exchanges every step. RELAXED parity: slow-tier halo data is up to
    # H-1 steps stale at the strip's outer rim (the partitioned-
    # communication / halo-widening trade — PAPERS.md); CFL maxima stay
    # conservative. Eligibility (fused serial dist step, chunk_fuse
    # K >= 2 with H | K, tiered mesh with the axis declared dcn, shard
    # extent >= H, not ragged) is checked per build and refusals are
    # recorded ("<family>_exchange_depth"). "auto"/"off" = no depth map
    # (exact parity is never silently traded).
    tpu_exchange_depth: str = "auto"
    # 3-D VTK output mode: "ascii" (reference default), "binary", or
    # "sharded" — the MPI-IO-pattern parallel write (utils/vtkio.py
    # ShardedVtkWriter; binary, byte-identical to "binary"). On a
    # single-device run "sharded" degrades to "binary" (same bytes).
    tpu_vtk: str = "ascii"
    # checkpoint/restart (utils/checkpoint.py; the reference has none).
    # Writes rotate the live file to <path>.prev first (two generations on
    # disk) and carry per-field CRC32s; load rejects torn/corrupt files and
    # falls back to the .prev generation (README "Robustness").
    tpu_checkpoint: str = ""
    tpu_ckpt_every: int = 10
    tpu_restart: str = ""
    # elastic checkpoint format (utils/checkpoint.save_elastic): a JSON
    # manifest + per-rank shard files holding the MESH-INDEPENDENT global
    # reference-layout fields, so restore accepts a DIFFERENT mesh (or a
    # single device) by reassembling and resharding via NamedSharding —
    # the 8->4->1 chip shrink and the fleet autoscaling primitive
    # (fleet/scheduler.FleetScheduler.elastic_restore). 0 (default) keeps
    # the legacy single-.npz stacked-block format, which is
    # mesh-locked but preserves ghost state bit-exactly.
    tpu_ckpt_elastic: int = 0
    # chunk-boundary agreement protocol (parallel/coordinator.py):
    # auto = coordinate exactly under a multi-process launch (lifting
    # the PR 4 transient_budget=0 ban — the global budget, rollback and
    # checkpoint decisions are agreed via a host-side allgather at each
    # boundary), on = force the 1-rank coordinator single-process (the
    # protocol-path proof shape), off = the historical uncoordinated
    # loop (multi-process faults kill the job cleanly).
    tpu_coord: str = "auto"
    # boundary-allgather watchdog (parallel/coordinator.py, PR 12):
    # seconds a rank waits at the chunk-boundary rendezvous before the
    # survivors declare the silent rank(s) DEAD via the membership
    # agreement round and raise RankDeadError. Keep it well UNDER the
    # backend's own collective timeout (XLA cross-host barriers default
    # to 10+ minutes) so the host-side rendezvous is where a death
    # surfaces, and above the slowest honest chunk (a cold compile
    # inside a dispatch must not read as a death). 0 disables (the
    # pre-PR-12 hang-until-backend behavior).
    tpu_coord_timeout: float = 300.0
    # shrink-to-survivors resume (cli.py / fleet/scheduler.shrink_resume):
    # 1 (default) = on RankDeadError, when an elastic checkpoint is
    # armed, restore the newest agreed generation (+ fault ledger) onto
    # the surviving capacity and finish the run degraded; 0 = surface
    # the structured error and stop (operator-driven resume). The
    # in-process resume covers the single-process shapes (one host
    # owning local devices; the lockstep proof path) — under a real
    # multi-process launch the survivors PRINT the relaunch walkthrough
    # instead (an in-place process-group shrink would need a re-elected
    # coordinator; see cli._resume_after_death).
    tpu_dead_resume: int = 1
    # serving autopilot (fleet/autopilot.py, ISSUE 19): the policy loop
    # that closes observe->decide->act inside the daemon's poll cycle —
    # "off" (default: the daemon is byte-identical to the policy-less
    # build, test-pinned) or "on[:k=v,...]" with hysteresis overrides
    # (burn_high/burn_low/backlog_high/sustain/cooldown/min_lanes/
    # max_lanes/idle_polls/itermax_cap/flap_window — see
    # fleet/autopilot.parse_autopilot_spec). On: a RankDeadError from the
    # resident elastic job auto-`shrink_resume`s onto survivor capacity
    # (ledger carried), sustained SLO burn/backlog grows the lane pool
    # (checkpoint-fenced via the elastic manifest), sustained idle
    # shrinks it, and past capacity the daemon steps down the explicit
    # degradation ladder (class-lane consolidation -> itermax caps ->
    # lowest-priority admission shedding), back up when burn recovers.
    # Every decision is an `autoscale` telemetry record. A HOUSEKEEPING
    # key: never part of the bucket signature or traced programs.
    tpu_autopilot: str = "off"
    # divergence rollback-recovery (models/_driver.RingRecovery; README
    # "Robustness"): tpu_recover_ring > 0 arms an in-memory ring of the
    # last-K confirmed finite chunk states (no disk round-trip on the hot
    # path; the on-disk tpu_checkpoint is the cold tier when the ring is
    # exhausted). On a NaN loop time the drive loop rolls back to the
    # newest ring entry (successive attempts dig deeper) and re-drives
    # with dt clamped by tpu_recover_dt_scale (cumulative per attempt),
    # at most tpu_recover_max attempts per run — each attempt emits a
    # structured `recover` telemetry record. 0 (default) keeps the
    # historical terminate-on-NaN behavior. Memory cost: ring x one state
    # tuple held on device.
    tpu_recover_ring: int = 0
    tpu_recover_dt_scale: float = 0.5
    tpu_recover_max: int = 3
    # retry-budget replenishment (models/_driver.drive_chunks): the
    # one-shot transient device-fault budget refills — and a pallas->jnp
    # runtime fallback is allowed to restore the pallas chunk — after this
    # many consecutive clean chunks, so a 10-hour run survives more than
    # one spaced transient. 0 = never refill (the historical
    # one-fault-per-run budget).
    tpu_retry_replenish: int = 8
    # keys explicitly present in the parsed file (not a .par key itself);
    # lets the driver tell a 3-D config (kmax/zlength/bcFront set) from a
    # 2-D one, since the reference distinguishes by binary instead
    seen_keys: tuple = ()

    def replace(self, **kw) -> "Parameter":
        return dataclasses.replace(self, **kw)


_FIELDS = {
    f.name: f.type
    for f in dataclasses.fields(Parameter)
    if f.name != "seen_keys"
}
_CASTS = {"int": int, "float": float, "str": str}


def _parse_line(line: str):
    line = line.split("#", 1)[0]
    toks = line.split()
    if len(toks) < 2:
        return None
    return toks[0], toks[1]


def read_parameter(path: str, base: Parameter | None = None) -> Parameter:
    """Parse a .par file. Prefix-match keys like the reference parser does."""
    param = dataclasses.replace(base) if base is not None else Parameter()
    try:
        fh = open(path)
    except OSError:
        print(f"Could not open parameter file: {path}", file=sys.stderr)
        raise SystemExit(1)
    seen = set(param.seen_keys)
    with fh:
        for raw in fh:
            kv = _parse_line(raw)
            if kv is None:
                continue
            tok, val = kv
            # reference semantics: every known key whose name is a prefix of the
            # token gets assigned (independent `if`s, not elif) — EXCEPT an
            # exact key name, which assigns only itself: the framework keys
            # are namespaced (tpu_coord / tpu_coord_timeout) where the
            # reference's key set is prefix-free, so without exact-wins the
            # longer key's line would clobber the shorter key too
            keys = ([tok] if tok in _FIELDS
                    else [k for k in _FIELDS if tok.startswith(k)])
            for key in keys:
                ftype = _FIELDS[key]
                cast = _CASTS[ftype if isinstance(ftype, str) else ftype.__name__]
                try:
                    setattr(param, key, cast(val))
                    seen.add(key)
                except ValueError:
                    print(
                        f"bad value {val!r} for parameter {key}", file=sys.stderr
                    )
                    raise SystemExit(1)
    param.seen_keys = tuple(sorted(seen))
    return param


def is_3d_config(p: Parameter) -> bool:
    """True when the .par explicitly configures the third dimension (the
    reference distinguishes 2-D/3-D by binary; we dispatch on the geometry/BC
    keys every real 3-D config sets)."""
    return p.name.endswith("3d") or any(
        k in p.seen_keys for k in ("kmax", "zlength", "bcFront", "bcBack")
    )


def print_parameter(p: Parameter, out=None) -> None:
    """Echo the configuration (parity: A5 parameter.c:88-111 for 2-D configs,
    A6 parameter.c:95-126 — Front/Back, W, z-dims — for 3-D ones)."""
    out = out if out is not None else sys.stdout
    w = out.write
    three_d = is_3d_config(p)
    w(f"Parameters for {p.name}\n")
    if three_d:
        w(
            "Boundary conditions Left:%d Right:%d Bottom:%d Top:%d Front:%d "
            "Back:%d\n"
            % (p.bcLeft, p.bcRight, p.bcBottom, p.bcTop, p.bcFront, p.bcBack)
        )
    else:
        w(
            "Boundary conditions Left:%d Right:%d Bottom:%d Top:%d\n"
            % (p.bcLeft, p.bcRight, p.bcBottom, p.bcTop)
        )
    w("\tReynolds number: %.2f\n" % p.re)
    if three_d:
        w(
            "\tInit arrays: U:%.2f V:%.2f W:%.2f P:%.2f\n"
            % (p.u_init, p.v_init, p.w_init, p.p_init)
        )
    else:
        w("\tInit arrays: U:%.2f V:%.2f P:%.2f\n" % (p.u_init, p.v_init, p.p_init))
    w("Geometry data:\n")
    if three_d:
        w(
            "\tDomain box size (x, y, z): %.2f, %.2f, %.2f\n"
            % (p.xlength, p.ylength, p.zlength)
        )
        w("\tCells (x, y, z): %d, %d, %d\n" % (p.imax, p.jmax, p.kmax))
    else:
        w("\tDomain box size (x, y): %.2f, %.2f\n" % (p.xlength, p.ylength))
        w("\tCells (x, y): %d, %d\n" % (p.imax, p.jmax))
    w("Timestep parameters:\n")
    w("\tDefault stepsize: %.2f, Final time %.2f\n" % (p.dt, p.te))
    w("\tTau factor: %.2f\n" % p.tau)
    w("Iterative solver parameters:\n")
    w("\tMax iterations: %d\n" % p.itermax)
    w("\tepsilon (stopping tolerance) : %f\n" % p.eps)
    w("\tgamma factor: %f\n" % p.gamma)
    w("\tomega (SOR relaxation): %f\n" % p.omg)


def print_solver_config(p, grid, dt_bound, out=None) -> None:
    """The reference's -DVERBOSE solver-config block, 3-D driver only
    (assignment-6/src/solver.c:36-73 printConfig, gated like main.c's
    VERBOSE): computed grid spacings and the CFL dt bound, on top of the
    always-printed parameter echo (print_parameter)."""
    out = out or sys.stdout
    w = out.write
    w("Parameters for #%s#\n" % p.name)
    w(
        "BC Left:%d Right:%d Bottom:%d Top:%d Front:%d Back:%d\n"
        % (p.bcLeft, p.bcRight, p.bcBottom, p.bcTop, p.bcFront, p.bcBack)
    )
    w("\tReynolds number: %.2f\n" % p.re)
    w("\tGx Gy: %.2f %.2f %.2f\n" % (p.gx, p.gy, p.gz))
    w("Geometry data:\n")
    w(
        "\tDomain box size (x, y, z): %.2f, %.2f, %.2f\n"
        % (grid.xlength, grid.ylength, grid.zlength)
    )
    w("\tCells (x, y, z): %d, %d, %d\n" % (grid.imax, grid.jmax, grid.kmax))
    w(
        "\tCell size (dx, dy, dz): %f, %f, %f\n" % (grid.dx, grid.dy, grid.dz)
    )
    w("Timestep parameters:\n")
    w("\tDefault stepsize: %.2f, Final time %.2f\n" % (p.dt, p.te))
    w("\tdt bound: %.6f\n" % dt_bound)
    w("\tTau factor: %.2f\n" % p.tau)
    w("Iterative parameters:\n")
    w("\tMax iterations: %d\n" % p.itermax)
    w("\tepsilon (stopping tolerance) : %f\n" % p.eps)
    w("\tgamma factor: %f\n" % p.gamma)
    w("\tomega (SOR relaxation): %f\n" % p.omg)


def validate_obstacle_layout(layout: str) -> None:
    """Obstacle flag fields run only on the masked checkerboard kernel
    (2-D and 3-D alike); reject a forced compressed layout instead of
    silently ignoring it. Shared by NS2DSolver and NS3DSolver."""
    if layout not in ("auto", "checkerboard"):
        raise ValueError(
            f"tpu_sor_layout {layout} does not support obstacle flag "
            "fields; obstacle runs use the masked checkerboard kernel "
            "(auto|checkerboard)"
        )
