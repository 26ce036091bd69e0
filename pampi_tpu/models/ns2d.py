"""NS-2D incompressible Navier-Stokes time-stepper (lid-driven cavity, canal).

Capability parity with /root/reference/assignment-5/sequential — the full
pipeline of SURVEY.md §3.5: computeTimestep → setBoundaryConditions →
setSpecialBoundaryCondition → computeFG → computeRHS → (nt%100==0)
normalizePressure → solve → adaptUV, advancing t += dt while t <= te
(main.c:43-60).

TPU-first design:
- One timestep is a single traced function; the pressure solve inside it is
  the same red-black `lax.while_loop` used by the Poisson model (equivalence
  policy documented there — the reference's lexicographic SOR trajectory is
  matched at the converged-residual level, not sweep-by-sweep).
- The time loop itself runs ON DEVICE in chunks of `chunk` steps (a
  `lax.while_loop` whose cond is `t <= te && k < chunk`), so the host syncs
  once per chunk — not once per step — and XLA overlaps everything else.
  Progress is reported at chunk granularity (progress.c parity).
- tau > 0 (adaptive CFL) vs constant-dt is a trace-time branch, like the
  reference's `if (tau > 0)` (main.c:44).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops import ns2d as ops
from ..utils import faultinject as _fi
from ..utils import flags as _flags
from ..utils import profiling as _prof
from ..utils import telemetry as _tm
from ._driver import clamped_dt
from ..utils.datio import write_pressure, write_velocity
from ..utils.params import Parameter, validate_obstacle_layout
from ..utils.precision import resolve_dtype
from ..utils.progress import Progress


def make_pressure_solve(imax, jmax, dx, dy, omega, eps, itermax, dtype,
                        backend: str = "auto", n_inner: int = 1,
                        solver: str = "sor", layout: str = "auto",
                        stall_rtol=None, flat: bool = False,
                        mg_fused: str = "off"):
    """Pressure-Poisson solve loop (solve, solver.c:140-191): carry
    (p, res, it); res = Σr²/(imax·jmax) vs eps²; Neumann ghost copy per sweep.

    Layout: `layout` goes straight to make_rb_loop's standard dispatch
    (auto -> quarters when eligible, checkerboard otherwise). Measured
    (v5e, 4096² dcavity, itermax=100, chained-step differencing, round 3):
    quarters 22.2-22.5 ms/step vs checkerboard 36.9-39.6 — quarters wins
    1.7× at the step level too. Round 2 had measured quarters LOSING (68 vs
    39 ms/step) and pinned NS-2D auto to checkerboard; that loss predated
    the staged single-transpose packing — the pack+unpad roundtrip now
    measures 0.94 ms at 4096².

    solver="sor" (default, the reference's algorithm): identical semantics to
    the Poisson convergence loop, so it IS that loop — `make_solver_fn`
    dispatches to the fused Pallas kernel on TPU (f32/bf16), converting to
    the padded layout once per pressure solve, not per sweep.
    solver="mg": geometric multigrid V-cycles (ops/multigrid.py), same
    stopping contract, `it` counts cycles.
    solver="fft": direct DCT-diagonalization solve (ops/dctpoisson.py) —
    exact in one application, `it` reports 1."""
    if solver == "mg":
        from ..ops.multigrid import make_mg_solve_2d

        return make_mg_solve_2d(imax, jmax, dx, dy, eps, itermax, dtype,
                                stall_rtol=stall_rtol, backend=backend,
                                fused=mg_fused)
    if solver == "fft":
        from ..ops.dctpoisson import make_dct_solve_2d

        return make_dct_solve_2d(imax, jmax, dx, dy, dtype)
    if solver == "sor_lex":
        # the reference's LEXICOGRAPHIC solve (assignment-5/sequential/src/
        # solver.c:159-176) as an oracle mode: on itermax-capped configs the
        # capped trajectory depends on the sweep ORDERING, so C-vs-framework
        # field comparisons at fixed step count need this path, not rb
        # (tools/northstar.py match4096). Always the jnp scan program
        # (ops/sor.lex_sweep), f64-capable, never pallas.
        from .poisson import make_solver_fn

        return make_solver_fn(imax, jmax, dx, dy, omega, eps, itermax,
                              dtype, backend="jnp", method="lex")
    if solver != "sor":
        raise ValueError(
            f"NS pressure solve supports sor|sor_lex|mg|fft, got {solver!r} "
            "(sor_rba is a Poisson-only oracle mode)"
        )
    from .poisson import make_solver_fn

    return make_solver_fn(imax, jmax, dx, dy, omega, eps, itermax, dtype,
                          backend=backend, n_inner=n_inner,
                          layout=layout, flat=flat)


class NS2DSolver:
    """Driver-facing NS-2D solver (≙ the Solver struct + main loop)."""

    CHUNK = 64  # device steps per host sync

    def __init__(self, param: Parameter, dtype=None):
        from ..utils.dispatch import resolve_solver

        param = resolve_solver(param, obstacles=bool(param.obstacles.strip()))
        if dtype is None:
            dtype = resolve_dtype(param.tpu_dtype,
                                  record_key="ns2d_dtype")
        self.param = param
        self.dtype = dtype
        self.imax, self.jmax = param.imax, param.jmax
        self.dx = param.xlength / param.imax
        self.dy = param.ylength / param.jmax
        shape = (param.jmax + 2, param.imax + 2)
        self.u = jnp.full(shape, param.u_init, dtype)
        self.v = jnp.full(shape, param.v_init, dtype)
        self.p = jnp.full(shape, param.p_init, dtype)
        inv_sqr_sum = 1.0 / (self.dx * self.dx) + 1.0 / (self.dy * self.dy)
        self.dt_bound = 0.5 * param.re / inv_sqr_sum
        self.t = 0.0
        self.nt = 0
        self._backend = "auto"
        self._fused = False  # set by _build_chunk (fused-phase dispatch)
        self._dt_scale = 1.0  # recovery dt clamp (models/_driver.clamped_dt)
        # flag-field obstacles (ops/obstacle.py): static geometry -> static
        # masks baked into the traced step as constants (branch-free)
        if param.obstacles.strip():
            if param.tpu_solver in ("fft", "sor_lex"):
                raise ValueError(
                    f"tpu_solver {param.tpu_solver} cannot solve obstacle "
                    "flag fields (fft: non-constant coefficients; sor_lex: "
                    "the lex oracle has no eps-coefficient form); use sor "
                    "or mg"
                )
            validate_obstacle_layout(param.tpu_sor_layout)
            from ..ops import obstacle as obst

            fluid = obst.build_fluid(
                param.imax, param.jmax, self.dx, self.dy, param.obstacles
            )
            self.masks = obst.make_masks(fluid, self.dx, self.dy, param.omg, dtype)
        else:
            self.masks = None
        t0 = time.perf_counter()
        # fault-injection generation for this build (utils/faultinject.py):
        # taken HERE and in _rebuild_chunk only, never inside _build_chunk —
        # the pallas->jnp fallback rebuild must keep the failing chunk's
        # armed corruption instead of silently spending a fresh generation
        self._field_faults = _fi.take_field_faults()
        self._chunk_fn = jax.jit(self._build_chunk())
        from ..utils import dispatch as _dispatch

        _tm.emit("build", family="ns2d", grid=[self.jmax, self.imax],
                 trace_wall_s=round(time.perf_counter() - t0, 3),
                 phases=_dispatch.last("ns2d_phases"))

    def _uses_pallas(self) -> bool:
        """Whether the current chunk contains ANY pallas kernel — the
        pressure solve's (the uniform solver, the flag-masked solver, and
        mg's fine-level smoother all go through the same backend probe;
        jnp-dispatched dtypes/backends never do; fft and the always-jnp
        sor_lex oracle contain no solve kernel) or the fused step-phase
        pair, so the runtime retry protocol (models/_driver.pallas_retry)
        covers the fused chunk too."""
        if self._fused:
            return True
        if self.param.tpu_solver in ("fft", "sor_lex"):
            return False
        from .poisson import _use_pallas

        return _use_pallas(self._backend, self.dtype)

    def _make_solve(self, backend: str):
        """The pressure-solve closure for one backend — shared by the jnp
        step chain and the fused-phase chunk (the fused kernels replace the
        non-solve phases only; the solve dispatch is unchanged)."""
        param = self.param
        dx, dy = self.dx, self.dy
        dtype = self.dtype
        masks = self.masks
        if masks is None:
            solve = make_pressure_solve(
                param.imax,
                param.jmax,
                dx,
                dy,
                param.omg,
                param.eps,
                param.itermax,
                dtype,
                backend=backend,
                n_inner=param.tpu_sor_inner,
                solver=param.tpu_solver,
                layout=param.tpu_sor_layout,
                stall_rtol=param.tpu_mg_stall_rtol,
                flat=bool(param.tpu_flat_solve),
                mg_fused=param.tpu_mg_fused,
            )
        elif param.tpu_solver == "mg":
            # obstacle-capable multigrid: rediscretized eps-coefficient
            # operator per level (ops/multigrid.make_obstacle_mg_solve_2d) —
            # the O(1)-cycles option fft cannot provide here
            from ..ops.multigrid import make_obstacle_mg_solve_2d

            solve = make_obstacle_mg_solve_2d(
                param.imax, param.jmax, dx, dy, param.eps, param.itermax,
                masks, dtype,
                stall_rtol=param.tpu_mg_stall_rtol, backend=backend,
                fused=param.tpu_mg_fused,
            )
        else:
            from ..ops import obstacle as obst

            solve = obst.make_obstacle_solver_fn(
                param.imax, param.jmax, dx, dy, param.eps, param.itermax,
                masks, dtype, backend=backend,
                n_inner=param.tpu_sor_inner,
            )
        return _prof.scoped("solve", solve)

    # -- one full timestep, traced ------------------------------------
    def _build_presolve(self):
        """The pre-solve phase chain (dt → wall BCs → special BC → obstacle
        BC → F/G predictor → obstacle F/G mask → Poisson rhs) as a
        standalone traced function (u, v) -> (u, v, f, g, rhs, dt).
        _build_step composes it with the solve/projection phases; the
        solve/non-solve decomposition tools (bench.py, tools/northstar.py)
        call it to derive a representative rhs for timing the step's own
        solve closure — one wiring, no hand-copies to drift."""
        param = self.param
        dx, dy = self.dx, self.dy
        dtype = self.dtype
        masks = self.masks
        adaptive = param.tau > 0.0
        problem = param.name
        dt_scale = self._dt_scale  # 1.0 = identity (recovery rebuilds clamp)

        def presolve(u, v):
            if adaptive:
                dt = ops.compute_timestep(u, v, self.dt_bound, dx, dy, param.tau)
            else:
                dt = jnp.asarray(param.dt, dtype)
            dt = clamped_dt(dt, dt_scale)
            u, v = ops.set_boundary_conditions(
                u, v, param.bcLeft, param.bcRight, param.bcBottom, param.bcTop
            )
            if problem == "dcavity":
                u = ops.set_special_bc_dcavity(u)
            elif problem in ("canal", "canal_obstacle"):
                u = ops.set_special_bc_canal(u, dy, param.ylength, dtype)
            if masks is not None:
                from ..ops.obstacle import (
                    apply_obstacle_velocity_bc,
                    mask_fg,
                )

                u, v = apply_obstacle_velocity_bc(u, v, masks)
            f, g = ops.compute_fg(
                u, v, dt, param.re, param.gx, param.gy, param.gamma, dx, dy
            )
            if masks is not None:
                f, g = mask_fg(f, g, u, v, masks)
            rhs = ops.compute_rhs(f, g, dt, dx, dy)
            return u, v, f, g, rhs, dt

        return presolve

    def time_solve_ms(self, reps: int = 6) -> float:
        """Best-of-`reps` wall time (ms) of the step's OWN solve closure on
        the first step's rhs. The solve/non-solve decomposition tools
        (bench.py, tools/northstar.py) both call this, so BENCH_*.json and
        the northstar artifact always time the identical protocol: rhs via
        _build_presolve, jit once, warm with a scalar readback fence,
        best-of-reps perf_counter."""
        import time

        *_, rhs, _dt = jax.jit(self._build_presolve())(self.u, self.v)
        fold = getattr(self, "_folded_solve", None)
        if fold is not None:
            # the folded chunk runs its solve ENTIRELY in the padded layout
            # (models/poisson.make_padded_solver_fn) — time that program,
            # not the conversion-wrapped _make_solve the step no longer uses
            solve_fn, pad = fold
            solve = jax.jit(solve_fn)
            p_in, rhs_in = pad(self.p), pad(rhs)
        else:
            solve = jax.jit(self._make_solve(self._backend))
            p_in, rhs_in = self.p, rhs
        _p, res, _it = solve(p_in, rhs_in)
        float(res)  # compile + warm-up; scalar readback is the fence
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            _p, res, _it = solve(p_in, rhs_in)
            float(res)
            best = min(best, time.perf_counter() - t0)
        return best * 1e3

    def _build_step(self, backend: str = "auto", instrumented: bool = False):
        """One traced timestep (the jnp phase chain — the parity oracle and
        CPU path; _build_fused_chunk is the TPU production composition).
        instrumented=True returns the SAME pipeline with the pressure
        solve's discarded outputs exposed — (u, v, p, t, nt, res, it, dt) —
        so measurement tools (tools/northstar.py, tools/perf_obstacle_mg.py)
        can sample solver iteration counts without hand-copying the step
        wiring (which would silently diverge when this pipeline changes)."""
        param = self.param
        dx, dy = self.dx, self.dy
        dtype = self.dtype
        masks = self.masks
        solve = self._make_solve(backend)
        presolve = self._build_presolve()
        faults = getattr(self, "_field_faults", ())

        def step(u, v, p, t, nt):
            with jax.named_scope("phases"):
                u, v, p = _fi.apply_field_faults(faults, nt, u=u, v=v, p=p)
                u, v, f, g, rhs, dt = presolve(u, v)
                if masks is None:
                    p = lax.cond(nt % 100 == 0, ops.normalize_pressure,
                                 lambda q: q, p)
                else:
                    from ..ops.obstacle import normalize_pressure_fluid

                    p = lax.cond(
                        nt % 100 == 0,
                        lambda q: normalize_pressure_fluid(q, masks),
                        lambda q: q,
                        p,
                    )
            p, res, it = solve(p, rhs)
            with jax.named_scope("phases"):
                if masks is None:
                    u, v = ops.adapt_uv(u, v, f, g, p, dt, dx, dy)
                else:
                    from ..ops.obstacle import adapt_uv_obstacle

                    u, v = adapt_uv_obstacle(u, v, f, g, p, dt, dx, dy, masks)
                # t accumulates in high precision regardless of the field
                # dtype (bfloat16 would stall t once ulp/2 > dt and never
                # reach te)
                time_dtype = (jnp.float64 if jax.config.jax_enable_x64
                              else jnp.float32)
                t_next = t + dt.astype(time_dtype)
                if _flags.verbose():
                    # ≙ -DVERBOSE "TIME %f , TIMESTEP %f" printed AFTER
                    # t += dt (A5 main.c:52-57)
                    jax.debug.print("TIME {} , TIMESTEP {}", t_next, dt)
            if instrumented:
                return u, v, p, t_next, nt + 1, res, it, dt
            return u, v, p, t_next, nt + 1

        return step

    def _build_fused_chunk(self, backend: str, metrics: bool = False,
                           te_arg: bool = False, kfuse: int = 1):
        """The fused-phase chunk: the non-solve step phases run as the two
        Pallas kernels of ops/ns2d_fused.py (BCs+FG+RHS before the solve,
        adaptUV+CFL-max after), the loop carries u/v in the kernels' padded
        layout plus the running (umax, vmax) scalars, and the timestep is
        pure scalar math (ops/ns2d.cfl_dt). Returns None when the fused
        path is not dispatched (knob off, jnp backend, no TPU, probe/VMEM
        failure) — the caller falls back to the jnp chunk.

        metrics=True (PAMPI_TELEMETRY set at build time) additionally
        threads the in-band telemetry vector through the chunk: the solve's
        res/it and dt join the already-carried CFL maxima as f32 scalars,
        plus the non-finite sentinel (utils/telemetry.sentinel_update) —
        read out only at the chunk boundary where the host already syncs.
        metrics=False takes the exact pre-telemetry trace (jaxpr identity,
        tests/test_telemetry.py)."""
        from ..ops.ns2d_fused import probe_fused_2d
        from ..utils.dispatch import record, resolve_fuse_phases

        # reset BEFORE any early return: the pallas-retry rebuild
        # (backend="jnp") exits at the gate below and must not leave a
        # stale folded solve for time_solve_ms to time
        self._folded_solve = None
        param = self.param
        if not resolve_fuse_phases(
            param, backend, self.dtype, probe_fused_2d, "ns2d_phases",
        ):
            return None
        from ..ops import ns2d_fused as nf

        dx, dy = self.dx, self.dy
        dtype = self.dtype
        masks = self.masks

        # p-layout fold (the ROADMAP post-fusion knob): when the pressure
        # solve resolves to the checkerboard tblock kernel, run it DIRECTLY
        # on the fused kernels' padded layout — p and rhs stay padded across
        # the whole chunk and the per-step layout passes around the solve
        # (unpad rhs, re-pad rhs, pad/unpad p) vanish. The quarters layout
        # keeps explicit conversions (its stacked data layout cannot be
        # shared with the phase kernels; it remains the measured-best solve
        # at 4096², so auto-even grids are untouched).
        solve_pad = br_fold = None

        def ckb_solve_home():
            if param.tpu_sor_layout == "checkerboard":
                return True
            if param.tpu_sor_layout == "quarters":
                return False
            # auto: ask the solver's OWN layout resolution (including its
            # quarters-VMEM-infeasible fallback to checkerboard) instead of
            # re-deriving the policy here; called lazily, only when the
            # other fold preconditions already hold (the probe builds a
            # throwaway quarters kernel)
            from .poisson import _try_quarters

            return _try_quarters(
                param.imax, param.jmax, dx, dy, param.omg, dtype,
                param.tpu_sor_inner, "auto",
            ) is None

        from .poisson import _use_pallas

        if (masks is None and param.tpu_solver == "sor"
                and (param.tpu_fuse_phases == "on"
                     or _use_pallas(backend, dtype))
                and ckb_solve_home()):
            from .poisson import make_padded_solver_fn

            try:
                solve_pad, br_fold, h_fold = make_padded_solver_fn(
                    param.imax, param.jmax, dx, dy, param.omg, param.eps,
                    param.itermax, dtype, n_inner=param.tpu_sor_inner,
                    flat=bool(param.tpu_flat_solve),
                )
                if (br_fold, h_fold) != nf.fused_layout_2d(
                        param.jmax, param.imax, dtype, block_rows=br_fold):
                    solve_pad = br_fold = None  # halo mismatch: no shared layout
            except ValueError:  # tblock unavailable/VMEM-infeasible
                solve_pad = br_fold = None

        def build_step(block_rows):
            return nf.make_fused_step_2d(
                param, param.jmax, param.imax, dx, dy, dtype,
                fluid=None if masks is None else masks.fluid,
                block_rows=block_rows,
            )

        try:
            pre, post, pad, unpad, _h = build_step(br_fold)
        except ValueError as exc:  # VMEM-infeasible geometry
            if br_fold is None:
                record("ns2d_phases", f"jnp ({exc})")
                return None
            # the solve's block_rows didn't fit the phase kernels' larger
            # VMEM budget: give up the fold, keep the fusion (PR 1 default
            # geometry) rather than dropping the whole step to the jnp chain
            solve_pad = br_fold = None
            try:
                pre, post, pad, unpad, _h = build_step(None)
            except ValueError as exc2:
                record("ns2d_phases", f"jnp ({exc2})")
                return None
        # recorded only now: the fold is live only if the phase kernels
        # themselves built (a VMEM failure above falls back to the jnp
        # chain, where no padded layout exists at all)
        record("ns2d_p_layout",
               "folded (solve shares the fused padded layout)"
               if solve_pad is not None else "explicit pad/unpad")
        if solve_pad is not None:
            record("sor2d", f"pallas_tblock (n_inner={param.tpu_sor_inner}"
                            ", folded)")
        solve = (self._make_solve(backend) if solve_pad is None
                 else _prof.scoped("solve", solve_pad))
        if solve_pad is not None:
            # time_solve_ms must time THIS padded-layout solve, not the
            # conversion-wrapped _make_solve the folded step no longer runs
            self._folded_solve = (solve_pad, pad)
        adaptive = param.tau > 0.0
        dt_scale = self._dt_scale  # 1.0 = identity (recovery rebuilds clamp)
        faults = getattr(self, "_field_faults", ())
        te_static = param.te
        chunk = param.tpu_chunk or self.CHUNK
        offs = jnp.zeros((2,), jnp.int32)
        time_dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
        if masks is not None:
            from ..ops.obstacle import normalize_pressure_fluid

            def normalize(q):
                return normalize_pressure_fluid(q, masks)
        else:
            normalize = ops.normalize_pressure

        folded = solve_pad is not None
        if folded:
            # normalize on the padded carry: the conversion pair runs only
            # inside the every-100-steps cond branch
            def norm_carry(q):
                return pad(normalize(unpad(q)))
        else:
            norm_carry = normalize

        def step(up, vp, p, t, nt, umax, vmax):
            with jax.named_scope("phases"):
                # `p` is the padded carry when folded, the plain array
                # otherwise
                up, vp, p = _fi.apply_field_faults(faults, nt, u=up, v=vp, p=p)
                if adaptive:
                    dt = ops.cfl_dt(umax, vmax, self.dt_bound, dx, dy,
                                    param.tau)
                else:
                    dt = jnp.asarray(param.dt, dtype)
                dt = clamped_dt(dt, dt_scale)
                dt11 = jnp.full((1, 1), dt, dtype)
                up, vp, fp, gp, rhsp = pre(offs, dt11, up, vp)
                p = lax.cond(nt % 100 == 0, norm_carry, lambda q: q, p)
                rhs = rhsp if folded else unpad(rhsp)
            p, _res, _it = solve(p, rhs)
            with jax.named_scope("phases"):
                p_post = p if folded else pad(p)
                up, vp, umax, vmax = post(offs, dt11, up, vp, fp, gp, p_post)
                t_next = t + dt.astype(time_dtype)
                if _flags.verbose():
                    jax.debug.print("TIME {} , TIMESTEP {}", t_next, dt)
            if metrics:
                return (up, vp, p, t_next, nt + 1, umax, vmax,
                        _res, _it, dt)
            return up, vp, p, t_next, nt + 1, umax, vmax

        def chunk_fn(u, v, p, t, nt, *te_in):
            # te_arg builds take the end time as a TRACED trailing arg
            # (the fleet's per-lane te carry); the default closes over
            # the baked constant — the byte-identical historical trace
            te = te_in[0] if te_in else te_static
            up, vp = pad(u), pad(v)
            if folded:
                p = pad(p)
            umax = jnp.max(jnp.abs(u))
            vmax = jnp.max(jnp.abs(v))

            def cond(c):
                return jnp.logical_and(c[3] <= te, c[7] < chunk)

            if kfuse > 1:
                # K-step fused trips (ISSUE 17): one scan advances K
                # gated steps — past te the frozen branch is an identity
                # on the carry, so nt/t stay exact at the boundary
                def kblock(c, _):
                    def live(c):
                        return step(*c)

                    return lax.cond(c[3] <= te, live, lambda c: c, c), None

                def body(c):
                    up, vp, p, t, nt, umax, vmax, k = c
                    (up, vp, p, t, nt, umax, vmax), _ = lax.scan(
                        kblock, (up, vp, p, t, nt, umax, vmax), None,
                        length=kfuse)
                    return up, vp, p, t, nt, umax, vmax, k + kfuse
            else:
                def body(c):
                    up, vp, p, t, nt, umax, vmax, k = c
                    up, vp, p, t, nt, umax, vmax = step(
                        up, vp, p, t, nt, umax, vmax
                    )
                    return up, vp, p, t, nt, umax, vmax, k + 1

            up, vp, p, t, nt, _um, _vm, _k = lax.while_loop(
                cond, body,
                (up, vp, p, t, nt, umax, vmax, jnp.asarray(0, jnp.int32)),
            )
            return unpad(up), unpad(vp), unpad(p) if folded else p, t, nt

        def chunk_fn_metrics(u, v, p, t, nt, m, *te_in):
            # the telemetry twin: same loop, the f32 metrics scalars ride
            # the carry and pack into the in-band vector at the boundary
            te = te_in[0] if te_in else te_static
            up, vp = pad(u), pad(v)
            if folded:
                p = pad(p)
            umax = jnp.max(jnp.abs(u))
            vmax = jnp.max(jnp.abs(v))

            def cond(c):
                return jnp.logical_and(c[3] <= te, c[7] < chunk)

            if kfuse > 1:
                # metrics_step runs PER STEP inside the live branch (the
                # POST-step nt, exactly the historical placement), so the
                # divergence sentinel keeps step resolution across the
                # K-block
                def kblock(c, _):
                    def live(c):
                        up, vp, p, t, nt, umax, vmax, res, it, dtv, bad = c
                        (up, vp, p, t, nt, umax, vmax,
                         res, it, dtv) = step(up, vp, p, t, nt, umax, vmax)
                        res, it, dtv, _um, _vm, bad = _tm.metrics_step(
                            bad, nt, res, it, dtv, umax, vmax)
                        return (up, vp, p, t, nt, umax, vmax,
                                res, it, dtv, bad)

                    return lax.cond(c[3] <= te, live, lambda c: c, c), None

                def body(c):
                    up, vp, p, t, nt, umax, vmax, k, res, it, dtv, bad = c
                    (up, vp, p, t, nt, umax, vmax,
                     res, it, dtv, bad), _ = lax.scan(
                        kblock,
                        (up, vp, p, t, nt, umax, vmax, res, it, dtv, bad),
                        None, length=kfuse)
                    return (up, vp, p, t, nt, umax, vmax, k + kfuse,
                            res, it, dtv, bad)
            else:
                def body(c):
                    up, vp, p, t, nt, umax, vmax, k, res, it, dtv, bad = c
                    up, vp, p, t, nt, umax, vmax, res, it, dtv = step(
                        up, vp, p, t, nt, umax, vmax
                    )
                    # maxima stay native-dtype in the carry (the CFL
                    # scalars); metrics_step's f32 copies feed only the
                    # sentinel
                    res, it, dtv, _um, _vm, bad = _tm.metrics_step(
                        bad, nt, res, it, dtv, umax, vmax)
                    return (up, vp, p, t, nt, umax, vmax, k + 1,
                            res, it, dtv, bad)

            (up, vp, p, t, nt, umax, vmax, _k,
             res, it, dtv, bad) = lax.while_loop(
                cond, body,
                (up, vp, p, t, nt, umax, vmax, jnp.asarray(0, jnp.int32),
                 m[_tm.M_RES], m[_tm.M_IT], m[_tm.M_DT], m[_tm.M_BAD]),
            )
            m = _tm.metrics_pack(res, it, dtv, umax, vmax, 0.0, bad)
            return (unpad(up), unpad(vp), unpad(p) if folded else p,
                    t, nt, m)

        return chunk_fn_metrics if metrics else chunk_fn

    def _build_chunk(self, backend: str = "auto", te_arg: bool = False):
        # telemetry is a trace-time decision, like utils/flags.py: unset
        # means the chunk below is byte-identical to the uninstrumented
        # program (asserted by tests/test_telemetry.py). Field-fault
        # injection (PAMPI_FAULTS nan/inf clauses) follows the same
        # contract via self._field_faults — set by __init__/_rebuild_chunk,
        # NOT taken here (the pallas fallback rebuild reuses the armed
        # generation; only a recovery rebuild advances it).
        # te_arg=True (the fleet's per-lane te carry) makes the end time a
        # TRACED trailing argument of the chunk instead of a baked
        # constant; the default is the byte-identical historical trace.
        metrics = _tm.enabled()
        self._metrics = metrics
        from ..utils.dispatch import resolve_chunk_fuse

        chunk = self.param.tpu_chunk or self.CHUNK
        kfuse = resolve_chunk_fuse(self.param, "ns2d_chunk_fuse", chunk)
        fused = self._build_fused_chunk(backend, metrics=metrics,
                                        te_arg=te_arg, kfuse=kfuse)
        self._fused = fused is not None
        if fused is not None:
            return fused
        step = self._build_step(backend, instrumented=metrics)
        te_static = self.param.te

        def chunk_fn(u, v, p, t, nt, *te_in):
            te = te_in[0] if te_in else te_static

            def cond(c):
                _, _, _, t, _, k = c
                return jnp.logical_and(t <= te, k < chunk)

            if kfuse > 1:
                # K-step fused trips (ISSUE 17): one scan advances K
                # gated steps (frozen identity past te) per while trip
                def kblock(c, _):
                    def live(c):
                        return step(*c)

                    return lax.cond(c[3] <= te, live, lambda c: c, c), None

                def body(c):
                    u, v, p, t, nt, k = c
                    (u, v, p, t, nt), _ = lax.scan(
                        kblock, (u, v, p, t, nt), None, length=kfuse)
                    return u, v, p, t, nt, k + kfuse
            else:
                def body(c):
                    u, v, p, t, nt, k = c
                    u, v, p, t, nt = step(u, v, p, t, nt)
                    return u, v, p, t, nt, k + 1

            u, v, p, t, nt, _ = lax.while_loop(
                cond, body, (u, v, p, t, nt, jnp.asarray(0, jnp.int32))
            )
            return u, v, p, t, nt

        def chunk_fn_metrics(u, v, p, t, nt, m, *te_in):
            # the telemetry twin of chunk_fn: the instrumented step exposes
            # the solve's discarded res/it plus dt; |u|/|v| maxima are the
            # two extra fused reductions this path did not already carry
            te = te_in[0] if te_in else te_static

            def cond(c):
                return jnp.logical_and(c[3] <= te, c[5] < chunk)

            if kfuse > 1:
                # per-step metrics_step with the POST-step nt inside the
                # live branch — divergence keeps step resolution in the
                # K-block
                def kblock(c, _):
                    def live(c):
                        u, v, p, t, nt, res, it, dtv, um, vm, bad = c
                        u, v, p, t, nt, res, it, dtv = step(u, v, p, t, nt)
                        res, it, dtv, um, vm, bad = _tm.metrics_step(
                            bad, nt, res, it, dtv,
                            ops.max_element(u), ops.max_element(v))
                        return u, v, p, t, nt, res, it, dtv, um, vm, bad

                    return lax.cond(c[3] <= te, live, lambda c: c, c), None

                def body(c):
                    u, v, p, t, nt, k, res, it, dtv, um, vm, bad = c
                    (u, v, p, t, nt, res, it, dtv, um, vm, bad), _ = \
                        lax.scan(
                            kblock,
                            (u, v, p, t, nt, res, it, dtv, um, vm, bad),
                            None, length=kfuse)
                    return (u, v, p, t, nt, k + kfuse,
                            res, it, dtv, um, vm, bad)
            else:
                def body(c):
                    u, v, p, t, nt, k, res, it, dtv, um, vm, bad = c
                    u, v, p, t, nt, res, it, dtv = step(u, v, p, t, nt)
                    res, it, dtv, um, vm, bad = _tm.metrics_step(
                        bad, nt, res, it, dtv,
                        ops.max_element(u), ops.max_element(v))
                    return u, v, p, t, nt, k + 1, res, it, dtv, um, vm, bad

            (u, v, p, t, nt, _k, res, it, dtv, um, vm, bad) = lax.while_loop(
                cond, body,
                (u, v, p, t, nt, jnp.asarray(0, jnp.int32),
                 m[_tm.M_RES], m[_tm.M_IT], m[_tm.M_DT],
                 m[_tm.M_UMAX], m[_tm.M_VMAX], m[_tm.M_BAD]),
            )
            return u, v, p, t, nt, _tm.metrics_pack(
                res, it, dtv, um, vm, 0.0, bad)

        return chunk_fn_metrics if metrics else chunk_fn

    # -- driver API ----------------------------------------------------
    def _rebuild_chunk(self):
        """Re-trace the chunk against the solver's CURRENT attributes
        (backend, recovery dt clamp) — the rollback-recovery rebuild hook
        (models/_driver.RingRecovery). Advances the fault-injection
        generation: single-charge corruption clauses are spent, so the
        recovered run re-drives clean."""
        self._field_faults = _fi.take_field_faults()
        self._chunk_fn = jax.jit(self._build_chunk(backend=self._backend))
        return self._chunk_fn

    def initial_state(self) -> tuple:
        """The chunk-call state tuple matching the built chunk's arity —
        (u, v, p, t, nt), plus the in-band telemetry metrics vector when
        PAMPI_TELEMETRY was set at build time. The measurement tools
        (bench.py, tools/northstar.py) call the chunk with this instead of
        hand-building the tuple, so the telemetry arity cannot drift."""
        time_dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
        state = (self.u, self.v, self.p,
                 jnp.asarray(self.t, time_dtype),
                 jnp.asarray(self.nt, jnp.int32))
        if getattr(self, "_metrics", False):
            state = state + (_tm.metrics_init(),)
        return state

    # -- elastic-checkpoint contract (utils/checkpoint.save_elastic) ---
    def global_shape(self) -> tuple:
        return (self.jmax + 2, self.imax + 2)

    def global_fields(self) -> dict:
        """Reference-layout global fields: single-device fields ARE the
        global layout (interior + ghost ring)."""
        return {f: np.asarray(getattr(self, f)) for f in ("u", "v", "p")}

    def set_global_fields(self, fields: dict) -> None:
        for f, arr in fields.items():
            cur = getattr(self, f)
            setattr(self, f, jnp.asarray(arr, cur.dtype))

    def run(self, progress: bool = True, on_sync=None) -> None:
        """Advance from t to te. `on_sync(self)` fires at each host sync
        (every CHUNK device steps) — the checkpoint hook point. Loop +
        retry/rollback protocol live in models/_driver.py."""
        from ._driver import (
            coord_ckpt_cadence,
            drive_chunks,
            make_recovery,
            pallas_retry,
        )

        bar = Progress(self.param.te, enabled=progress and not _flags.verbose())
        state = self.initial_state()
        rec = _tm.ChunkRecorder("ns2d", self.nt) if self._metrics else None
        recover = make_recovery(self, "ns2d", time_index=3, recorder=rec)

        def publish(s):
            self.u, self.v, self.p = s[0], s[1], s[2]
            self.t, self.nt = float(s[3]), int(s[4])

        def on_state(s):
            if rec is not None:
                rec.update(float(s[3]), int(s[4]), s[5])
            if recover is not None:
                recover.capture(s)
            if on_sync is not None:
                publish(s)
                on_sync(self)

        if recover is not None:
            recover.capture(state)  # first-chunk divergence is recoverable
        from ..parallel.coordinator import make_coordinator
        from ..utils import xprof as _xprof

        # single-device default is the uncoordinated historical loop;
        # tpu_coord on forces the 1-rank protocol path (seam identity)
        coord = make_coordinator(self.param, "ns2d")
        ckpt_every, on_ckpt = coord_ckpt_cadence(self, coord, publish)
        nt0 = self.nt
        with _xprof.capture("ns2d", steps=lambda: self.nt - nt0):
            state = drive_chunks(
                state, self._chunk_fn, self.param.te, 3, bar,
                pallas_retry(
                    self, "pressure solve",
                    restore_after=self.param.tpu_retry_replenish,
                ),
                on_state, lookahead=self.param.tpu_lookahead,
                replenish_after=self.param.tpu_retry_replenish,
                recover=recover, coordinator=coord,
                ckpt_every=ckpt_every, on_ckpt=on_ckpt, family="ns2d",
                ledger=getattr(self, "_fault_ledger", None))
            publish(state)

    def write_result(
        self, pressure_path: str = "pressure.dat", velocity_path: str = "velocity.dat"
    ) -> None:
        write_pressure(np.asarray(self.p), self.dx, self.dy, pressure_path)
        write_velocity(
            np.asarray(self.u), np.asarray(self.v), self.dx, self.dy, velocity_path
        )
