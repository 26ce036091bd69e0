"""NS-3D incompressible Navier-Stokes time-stepper (assignment-6 capability,
COMPLETED: the reference ships its distributed comm bodies as skeletons).

Pipeline parity with /root/reference/assignment-6/src/main.c:50-67:
computeTimestep → setBoundaryConditions → setSpecialBoundaryCondition →
computeFG → computeRHS → solve → adaptUV, t += dt while t <= te. (Unlike the
2-D driver there is NO normalizePressure in the loop.)

The pressure solve is 3-D red-black SOR (solve, solver.c:175-297): pass 0
visits (i+j+k) odd cells, pass 1 even (the reference's ksw/jsw/isw
checkerboard), factor = ω/2·(dx²dy²dz²)/(dy²dz²+dx²dz²+dx²dy²), 6-face
Neumann ghost copies after both passes, residual normalized by
imax·jmax·kmax. DOCUMENTED DEVIATION: the reference never resets `res`
inside the while loop (solver.c:203-230) — an accumulation bug flagged in
SURVEY.md §2.1; we reset per iteration (and the parity oracle used by the
tests is the reference built with the same one-line fix).

Time loop runs on-device in host-synced chunks like NS-2D.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops import ns3d as ops
from ..utils import faultinject as _fi
from ..utils import flags as _flags
from ..utils import telemetry as _tm
from ._driver import clamped_dt
from ..utils.grid import Grid
from ..utils.params import Parameter, validate_obstacle_layout
from ..utils.precision import resolve_dtype
from ..utils.progress import Progress
from ..utils.vtkio import VtkWriter


def checkerboard_mask_3d(kmax, jmax, imax, parity, dtype):
    """Interior mask where (i+j+k) % 2 == parity (1-based indices). Pass 0
    of the reference's sweep visits parity 1 (odd), pass 1 parity 0."""
    kk = jnp.arange(1, kmax + 1, dtype=jnp.int32)[:, None, None]
    jj = jnp.arange(1, jmax + 1, dtype=jnp.int32)[None, :, None]
    ii = jnp.arange(1, imax + 1, dtype=jnp.int32)[None, None, :]
    return (((ii + jj + kk) % 2) == parity).astype(dtype)


def neumann_faces_3d(p):
    """6-face pressure ghost copy (solve's commIsBoundary blocks,
    solver.c:233-279); tangential ranges [1:-1], edges/corners untouched."""
    p = p.at[0, 1:-1, 1:-1].set(p[1, 1:-1, 1:-1])  # front
    p = p.at[-1, 1:-1, 1:-1].set(p[-2, 1:-1, 1:-1])  # back
    p = p.at[1:-1, 0, 1:-1].set(p[1:-1, 1, 1:-1])  # bottom
    p = p.at[1:-1, -1, 1:-1].set(p[1:-1, -2, 1:-1])  # top
    p = p.at[1:-1, 1:-1, 0].set(p[1:-1, 1:-1, 1])  # left
    p = p.at[1:-1, 1:-1, -1].set(p[1:-1, 1:-1, -2])  # right
    return p


def interior_residual_3d(p, rhs, idx2, idy2, idz2):
    """Pointwise residual r = rhs - lap(p) on the interior — the single home
    of the 7-point stencil expression (sor_pass_3d and ops/multigrid share
    it)."""
    lap = (
        (p[1:-1, 1:-1, 2:] - 2.0 * p[1:-1, 1:-1, 1:-1] + p[1:-1, 1:-1, :-2]) * idx2
        + (p[1:-1, 2:, 1:-1] - 2.0 * p[1:-1, 1:-1, 1:-1] + p[1:-1, :-2, 1:-1]) * idy2
        + (p[2:, 1:-1, 1:-1] - 2.0 * p[1:-1, 1:-1, 1:-1] + p[:-2, 1:-1, 1:-1]) * idz2
    )
    return rhs[1:-1, 1:-1, 1:-1] - lap


def sor_pass_3d(p, rhs, mask, factor, idx2, idy2, idz2):
    """One masked half-sweep of the 7-point stencil (solver.c:210-229)."""
    r = interior_residual_3d(p, rhs, idx2, idy2, idz2) * mask
    p = p.at[1:-1, 1:-1, 1:-1].add(-factor * r)
    return p, jnp.sum(r * r)


def sor_coefficients_3d(dx, dy, dz, omega):
    """(factor, idx2, idy2, idz2) of the 3-D SOR update (solver.c:186-196) —
    the single source of truth for both the single-device and distributed
    solvers."""
    dx2, dy2, dz2 = dx * dx, dy * dy, dz * dz
    factor = omega * 0.5 * (dx2 * dy2 * dz2) / (dy2 * dz2 + dx2 * dz2 + dx2 * dy2)
    return factor, 1.0 / dx2, 1.0 / dy2, 1.0 / dz2


def write_vtk_result(param, grid, fields, path=None, fmt: str = "ascii") -> None:
    """VTK output (main.c:100-106): scalar pressure + vector velocity.
    fields = (ug, vg, wg, pg) cell-centered global arrays."""
    ug, vg, wg, pg = fields
    problem = param.name.replace("3d", "")
    writer = VtkWriter(problem, grid, fmt=fmt, path=path)
    writer.scalar("pressure", pg)
    writer.vector("velocity", ug, vg, wg)
    writer.close()


def _pallas_why_not_3d(backend: str, dtype):
    """models/poisson._pallas_why_not with the 3-D kernel's probe."""
    from .poisson import _pallas_why_not

    def probe():
        from ..ops import sor3d_pallas as sp3

        return sp3.pltpu is not None and sp3.probe_pallas_3d()

    return _pallas_why_not(backend, dtype, probe=probe)


def _use_pallas_3d(backend: str, dtype) -> bool:
    return _pallas_why_not_3d(backend, dtype) is None


def make_pressure_solve_3d(imax, jmax, kmax, dx, dy, dz, omega, eps, itermax,
                           dtype, backend: str = "auto", n_inner: int = 1,
                           solver: str = "sor", layout: str = "auto",
                           stall_rtol=None, mg_fused: str = "off"):
    """Convergence loop for the 3-D pressure solve. solver="sor" (default,
    the reference's algorithm): backend="auto" dispatches to the fused Pallas
    kernel (ops/sor3d_pallas.py) on a real TPU chip and to the jnp half-sweep
    composition otherwise; both carry (p, res, it) through a
    `lax.while_loop`. Under pallas the loop carries the PADDED array (one pad
    before, one unpad after — no per-iteration layout conversion); with
    n_inner > 1 each loop step runs n_inner red-black iterations in one HBM
    sweep and observes the last one's residual, so `it` advances by n_inner
    per step (honest iteration accounting). solver="mg": geometric multigrid
    V-cycles (ops/multigrid.py), same stopping contract, `it` counts
    cycles."""
    if solver == "mg":
        from ..ops.multigrid import make_mg_solve_3d

        return make_mg_solve_3d(imax, jmax, kmax, dx, dy, dz, eps, itermax,
                                dtype, stall_rtol=stall_rtol,
                                backend=backend, fused=mg_fused)
    if solver == "fft":
        from ..ops.dctpoisson import make_dct_solve_3d

        return make_dct_solve_3d(imax, jmax, kmax, dx, dy, dz, dtype)
    if solver != "sor":
        raise ValueError(
            f"NS pressure solve supports sor|mg|fft, got {solver!r} "
            "(sor_lex/sor_rba are Poisson-only oracle modes)"
        )
    norm = float(imax * jmax * kmax)
    epssq = eps * eps

    if layout not in ("auto", "checkerboard", "octants"):
        raise ValueError(
            f"3-D SOR layout must be auto|checkerboard|octants, got "
            f"{layout!r} (quarters is the 2-D layout)"
        )
    from ..utils.dispatch import record

    why = _pallas_why_not_3d(backend, dtype)
    use_pallas = why is None
    even = imax % 2 == 0 and jmax % 2 == 0 and kmax % 2 == 0
    if layout == "octants" and not even:
        raise ValueError("octant layout needs even imax, jmax, kmax")
    if use_pallas and layout in ("auto", "octants") and even:
        # the OCTANT layout (ops/sor_octants.py): 4.9× the checkerboard
        # kernel at 128³ f32 on v5e (0.257 vs 1.25 ms/iter, k=4)
        from ..ops import sor3d_pallas as sp3

        bko = sp3.pick_block_k_octants(kmax, jmax, imax, dtype, n_inner)
        degenerate = sp3.block_k_octants_degenerate(
            bko, kmax, jmax, imax, dtype, n_inner
        )
        if not degenerate:
            rb_iter, bko, _h = sp3.make_rb_iter_tblock_3d_octants(
                imax, jmax, kmax, dx, dy, dz, omega, dtype,
                n_inner=n_inner, block_k=bko,
            )
            if rb_iter is not None:
                record("sor3d", f"pallas_octants (n_inner={n_inner})")
                return sp3.make_octants_solve_loop(
                    rb_iter, bko, n_inner, norm, eps, itermax,
                    kmax, jmax, imax, dtype,
                )
        elif layout == "octants":
            raise ValueError(
                "octant layout: VMEM budget degenerates block_k at this "
                "in-plane size; use layout=auto or checkerboard"
            )
    if use_pallas and backend != "pallas":
        from ..ops import sor3d_pallas as sp3

        # in-plane size so large the VMEM budget forces block_k below the
        # halo depth: the kernel would recompute halos >3x over and likely
        # overflow VMEM — the jnp path is the better program
        bk = sp3.pick_block_k(kmax, jmax, imax, dtype, n_inner)
        if sp3.block_k_degenerate(bk, kmax, n_inner):
            use_pallas, why = False, "block_k degenerate"

    if use_pallas:
        from ..ops import sor3d_pallas as sp3

        rb_iter, block_k = sp3.make_rb_iter_tblock_3d(
            imax, jmax, kmax, dx, dy, dz, omega, dtype, n_inner=n_inner
        )
        if rb_iter is None:
            raise ValueError("pallas 3-D backend unavailable")
        record("sor3d", f"pallas_tblock (n_inner={n_inner})")
        return sp3.make_tblock_solve_loop(
            rb_iter, block_k, n_inner, norm, eps, itermax,
            kmax, jmax, imax, dtype,
        )

    record("sor3d", f"jnp ({why})")
    factor, idx2, idy2, idz2 = sor_coefficients_3d(dx, dy, dz, omega)
    odd = checkerboard_mask_3d(kmax, jmax, imax, 1, dtype)
    even = checkerboard_mask_3d(kmax, jmax, imax, 0, dtype)

    def solve(p, rhs):
        def cond(c):
            _, res, it = c
            return jnp.logical_and(res >= epssq, it < itermax)

        def body(c):
            p, _, it = c
            p, r0 = sor_pass_3d(p, rhs, odd, factor, idx2, idy2, idz2)
            p, r1 = sor_pass_3d(p, rhs, even, factor, idx2, idy2, idz2)
            p = neumann_faces_3d(p)
            if _flags.debug():
                jax.debug.print("{} Residuum: {}", it, (r0 + r1) / norm)
            return p, (r0 + r1) / norm, it + 1

        return lax.while_loop(
            cond, body, (p, jnp.asarray(1.0, dtype), jnp.asarray(0, jnp.int32))
        )

    return solve


class NS3DSolver:
    """Driver-facing NS-3D solver (≙ assignment-6 Solver struct + main loop)."""

    CHUNK = 32

    def __init__(self, param: Parameter, dtype=None):
        from ..utils.dispatch import resolve_solver

        param = resolve_solver(param, obstacles=bool(param.obstacles.strip()))
        if dtype is None:
            dtype = resolve_dtype(param.tpu_dtype,
                                  record_key="ns3d_dtype")
        self.param = param
        self.dtype = dtype
        self.grid = Grid(
            imax=param.imax,
            jmax=param.jmax,
            kmax=param.kmax,
            xlength=param.xlength,
            ylength=param.ylength,
            zlength=param.zlength,
        )
        g = self.grid
        shape = (g.kmax + 2, g.jmax + 2, g.imax + 2)
        self.u = jnp.full(shape, param.u_init, dtype)
        self.v = jnp.full(shape, param.v_init, dtype)
        self.w = jnp.full(shape, param.w_init, dtype)
        self.p = jnp.full(shape, param.p_init, dtype)
        inv_sqr_sum = 1.0 / g.dx**2 + 1.0 / g.dy**2 + 1.0 / g.dz**2
        self.dt_bound = 0.5 * param.re / inv_sqr_sum
        self.t = 0.0
        self.nt = 0
        self._backend = "auto"
        self._fused = False  # set by _build_chunk (fused-phase dispatch)
        self._dt_scale = 1.0  # recovery dt clamp (models/_driver.clamped_dt)
        # flag-field obstacles (ops/obstacle3d.py): static geometry -> static
        # masks baked into the traced step as constants (branch-free)
        if param.obstacles.strip():
            if param.tpu_solver == "fft":
                raise ValueError(
                    "tpu_solver fft cannot solve obstacle flag fields (the "
                    "stencil is not constant-coefficient); use sor or mg"
                )
            validate_obstacle_layout(param.tpu_sor_layout)
            from ..ops import obstacle3d as obst3

            fluid = obst3.build_fluid_3d(
                g.imax, g.jmax, g.kmax, g.dx, g.dy, g.dz, param.obstacles
            )
            self.masks = obst3.make_masks_3d(
                fluid, g.dx, g.dy, g.dz, param.omg, dtype
            )
        else:
            self.masks = None
        t0 = time.perf_counter()
        # fault-injection generation: taken here and in _rebuild_chunk
        # only (see models/ns2d.py for the pallas-fallback rationale)
        self._field_faults = _fi.take_field_faults()
        self._chunk_fn = jax.jit(self._build_chunk())
        from ..utils import dispatch as _dispatch

        _tm.emit("build", family="ns3d",
                 grid=[g.kmax, g.jmax, g.imax],
                 trace_wall_s=round(time.perf_counter() - t0, 3),
                 phases=_dispatch.last("ns3d_phases"))

    def _uses_pallas(self) -> bool:
        if self._fused:
            return True  # the fused step-phase pair is a pallas kernel
        if self.param.tpu_solver == "fft":
            return False  # fft chunks contain no pallas kernel
        # sor AND mg go through the probe: mg's fine-level smoother
        # dispatches the 3-D tblock kernel on large levels (round 4)
        return _use_pallas_3d(self._backend, self.dtype)

    def _make_solve(self, backend: str):
        """The 3-D pressure-solve closure for one backend — shared by the
        jnp step chain and the fused-phase chunk."""
        param = self.param
        g = self.grid
        dtype = self.dtype
        dx, dy, dz = g.dx, g.dy, g.dz
        masks = self.masks
        if masks is not None and param.tpu_solver == "mg":
            # 3-D obstacle multigrid (round 4): rediscretized
            # eps-coefficient operator per level, exact dense bottom
            from ..ops.multigrid import make_obstacle_mg_solve_3d

            solve = make_obstacle_mg_solve_3d(
                g.imax, g.jmax, g.kmax, dx, dy, dz,
                param.eps, param.itermax, masks, dtype,
                stall_rtol=param.tpu_mg_stall_rtol, backend=backend,
                fused=param.tpu_mg_fused,
            )
        elif masks is not None:
            from ..ops.obstacle3d import make_obstacle_solver_fn_3d

            solve = make_obstacle_solver_fn_3d(
                g.imax, g.jmax, g.kmax, dx, dy, dz,
                param.eps, param.itermax, masks, dtype,
                backend=backend, n_inner=param.tpu_sor_inner,
            )
        else:
            solve = make_pressure_solve_3d(
                g.imax, g.jmax, g.kmax, dx, dy, dz,
                param.omg, param.eps, param.itermax, dtype,
                backend=backend, n_inner=param.tpu_sor_inner,
                solver=param.tpu_solver,
                layout=param.tpu_sor_layout,
                stall_rtol=param.tpu_mg_stall_rtol,
                mg_fused=param.tpu_mg_fused,
            )
        return solve

    def _build_step(self, backend: str = "auto", instrumented: bool = False):
        """One traced timestep. instrumented=True returns the SAME pipeline
        with the solve's discarded outputs exposed —
        (u, v, w, p, t, nt, res, it, dt) — the telemetry chunk's source
        (the NS-2D convention, models/ns2d.py)."""
        param = self.param
        g = self.grid
        dtype = self.dtype
        dx, dy, dz = g.dx, g.dy, g.dz
        masks = self.masks
        solve = self._make_solve(backend)
        bcs = {
            "top": param.bcTop,
            "bottom": param.bcBottom,
            "left": param.bcLeft,
            "right": param.bcRight,
            "front": param.bcFront,
            "back": param.bcBack,
        }
        adaptive = param.tau > 0.0
        problem = param.name.replace("3d", "")
        dt_scale = self._dt_scale  # 1.0 = identity (recovery rebuilds clamp)
        faults = getattr(self, "_field_faults", ())

        def step(u, v, w, p, t, nt):
            u, v, w, p = _fi.apply_field_faults(faults, nt, u=u, v=v, w=w,
                                                p=p)
            if adaptive:
                dt = ops.compute_timestep_3d(
                    u, v, w, jnp.asarray(self.dt_bound, dtype), dx, dy, dz, param.tau
                )
            else:
                dt = jnp.asarray(param.dt, dtype)
            dt = clamped_dt(dt, dt_scale)
            u, v, w = ops.set_boundary_conditions_3d(u, v, w, bcs)
            if problem == "dcavity":
                u = ops.set_special_bc_dcavity_3d(u)
            elif problem == "canal":
                u = ops.set_special_bc_canal_3d(u)
            if masks is not None:
                from ..ops.obstacle3d import (
                    adapt_uvw_obstacle,
                    apply_obstacle_velocity_bc_3d,
                    mask_fgh,
                )

                u, v, w = apply_obstacle_velocity_bc_3d(u, v, w, masks)
            f, g_, h = ops.compute_fgh(
                u, v, w, dt, param.re, param.gx, param.gy, param.gz,
                param.gamma, dx, dy, dz,
            )
            if masks is not None:
                f, g_, h = mask_fgh(f, g_, h, u, v, w, masks)
            rhs = ops.compute_rhs(f, g_, h, dt, dx, dy, dz)
            p, _res, _it = solve(p, rhs)
            if masks is not None:
                u, v, w = adapt_uvw_obstacle(
                    u, v, w, f, g_, h, p, dt, dx, dy, dz, masks
                )
            else:
                u, v, w = ops.adapt_uvw(u, v, w, f, g_, h, p, dt, dx, dy, dz)
            time_dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
            t_next = t + dt.astype(time_dtype)
            if _flags.verbose():
                # printed AFTER t += dt, matching A6 main.c:58-62
                jax.debug.print("TIME {} , TIMESTEP {}", t_next, dt)
            if instrumented:
                return u, v, w, p, t_next, nt + 1, _res, _it, dt
            return u, v, w, p, t_next, nt + 1

        return step

    def _build_fused_chunk(self, backend: str, metrics: bool = False,
                           te_arg: bool = False, kfuse: int = 1):
        """The 3-D fused-phase chunk (ops/ns3d_fused.py): the non-solve
        phases run as two Pallas kernels around the solve, the loop carries
        u/v/w in the padded layout plus the running (umax, vmax, wmax),
        and the timestep is scalar math (ops/ns3d.cfl_dt_3d). None when the
        fused path is not dispatched — the caller falls back to the jnp
        chunk. Obstacle flag fields compose in-kernel (the 2-D template):
        the global flag rides as a baked padded constant."""
        from ..ops.ns3d_fused import probe_fused_3d
        from ..utils.dispatch import record, resolve_fuse_phases

        param = self.param
        if not resolve_fuse_phases(
            param, backend, self.dtype, probe_fused_3d, "ns3d_phases",
        ):
            return None
        from ..ops import ns3d_fused as nf3

        g = self.grid
        dtype = self.dtype
        dx, dy, dz = g.dx, g.dy, g.dz
        try:
            pre, post, pad3, unpad3, _h = nf3.make_fused_step_3d(
                param, g.kmax, g.jmax, g.imax, dx, dy, dz, dtype,
                fluid=None if self.masks is None else self.masks.fluid,
            )
        except ValueError as exc:  # VMEM-infeasible geometry
            record("ns3d_phases", f"jnp ({exc})")
            return None
        solve = self._make_solve(backend)
        adaptive = param.tau > 0.0
        dt_scale = self._dt_scale  # 1.0 = identity (recovery rebuilds clamp)
        faults = getattr(self, "_field_faults", ())
        te_static = param.te
        chunk = param.tpu_chunk or self.CHUNK
        offs = jnp.zeros((3,), jnp.int32)
        dt_bound = jnp.asarray(self.dt_bound, dtype)
        time_dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32

        def step(up, vp, wp, p, t, nt, umax, vmax, wmax):
            up, vp, wp, p = _fi.apply_field_faults(faults, nt, u=up, v=vp,
                                                   w=wp, p=p)
            if adaptive:
                dt = ops.cfl_dt_3d(umax, vmax, wmax, dt_bound, dx, dy, dz,
                                   param.tau)
            else:
                dt = jnp.asarray(param.dt, dtype)
            dt = clamped_dt(dt, dt_scale)
            dt11 = jnp.full((1, 1), dt, dtype)
            up, vp, wp, fp, gp, hp, rhsp = pre(offs, dt11, up, vp, wp)
            rhs = unpad3(rhsp)
            p, _res, _it = solve(p, rhs)
            up, vp, wp, umax, vmax, wmax = post(
                offs, dt11, up, vp, wp, fp, gp, hp, pad3(p)
            )
            t_next = t + dt.astype(time_dtype)
            if _flags.verbose():
                jax.debug.print("TIME {} , TIMESTEP {}", t_next, dt)
            if metrics:
                return (up, vp, wp, p, t_next, nt + 1, umax, vmax, wmax,
                        _res, _it, dt)
            return up, vp, wp, p, t_next, nt + 1, umax, vmax, wmax

        def chunk_fn(u, v, w, p, t, nt, *te_in):
            # te_arg builds take the end time as a TRACED trailing arg
            # (the fleet's per-lane te carry); the default closes over
            # the baked constant — the byte-identical historical trace
            te = te_in[0] if te_in else te_static
            up, vp, wp = pad3(u), pad3(v), pad3(w)
            umax = jnp.max(jnp.abs(u))
            vmax = jnp.max(jnp.abs(v))
            wmax = jnp.max(jnp.abs(w))

            def cond(c):
                return jnp.logical_and(c[4] <= te, c[9] < chunk)

            if kfuse > 1:
                # K-step fused trips (ISSUE 17): one scan advances K
                # gated steps (frozen identity past te) per while trip
                def kblock(c, _):
                    def live(c):
                        return step(*c)

                    return lax.cond(c[4] <= te, live, lambda c: c, c), None

                def body(c):
                    up, vp, wp, p, t, nt, um, vm, wm, k = c
                    (up, vp, wp, p, t, nt, um, vm, wm), _ = lax.scan(
                        kblock, (up, vp, wp, p, t, nt, um, vm, wm), None,
                        length=kfuse)
                    return up, vp, wp, p, t, nt, um, vm, wm, k + kfuse
            else:
                def body(c):
                    up, vp, wp, p, t, nt, um, vm, wm, k = c
                    up, vp, wp, p, t, nt, um, vm, wm = step(
                        up, vp, wp, p, t, nt, um, vm, wm
                    )
                    return up, vp, wp, p, t, nt, um, vm, wm, k + 1

            up, vp, wp, p, t, nt, _um, _vm, _wm, _k = lax.while_loop(
                cond, body,
                (up, vp, wp, p, t, nt, umax, vmax, wmax,
                 jnp.asarray(0, jnp.int32)),
            )
            return unpad3(up), unpad3(vp), unpad3(wp), p, t, nt

        def chunk_fn_metrics(u, v, w, p, t, nt, m, *te_in):
            # the telemetry twin: the carried CFL maxima and the solve's
            # res/it pack into the in-band vector at the chunk boundary
            te = te_in[0] if te_in else te_static
            up, vp, wp = pad3(u), pad3(v), pad3(w)
            umax = jnp.max(jnp.abs(u))
            vmax = jnp.max(jnp.abs(v))
            wmax = jnp.max(jnp.abs(w))

            def cond(c):
                return jnp.logical_and(c[4] <= te, c[9] < chunk)

            if kfuse > 1:
                # per-step metrics_step (POST-step nt) inside the live
                # branch — divergence keeps step resolution in the K-block
                def kblock(c, _):
                    def live(c):
                        (up, vp, wp, p, t, nt, um, vm, wm,
                         res, it, dtv, bad) = c
                        (up, vp, wp, p, t, nt, um, vm, wm,
                         res, it, dtv) = step(up, vp, wp, p, t, nt,
                                              um, vm, wm)
                        res, it, dtv, _u, _v, _w, bad = _tm.metrics_step(
                            bad, nt, res, it, dtv, um, vm, wm)
                        return (up, vp, wp, p, t, nt, um, vm, wm,
                                res, it, dtv, bad)

                    return lax.cond(c[4] <= te, live, lambda c: c, c), None

                def body(c):
                    (up, vp, wp, p, t, nt, um, vm, wm, k,
                     res, it, dtv, bad) = c
                    (up, vp, wp, p, t, nt, um, vm, wm,
                     res, it, dtv, bad), _ = lax.scan(
                        kblock,
                        (up, vp, wp, p, t, nt, um, vm, wm,
                         res, it, dtv, bad),
                        None, length=kfuse)
                    return (up, vp, wp, p, t, nt, um, vm, wm, k + kfuse,
                            res, it, dtv, bad)
            else:
                def body(c):
                    (up, vp, wp, p, t, nt, um, vm, wm, k,
                     res, it, dtv, bad) = c
                    (up, vp, wp, p, t, nt, um, vm, wm,
                     res, it, dtv) = step(up, vp, wp, p, t, nt, um, vm, wm)
                    # maxima stay native-dtype in the carry (the CFL
                    # scalars)
                    res, it, dtv, _u, _v, _w, bad = _tm.metrics_step(
                        bad, nt, res, it, dtv, um, vm, wm)
                    return (up, vp, wp, p, t, nt, um, vm, wm, k + 1,
                            res, it, dtv, bad)

            (up, vp, wp, p, t, nt, um, vm, wm, _k,
             res, it, dtv, bad) = lax.while_loop(
                cond, body,
                (up, vp, wp, p, t, nt, umax, vmax, wmax,
                 jnp.asarray(0, jnp.int32),
                 m[_tm.M_RES], m[_tm.M_IT], m[_tm.M_DT], m[_tm.M_BAD]),
            )
            return (unpad3(up), unpad3(vp), unpad3(wp), p, t, nt,
                    _tm.metrics_pack(res, it, dtv, um, vm, wm, bad))

        return chunk_fn_metrics if metrics else chunk_fn

    def _build_chunk(self, backend: str = "auto", te_arg: bool = False):
        # trace-time telemetry gate (utils/flags.py convention): unset means
        # the chunk below is byte-identical to the uninstrumented program.
        # Field-fault injection reads self._field_faults — set by
        # __init__/_rebuild_chunk, not taken here (see ns2d).
        # te_arg=True makes the end time a traced trailing argument (the
        # fleet's per-lane te carry — see models/ns2d._build_chunk).
        metrics = _tm.enabled()
        self._metrics = metrics
        from ..utils.dispatch import resolve_chunk_fuse

        chunk = self.param.tpu_chunk or self.CHUNK
        kfuse = resolve_chunk_fuse(self.param, "ns3d_chunk_fuse", chunk)
        fused = self._build_fused_chunk(backend, metrics=metrics,
                                        te_arg=te_arg, kfuse=kfuse)
        self._fused = fused is not None
        if fused is not None:
            return fused
        step = self._build_step(backend, instrumented=metrics)
        te_static = self.param.te

        def chunk_fn(u, v, w, p, t, nt, *te_in):
            te = te_in[0] if te_in else te_static

            def cond(c):
                return jnp.logical_and(c[4] <= te, c[6] < chunk)

            if kfuse > 1:
                # K-step fused trips (ISSUE 17): one scan advances K
                # gated steps (frozen identity past te) per while trip
                def kblock(c, _):
                    def live(c):
                        return step(*c)

                    return lax.cond(c[4] <= te, live, lambda c: c, c), None

                def body(c):
                    u, v, w, p, t, nt, k = c
                    (u, v, w, p, t, nt), _ = lax.scan(
                        kblock, (u, v, w, p, t, nt), None, length=kfuse)
                    return u, v, w, p, t, nt, k + kfuse
            else:
                def body(c):
                    u, v, w, p, t, nt, k = c
                    u, v, w, p, t, nt = step(u, v, w, p, t, nt)
                    return u, v, w, p, t, nt, k + 1

            u, v, w, p, t, nt, _ = lax.while_loop(
                cond, body, (u, v, w, p, t, nt, jnp.asarray(0, jnp.int32))
            )
            return u, v, w, p, t, nt

        def chunk_fn_metrics(u, v, w, p, t, nt, m, *te_in):
            te = te_in[0] if te_in else te_static

            def cond(c):
                return jnp.logical_and(c[4] <= te, c[6] < chunk)

            if kfuse > 1:
                # per-step metrics_step (POST-step nt) inside the live
                # branch — divergence keeps step resolution in the K-block
                def kblock(c, _):
                    def live(c):
                        (u, v, w, p, t, nt,
                         res, it, dtv, um, vm, wm, bad) = c
                        u, v, w, p, t, nt, res, it, dtv = step(
                            u, v, w, p, t, nt)
                        res, it, dtv, um, vm, wm, bad = _tm.metrics_step(
                            bad, nt, res, it, dtv, ops.max_element(u),
                            ops.max_element(v), ops.max_element(w))
                        return (u, v, w, p, t, nt,
                                res, it, dtv, um, vm, wm, bad)

                    return lax.cond(c[4] <= te, live, lambda c: c, c), None

                def body(c):
                    u, v, w, p, t, nt, k, res, it, dtv, um, vm, wm, bad = c
                    (u, v, w, p, t, nt,
                     res, it, dtv, um, vm, wm, bad), _ = lax.scan(
                        kblock,
                        (u, v, w, p, t, nt, res, it, dtv, um, vm, wm, bad),
                        None, length=kfuse)
                    return (u, v, w, p, t, nt, k + kfuse,
                            res, it, dtv, um, vm, wm, bad)
            else:
                def body(c):
                    u, v, w, p, t, nt, k, res, it, dtv, um, vm, wm, bad = c
                    u, v, w, p, t, nt, res, it, dtv = step(
                        u, v, w, p, t, nt)
                    res, it, dtv, um, vm, wm, bad = _tm.metrics_step(
                        bad, nt, res, it, dtv, ops.max_element(u),
                        ops.max_element(v), ops.max_element(w))
                    return (u, v, w, p, t, nt, k + 1,
                            res, it, dtv, um, vm, wm, bad)

            (u, v, w, p, t, nt, _k,
             res, it, dtv, um, vm, wm, bad) = lax.while_loop(
                cond, body,
                (u, v, w, p, t, nt, jnp.asarray(0, jnp.int32),
                 m[_tm.M_RES], m[_tm.M_IT], m[_tm.M_DT],
                 m[_tm.M_UMAX], m[_tm.M_VMAX], m[_tm.M_WMAX],
                 m[_tm.M_BAD]),
            )
            return u, v, w, p, t, nt, _tm.metrics_pack(
                res, it, dtv, um, vm, wm, bad)

        return chunk_fn_metrics if metrics else chunk_fn

    def _rebuild_chunk(self):
        """Re-trace the chunk against the solver's CURRENT attributes
        (backend, recovery dt clamp) — the rollback-recovery rebuild hook
        (models/_driver.RingRecovery). Advances the fault-injection
        generation (see models/ns2d._rebuild_chunk)."""
        self._field_faults = _fi.take_field_faults()
        self._chunk_fn = jax.jit(self._build_chunk(backend=self._backend))
        return self._chunk_fn

    def initial_state(self) -> tuple:
        """(u, v, w, p, t, nt[, metrics]) matching the built chunk's arity
        (the NS-2D convention — see models/ns2d.initial_state)."""
        time_dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
        state = (self.u, self.v, self.w, self.p,
                 jnp.asarray(self.t, time_dtype),
                 jnp.asarray(self.nt, jnp.int32))
        if getattr(self, "_metrics", False):
            state = state + (_tm.metrics_init(),)
        return state

    # -- elastic-checkpoint contract (utils/checkpoint.save_elastic) ---
    def global_shape(self) -> tuple:
        g = self.grid
        return (g.kmax + 2, g.jmax + 2, g.imax + 2)

    def global_fields(self) -> dict:
        """Reference-layout global fields (see models/ns2d.global_fields)."""
        return {f: np.asarray(getattr(self, f))
                for f in ("u", "v", "w", "p")}

    def set_global_fields(self, fields: dict) -> None:
        for f, arr in fields.items():
            cur = getattr(self, f)
            setattr(self, f, jnp.asarray(arr, cur.dtype))

    def run(self, progress: bool = True, on_sync=None) -> None:
        bar = Progress(self.param.te, enabled=progress and not _flags.verbose())
        from ._driver import (
            coord_ckpt_cadence,
            drive_chunks,
            make_recovery,
            pallas_retry,
        )

        state = self.initial_state()
        rec = _tm.ChunkRecorder("ns3d", self.nt) if self._metrics else None
        recover = make_recovery(self, "ns3d", time_index=4, recorder=rec)

        def publish(s):
            self.u, self.v, self.w, self.p = s[0], s[1], s[2], s[3]
            self.t, self.nt = float(s[4]), int(s[5])

        def on_state(s):
            if rec is not None:
                rec.update(float(s[4]), int(s[5]), s[6])
            if recover is not None:
                recover.capture(s)
            if on_sync is not None:
                publish(s)
                on_sync(self)

        if recover is not None:
            recover.capture(state)  # first-chunk divergence is recoverable
        from ..parallel.coordinator import make_coordinator
        from ..utils import xprof as _xprof

        # uncoordinated by default; tpu_coord on = the 1-rank protocol
        # path (see models/ns2d.run)
        coord = make_coordinator(self.param, "ns3d")
        ckpt_every, on_ckpt = coord_ckpt_cadence(self, coord, publish)
        nt0 = self.nt
        with _xprof.capture("ns3d", steps=lambda: self.nt - nt0):
            state = drive_chunks(
                state, self._chunk_fn, self.param.te, 4, bar,
                pallas_retry(
                    self, "3-D pressure solve",
                    restore_after=self.param.tpu_retry_replenish,
                ),
                on_state, lookahead=self.param.tpu_lookahead,
                replenish_after=self.param.tpu_retry_replenish,
                recover=recover, coordinator=coord,
                ckpt_every=ckpt_every, on_ckpt=on_ckpt, family="ns3d",
                ledger=getattr(self, "_fault_ledger", None))
            publish(state)

    def collect(self):
        """Cell-centered global fields (≙ commCollectResult's non-MPI path,
        comm.c:386-426): p interior; velocities averaged from staggered faces."""
        u = np.asarray(self.u)
        v = np.asarray(self.v)
        w = np.asarray(self.w)
        p = np.asarray(self.p)
        pg = p[1:-1, 1:-1, 1:-1]
        ug = (u[1:-1, 1:-1, 1:-1] + u[1:-1, 1:-1, :-2]) / 2.0
        vg = (v[1:-1, 1:-1, 1:-1] + v[1:-1, :-2, 1:-1]) / 2.0
        wg = (w[1:-1, 1:-1, 1:-1] + w[:-2, 1:-1, 1:-1]) / 2.0
        return ug, vg, wg, pg

    def write_result(self, path=None, fmt: str = "ascii") -> None:
        write_vtk_result(self.param, self.grid, self.collect(), path, fmt)
