"""Distributed NS-2D: the full time-stepper over a 2-D device mesh.

Capability parity with /root/reference/assignment-5/ex5-nazifkar (the complete
2-D MPI solver: Cartesian decomposition solver.c:406-520, neighbour-collective
exchange :137-165, staggered shift :167-216, Allreduce reductions :651/:677/
:697, rank-gated special BCs :860-880), built TPU-first on the comm layer.

Equivalence policy — EXACT sequential parity, not the reference's relaxed MPI
parity: the reference's distributed solve accepts a trajectory that differs
from its sequential oracle (rank-local lexicographic sweeps with stale halos,
SURVEY.md §3.2). Here every data dependency of the sequential pipeline is
honoured with a halo refresh before the read, so the distributed run equals
the single-device run bitwise (mod float reduction order) on any mesh:

  step start   exchange(u,v)  — maxElement scans ghosts (solver.c:193 quirk);
                                ghosts must hold current neighbour values
  after BCs    exchange(u,v)  — computeFG's stencil reads BC-written wall
                                strips owned by neighbour shards (the 3-D
                                reference does exactly this, solver.c:635-637)
  before RHS   shift(f,'i'), shift(g,'j') — staggered donor edges (≙ commShift)
  in solve     exchange(p) before each half-sweep (red-black needs fresh
                halos per colour), Neumann walls after both
  after solve  exchange(p)   — adaptUV reads p(i+1,j)/p(i,j+1) across shard
                                edges (≙ the closing commExchange, solver.c:288)

State between chunks is the stacked EXTENDED blocks (ghosts included), so
wall-ghost history (BC values, corner init values) survives host syncs
exactly; normalizePressure weights ghost positions only where they are
physical walls, reproducing the sequential full-array mean (solver.c:204).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..ops import ns2d as ops
from ..parallel.comm import (
    master_print,
    CartComm,
    get_offsets,
    halo_exchange,
    halo_exchange_bytes,
    halo_shift,
    reduction,
)
from ..parallel.quarters_dist import (
    pack_ext_to_q,
    q_exchange,
    quarters_dispatch,
    unpack_q_to_ext,
)
from ..parallel.stencil2d import (
    ca_halo,
    ca_inner,
    ca_masks,
    ca_rb_iters,
    ca_supported,
    embed_deep,
    rb_exchange_per_sweep,
    rb_split_iter,
    strip_deep,
    wall_flags,
)
from ..utils import dispatch as _dispatch
from ..utils import faultinject as _fi
from ..utils import flags as _flags
from ..utils import telemetry as _tm
from ..utils import xprof as _xprof
from ._driver import clamped_dt
from ..utils.datio import write_pressure, write_velocity
from ..utils.params import Parameter
from ..utils.precision import resolve_dtype
from ..utils.progress import Progress

NOSLIP, SLIP, OUTFLOW, PERIODIC = 1, 2, 3, 4


def _sel(pred, new, old):
    return jnp.where(pred, new, old)


class NS2DDistSolver:
    """Mesh-parallel NS-2D solver; same .par interface as NS2DSolver."""

    CHUNK = 64

    def __init__(self, param: Parameter, comm: CartComm | None = None, dtype=None):
        self._t0_build = time.perf_counter()
        # telemetry is a trace-time decision (utils/flags.py convention):
        # unset leaves every traced program below byte-identical
        metrics = _tm.enabled()
        self._metrics = metrics
        if dtype is None:
            dtype = resolve_dtype(param.tpu_dtype,
                                  record_key="ns2d_dist_dtype")
        if param.tpu_solver == "sor_lex":
            raise ValueError(
                "tpu_solver sor_lex is the single-device ordering oracle "
                "(tools/northstar.py match4096); distributed runs take "
                "sor|mg|fft"
            )
        self.param = param
        self.dtype = dtype
        self.comm = comm if comm is not None else CartComm(
            ndims=2, extents=(param.jmax, param.imax),
            tiers=param.tpu_mesh_tiers,
        )
        self.imax, self.jmax = param.imax, param.jmax
        self.dx = param.xlength / param.imax
        self.dy = param.ylength / param.jmax
        # ragged pad-with-mask decomposition (parallel/ragged2d.py): any
        # grid runs on any mesh, like the reference's sizeOfRank remainder
        # spread (assignment-6/src/comm.c:19-22)
        self.jl, self.il = self.comm.local_shape(
            (self.jmax, self.imax), ragged=True
        )
        Pj, Pi = self.comm.dims
        self.ragged = (self.jl * Pj != self.jmax) or (self.il * Pi != self.imax)
        param = _dispatch.resolve_solver(
            param, obstacles=bool(param.obstacles.strip()),
            ragged=self.ragged,
        )
        self.param = param
        # round 5 (VERDICT r4 item 2): obstacles now COMPOSE with ragged
        # decompositions — the flag field and the ragged live-mask are both
        # global-coordinate-gated constants, so the same per-shard solver
        # runs either (the reference's remainder ranks run the identical
        # solver, assignment-6/src/comm.c:19-22). mg/fft stay divisible-only
        # (coarsening/diagonalization need exact extents).
        if self.ragged and param.tpu_solver in ("mg", "fft"):
            raise ValueError(
                f"tpu_solver {param.tpu_solver} needs a divisible grid/mesh "
                f"(grid {self.jmax}x{self.imax} on {self.comm.dims}); ragged "
                "pad-with-mask runs use tpu_solver sor (obstacles compose)"
            )
        inv_sqr_sum = 1.0 / (self.dx * self.dx) + 1.0 / (self.dy * self.dy)
        self.dt_bound = 0.5 * param.re / inv_sqr_sum
        self.t = 0.0
        self.nt = 0
        # flag-field obstacles: GLOBAL static geometry; every shard slices
        # its mask blocks inside the kernel (ops/obstacle.shard_masks)
        if param.obstacles.strip():
            if param.tpu_solver == "fft":
                raise ValueError(
                    "tpu_solver fft cannot solve obstacle flag fields (the "
                    "stencil is not constant-coefficient); use sor or mg"
                )
            from ..ops import obstacle as obst

            fluid = obst.build_fluid(
                param.imax, param.jmax, self.dx, self.dy, param.obstacles
            )
            self.masks = obst.make_masks(
                fluid, self.dx, self.dy, param.omg, dtype
            )
        else:
            self.masks = None
        self._dt_scale = 1.0  # recovery dt clamp (models/_driver.clamped_dt)
        # fault-injection generation: taken here and in _rebuild_chunk
        # only (see models/ns2d.py for the rationale)
        self._field_faults = _fi.take_field_faults()
        self._build()
        # extended-block state, stacked over the mesh
        self.u, self.v, self.p = self._init_sm()

    # ------------------------------------------------------------------
    def _build(self):
        comm = self.comm
        param = self.param
        dtype = self.dtype
        metrics = self._metrics  # trace-time telemetry gate (see __init__)
        # field-fault injection + recovery dt clamp: both trace-time, both
        # identity when unarmed (the PAMPI_FAULTS-unset jaxpr contract);
        # the generation is taken by __init__/_rebuild_chunk, not here
        field_faults = self._field_faults
        dt_scale = self._dt_scale
        jl, il = self.jl, self.il
        dx, dy = self.dx, self.dy
        Pj = comm.axis_size("j")
        Pi = comm.axis_size("i")
        idx_dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32

        def walls():
            return wall_flags(comm)

        # -- boundary conditions, wall-gated (setBoundaryConditions) ----
        def set_bcs_divisible(u, v):
            lo_i, hi_i, lo_j, hi_j = walls()
            bc = param
            if bc.bcLeft == NOSLIP:
                u = u.at[1:-1, 0].set(_sel(lo_i, 0.0, u[1:-1, 0]))
                v = v.at[1:-1, 0].set(_sel(lo_i, -v[1:-1, 1], v[1:-1, 0]))
            elif bc.bcLeft == SLIP:
                u = u.at[1:-1, 0].set(_sel(lo_i, 0.0, u[1:-1, 0]))
                v = v.at[1:-1, 0].set(_sel(lo_i, v[1:-1, 1], v[1:-1, 0]))
            elif bc.bcLeft == OUTFLOW:
                u = u.at[1:-1, 0].set(_sel(lo_i, u[1:-1, 1], u[1:-1, 0]))
                v = v.at[1:-1, 0].set(_sel(lo_i, v[1:-1, 1], v[1:-1, 0]))
            if bc.bcRight == NOSLIP:
                u = u.at[1:-1, -2].set(_sel(hi_i, 0.0, u[1:-1, -2]))
                v = v.at[1:-1, -1].set(_sel(hi_i, -v[1:-1, -2], v[1:-1, -1]))
            elif bc.bcRight == SLIP:
                u = u.at[1:-1, -2].set(_sel(hi_i, 0.0, u[1:-1, -2]))
                v = v.at[1:-1, -1].set(_sel(hi_i, v[1:-1, -2], v[1:-1, -1]))
            elif bc.bcRight == OUTFLOW:
                u = u.at[1:-1, -2].set(_sel(hi_i, u[1:-1, -3], u[1:-1, -2]))
                v = v.at[1:-1, -1].set(_sel(hi_i, v[1:-1, -2], v[1:-1, -1]))
            if bc.bcBottom == NOSLIP:
                v = v.at[0, 1:-1].set(_sel(lo_j, 0.0, v[0, 1:-1]))
                u = u.at[0, 1:-1].set(_sel(lo_j, -u[1, 1:-1], u[0, 1:-1]))
            elif bc.bcBottom == SLIP:
                v = v.at[0, 1:-1].set(_sel(lo_j, 0.0, v[0, 1:-1]))
                u = u.at[0, 1:-1].set(_sel(lo_j, u[1, 1:-1], u[0, 1:-1]))
            elif bc.bcBottom == OUTFLOW:
                u = u.at[0, 1:-1].set(_sel(lo_j, u[1, 1:-1], u[0, 1:-1]))
                v = v.at[0, 1:-1].set(_sel(lo_j, v[1, 1:-1], v[0, 1:-1]))
            if bc.bcTop == NOSLIP:
                v = v.at[-2, 1:-1].set(_sel(hi_j, 0.0, v[-2, 1:-1]))
                u = u.at[-1, 1:-1].set(_sel(hi_j, -u[-2, 1:-1], u[-1, 1:-1]))
            elif bc.bcTop == SLIP:
                v = v.at[-2, 1:-1].set(_sel(hi_j, 0.0, v[-2, 1:-1]))
                u = u.at[-1, 1:-1].set(_sel(hi_j, u[-2, 1:-1], u[-1, 1:-1]))
            elif bc.bcTop == OUTFLOW:
                u = u.at[-1, 1:-1].set(_sel(hi_j, u[-2, 1:-1], u[-1, 1:-1]))
                v = v.at[-2, 1:-1].set(_sel(hi_j, v[-3, 1:-1], v[-2, 1:-1]))
            return u, v

        def set_special_bc_divisible(u):
            lo_i, hi_i, lo_j, hi_j = walls()
            if param.name == "dcavity":
                # lid row, global i in 1..imax-1: skip local col il on the
                # right-wall shard (the reference's loop-bound quirk,
                # solver.c:345-349)
                colmask = jnp.zeros(il + 2, dtype).at[1:-1].set(1.0)
                colmask = colmask.at[-2].mul(1.0 - hi_i.astype(dtype))
                lid = 2.0 - u[-2, :]
                new_row = jnp.where(colmask > 0, lid, u[-1, :])
                u = u.at[-1, :].set(_sel(hi_j, new_row, u[-1, :]))
            elif param.name in ("canal", "canal_obstacle"):
                # parabolic inflow at the left wall, global y coordinate
                joff = get_offsets("j", jl)
                jj = jnp.arange(1, jl + 1, dtype=idx_dtype) + joff
                y = ((jj - 0.5) * dy).astype(dtype)
                prof = y * (param.ylength - y) * 4.0 / (param.ylength**2)
                u = u.at[1:-1, 0].set(_sel(lo_i, prof, u[1:-1, 0]))
            return u

        # -- F/G wall fixups, wall-gated (solver.c:425-435) -------------
        def fg_fixups_divisible(f, g, u, v):
            lo_i, hi_i, lo_j, hi_j = walls()
            f = f.at[1:-1, 0].set(_sel(lo_i, u[1:-1, 0], f[1:-1, 0]))
            f = f.at[1:-1, -2].set(_sel(hi_i, u[1:-1, -2], f[1:-1, -2]))
            g = g.at[0, 1:-1].set(_sel(lo_j, v[0, 1:-1], g[0, 1:-1]))
            g = g.at[-2, 1:-1].set(_sel(hi_j, v[-2, 1:-1], g[-2, 1:-1]))
            return f, g

        # -- ragged pad-with-mask wall handling (parallel/ragged2d.py):
        # same arithmetic as the divisible forms, selected by GLOBAL index
        # so hi walls may sit anywhere inside (or before) a trailing shard
        if self.ragged:
            from ..parallel import ragged2d as rg

            def set_bcs(u, v):
                return rg.set_bcs_ragged(
                    u, v, param, comm, jl, il, self.jmax, self.imax
                )

            def set_special_bc(u):
                return rg.set_special_bc_ragged(
                    u, param, comm, jl, il, self.jmax, self.imax, dy,
                    idx_dtype,
                )

            def fg_fixups(f, g, u, v):
                return rg.fg_fixups_ragged(
                    f, g, u, v, comm, jl, il, self.jmax, self.imax
                )
        else:
            set_bcs = set_bcs_divisible
            set_special_bc = set_special_bc_divisible
            fg_fixups = fg_fixups_divisible

        # -- pressure solve (RB SOR; ≙ solve, solver.c:586-660) ---------
        dx2, dy2 = dx * dx, dy * dy
        idx2, idy2 = 1.0 / dx2, 1.0 / dy2
        factor = param.omg * 0.5 * (dx2 * dy2) / (dx2 + dy2)
        epssq = param.eps * param.eps
        norm = float(self.imax * self.jmax)

        def _solve_sor(p, rhs, cap=None):
            """Communication-avoiding red-black solve (stencil2d.ca_*): one
            depth-2n halo exchange per n exact local iterations (n =
            tpu_ca_inner clamped by shard extents; trajectory identical to
            the exchange-per-half-sweep form). Extent-1 shards use the
            classic per-half-sweep fallback. `cap` (the residual-adaptive
            budget, tpu_itermax_adaptive) dynamically tightens the static
            itermax; None traces the historical loop."""
            limit = param.itermax if cap is None else cap
            supported = ca_supported(jl, il)
            n = ca_inner(param, jl, il) if supported else 1
            H = ca_halo(n, ragged=self.ragged) if supported else 1
            masks = ca_masks(jl, il, H, self.jmax, self.imax, dtype)
            pd = embed_deep(p, H)
            rd = halo_exchange(embed_deep(rhs, H), comm, depth=H)

            def cond(c):
                _, res, it = c
                return jnp.logical_and(res >= epssq, it < limit)

            def body(c):
                pd, _, it = c
                if supported:
                    pd = halo_exchange(pd, comm, depth=H)
                    pd, r2 = ca_rb_iters(pd, rd, n, masks, factor, idx2, idy2)
                else:
                    pd, r2 = rb_exchange_per_sweep(
                        pd, rd, masks, comm, factor, idx2, idy2,
                        ragged=self.ragged,
                    )
                res = reduction(r2, comm, "sum") / norm
                if _flags.debug():
                    master_print(comm, "{} Residuum: {}", it + (n - 1), res)
                return pd, res, it + n

            pd, res, it = lax.while_loop(
                cond, body,
                (pd, jnp.asarray(1.0, dtype), jnp.asarray(0, jnp.int32)),
            )
            return halo_exchange(strip_deep(pd, H), comm), res, it

        def _solve_sor_split(p, rhs, cap=None):
            """The sweep-split twin of _solve_sor (dispatched with the
            overlapped schedule, ROADMAP item 3): same n-iteration
            residual cadence as the CA form — the trajectory is bitwise
            identical (the CA discipline already equals the per-half-
            sweep form) — but each half-sweep posts its depth-1 exchange
            behind the interior update (stencil2d.rb_split_iter), so on
            a solve-dominated step no exchange sits serialized on the
            critical path. Runs on the plain halo-1 layout; the rim-2
            interior mask gates the merge."""
            from ..parallel import overlap as _ovl
            from ..parallel.comm import persistent_exchange

            limit = param.itermax if cap is None else cap
            supported = ca_supported(jl, il)
            n = ca_inner(param, jl, il) if supported else 1
            masks = ca_masks(jl, il, 1, self.jmax, self.imax, dtype)
            int_mask = _ovl.interior_mask(
                (jl, il), 2, partitioned=(Pj > 1, Pi > 1))
            sched1 = persistent_exchange(comm, 1, dtype)

            def cond(c):
                _, res, it = c
                return jnp.logical_and(res >= epssq, it < limit)

            def body(c):
                p, _, it = c
                r2 = None
                for _k in range(n):
                    p, r2 = rb_split_iter(
                        p, rhs, masks, sched1, int_mask, factor, idx2,
                        idy2, ragged=self.ragged)
                res = reduction(r2, comm, "sum") / norm
                if _flags.debug():
                    master_print(comm, "{} Residuum: {}", it + (n - 1), res)
                return p, res, it + n

            p, res, it = lax.while_loop(
                cond, body,
                (p, jnp.asarray(1.0, dtype), jnp.asarray(0, jnp.int32)),
            )
            return halo_exchange(p, comm), res, it

        # -- quarter-layout production pressure solve (the round-3 wiring of
        # the headline Pallas kernel into the distributed path; same dispatch
        # contract as models/poisson_dist) --------------------------------
        plain_sor = param.tpu_solver not in ("mg", "fft") and self.masks is None
        rb_q, qg, n_q, pallas_q = quarters_dispatch(
            param, self.jmax, self.imax, jl, il, dx, dy, dtype,
            "ns2d_dist", plain_sor=plain_sor and not self.ragged,
        )
        # ragged Pallas fast path (round 5, VERDICT r4 item 2): the
        # compressed quarters layout cannot carry ragged walls, but the
        # flag-masked per-shard kernel can — the live region IS a flag
        # field (all-fluid masks; the kernel's global-coordinate gating
        # already excludes dead cells, ops/sor_obsdist). Dispatched only
        # when the kernel actually is (off-TPU the jnp case keeps
        # _solve_sor's bitwise CA discipline).
        # `tpu_sor_layout checkerboard` forces the masked kernel in dist
        # context (interpret off-TPU — the dryrun/test mode; the obsdist
        # kernel IS the distributed masked-checkerboard layout)
        force_masked = param.tpu_sor_layout == "checkerboard"
        solve_ragged_k = None
        if self.ragged and plain_sor:
            from ..models.poisson import _use_pallas
            from ..ops import obstacle as obst

            if force_masked or _use_pallas("auto", dtype):
                # the dispatch predicate gates the BUILD too: the all-fluid
                # masks are host-side global-sized arrays — off-TPU
                # unforced runs keep _solve_sor without paying for them
                m_live = obst.make_masks(
                    np.ones((self.jmax + 2, self.imax + 2), bool),
                    dx, dy, param.omg, dtype,
                )
                cand, used_k = obst.make_dist_obstacle_solver(
                    comm, self.imax, self.jmax, jl, il, dx, dy, param.eps,
                    param.itermax, m_live, dtype, ca_n=param.tpu_ca_inner,
                    sor_inner=param.tpu_sor_inner, ragged=True,
                    record_key="ns2d_dist",
                    backend="pallas" if force_masked else "auto",
                )
                if used_k:
                    solve_ragged_k = cand
                    pallas_q = True
        if rb_q is None and solve_ragged_k is None:
            tag = (
                "jnp_ca" if plain_sor else f"other_{param.tpu_solver}"
                if self.masks is None else "obstacle (see obstacle_dist)"
            )
            if self.ragged:
                tag += " ragged"
            _dispatch.record("ns2d_dist", tag)

        def _solve_sor_quarters(p, rhs, cap=None):
            """Stacked-quarter CA solve on the halo-1 extended blocks the
            time-stepper carries; returns the exchanged halo-1 block like
            _solve_sor (adaptUV reads p across shard edges)."""
            limit = param.itermax if cap is None else cap
            joff = get_offsets("j", jl)
            ioff = get_offsets("i", il)
            qoffs = jnp.stack(
                [(joff // 2).astype(jnp.int32), (ioff // 2).astype(jnp.int32)]
            )
            rq = q_exchange(pack_ext_to_q(rhs, qg), comm, qg)
            xq = pack_ext_to_q(p, qg)

            def cond(c):
                _, res, it = c
                return jnp.logical_and(res >= epssq, it < limit)

            def body(c):
                xq, _, it = c
                xq = q_exchange(xq, comm, qg)
                xq, r2 = rb_q(qoffs, xq, rq)
                res = reduction(r2, comm, "sum") / norm
                if _flags.debug():
                    master_print(comm, "{} Residuum: {}", it + (n_q - 1), res)
                return xq, res, it + n_q

            xq, res, it = lax.while_loop(
                cond, body,
                (xq, jnp.asarray(1.0, dtype), jnp.asarray(0, jnp.int32)),
            )
            return halo_exchange(unpack_q_to_ext(xq, qg), comm), res, it

        # pre-resolution of the overlap knob for the solve builders (the
        # recorded decision happens after the fused build below — this
        # predicate only selects the sweep-split smoother forms, whose
        # values are bitwise the serial forms either way). It mirrors
        # resolve_overlap's statically-known ineligibility (off / field
        # faults / fused knob off); the one input not known yet — the
        # fused probe failing at build — is healed by the serial MG
        # rebuild next to the sweep_split record below.
        ovl_pre = (param.tpu_overlap != "off"
                   and not field_faults
                   and param.tpu_fuse_phases != "off"
                   and (param.tpu_overlap == "on"
                        or jax.default_backend() == "tpu"))
        mg_serial_rebuild = None
        if param.tpu_solver == "fft":
            from ..ops.dctpoisson import make_dist_dct_solve_2d

            solve = make_dist_dct_solve_2d(
                comm, self.imax, self.jmax, jl, il, dx, dy, dtype
            )
        elif param.tpu_solver == "mg":
            if self.masks is not None:
                # the only floor-reaching solver on obstacle-at-scale
                # configs, now also on a mesh (VERDICT r3 item 6)
                from ..ops.multigrid import make_dist_obstacle_mg_solve_2d

                solve, mg_pallas = make_dist_obstacle_mg_solve_2d(
                    comm, self.imax, self.jmax, jl, il, dx, dy,
                    param.eps, param.itermax, self.masks, dtype,
                    stall_rtol=param.tpu_mg_stall_rtol,
                    fused=param.tpu_mg_fused,
                )
                # the MG factory reports per-shard Pallas smoothing the
                # same way the obstacle SOR solver does: relax check_vma
                pallas_q = pallas_q or mg_pallas
            else:
                from ..ops.multigrid import make_dist_mg_solve_2d

                solve, mg_pallas = make_dist_mg_solve_2d(
                    comm, self.imax, self.jmax, jl, il, dx, dy,
                    param.eps, param.itermax, dtype,
                    stall_rtol=param.tpu_mg_stall_rtol, split=ovl_pre,
                    fused=param.tpu_mg_fused,
                )
                pallas_q = pallas_q or mg_pallas
                if ovl_pre:
                    def mg_serial_rebuild():
                        s2, _ = make_dist_mg_solve_2d(
                            comm, self.imax, self.jmax, jl, il, dx, dy,
                            param.eps, param.itermax, dtype,
                            stall_rtol=param.tpu_mg_stall_rtol,
                            split=False, fused=param.tpu_mg_fused,
                        )
                        return s2
        elif self.masks is not None:
            from ..ops.obstacle import make_dist_obstacle_solver

            solve, obs_pallas = make_dist_obstacle_solver(
                comm, self.imax, self.jmax, jl, il, dx, dy,
                param.eps, param.itermax, self.masks, dtype,
                ca_n=param.tpu_ca_inner, sor_inner=param.tpu_sor_inner,
                ragged=self.ragged,
                backend="pallas" if force_masked else "auto",
            )
            # the obstacle solver reports whether it dispatched its
            # per-shard Pallas kernel: relax check_vma then
            pallas_q = pallas_q or obs_pallas
        elif rb_q is not None:
            solve = _solve_sor_quarters
        elif solve_ragged_k is not None:
            solve = solve_ragged_k
        else:
            solve = _solve_sor

        # -- fused step-phase kernels (ops/ns2d_fused.py): the per-shard
        # non-solve phases (BCs + special BC + FG + fixups + RHS, then
        # adaptUV) collapse into two global-coordinate-gated Pallas kernels
        # around the solve — PRE on the depth-H deep-halo block (one
        # exchange buys the whole validity chain, the CA discipline), POST
        # on the plain extended block (adaptUV reads only center/+1).
        # dt stays the jnp reduction (the deep-exchanged block contains the
        # same global value set, so the ghost-inclusive max is unchanged).
        # Ragged shards are the same kernels at uneven block bounds (global
        # gating + the POST live-mask multiply); obstacle runs feed the
        # per-shard global-constant flag slices at call time (fluid=True).
        from ..ops.ns2d_fused import FUSE_DEEP_HALO, probe_fused_2d

        fuse_why_not = None
        if min(jl, il) < FUSE_DEEP_HALO:
            fuse_why_not = f"shard extents < deep halo {FUSE_DEEP_HALO}"
        fused_k = None
        if _dispatch.resolve_fuse_phases(
            param, "auto", dtype, probe_fused_2d, "ns2d_dist_phases",
            why_not=fuse_why_not,
        ):
            from ..ops import ns2d_fused as nf

            try:
                pre_k, pad_deep, unpad_deep, _hk = nf.make_fused_pre_2d(
                    param, self.jmax, self.imax, dx, dy, dtype,
                    jl=jl, il=il, ext_pad=FUSE_DEEP_HALO - 1,
                    fluid=True if self.masks is not None else None,
                    prof_dtype=idx_dtype,
                )
                post_k, pad_ext, unpad_ext, _hk2 = nf.make_fused_post_2d(
                    param, self.jmax, self.imax, dx, dy, dtype,
                    jl=jl, il=il,
                    fluid=True if self.masks is not None else None,
                    ragged=self.ragged,
                )
                fused_k = (pre_k, post_k)
                pallas_q = True
            except ValueError as exc:  # VMEM-infeasible shard geometry
                _dispatch.record("ns2d_dist_phases", f"jnp ({exc})")

        # -- comm/compute overlap (ROADMAP item 2): the double-buffered
        # interior/boundary schedule rides the fused deep-halo step only;
        # the serial schedule stays the parity oracle (`off` is bitwise
        # the historical program — the CONTRACTS.json hash contract)
        ovl_why = None
        if fused_k is None:
            ovl_why = "needs the fused deep-halo step (tpu_fuse_phases)"
        elif field_faults:
            ovl_why = ("PAMPI_FAULTS field faults armed (in-step writes "
                       "would postdate the posted exchange)")
        overlap = _dispatch.resolve_overlap(
            param, "overlap_ns2d_dist", why_not=ovl_why)
        self._overlap = overlap
        self._overlap_plan = None  # set by the overlap block when the
        #   grid-restricted halves dispatch (tpu_overlap_restrict)
        # sweep split (ROADMAP item 3 layer 2): with the overlapped
        # schedule dispatched, the jnp RB-SOR convergence loop swaps to
        # the per-half-sweep split form — bitwise the CA trajectory,
        # with every depth-1 exchange posted behind an interior update.
        # Pallas solve paths keep their serial sweeps (the kernel reads
        # its whole block; a split needs kernel surgery, not a loop
        # swap) and record why.
        if overlap and solve is _solve_sor:
            solve = _solve_sor_split
            _dispatch.record("sweep_split_ns2d_dist", "split (jnp rb-sor)")
        elif overlap and param.tpu_solver == "mg" and self.masks is None:
            _dispatch.record("sweep_split_ns2d_dist",
                             "split (mg jnp-smoother levels)")
        elif overlap:
            _dispatch.record("sweep_split_ns2d_dist",
                             "serial (pallas/other solve)")
        elif mg_serial_rebuild is not None:
            # the pre-resolution guessed overlap but the fused probe
            # failed at build: drop the split smoother so the traced
            # program matches the recorded serial schedule
            solve = mg_serial_rebuild()

        # residual-adaptive itermax (tpu_itermax_adaptive, ROADMAP item
        # 1's last open bullet): the previous step's (res, it) shrinks
        # the NEXT solve's sweep budget inside the chunk loop — the cap
        # rides the chunk carry only (external arity unchanged, resets
        # to the full itermax at every chunk dispatch). Dist SOR paths
        # only: mg counts cycles, fft does not iterate, the obstacle
        # solvers carry their own loops.
        adapt_n = int(param.tpu_itermax_adaptive)
        use_cap = adapt_n > 0 and solve in (
            _solve_sor, _solve_sor_split, _solve_sor_quarters)
        if adapt_n > 0:
            _dispatch.record(
                "itermax_adaptive_ns2d_dist",
                f"adaptive (+{adapt_n} slack)" if use_cap
                else "static (solve path carries no sweep budget)")
        itermax_i = jnp.asarray(param.itermax, jnp.int32)

        def next_cap(res, it):
            # converged within the budget -> cap the next solve at
            # it + slack; a capped/non-converged solve restores the full
            # itermax so the budget never wedges a hard step
            return jnp.where(res < epssq,
                             jnp.minimum(itermax_i, it + adapt_n),
                             itermax_i)

        # -- weighted mean for normalizePressure ------------------------
        def wall_weight():
            if self.ragged:
                from ..parallel import ragged2d as rg

                return rg.wall_weight_ragged(
                    comm, jl, il, self.jmax, self.imax, dtype
                )
            lo_i, hi_i, lo_j, hi_j = walls()
            one = jnp.ones((), dtype)
            rowv = jnp.ones(jl + 2, dtype)
            rowv = rowv.at[0].set(_sel(lo_j, one, 0.0 * one))
            rowv = rowv.at[-1].set(_sel(hi_j, one, 0.0 * one))
            colv = jnp.ones(il + 2, dtype)
            colv = colv.at[0].set(_sel(lo_i, one, 0.0 * one))
            colv = colv.at[-1].set(_sel(hi_i, one, 0.0 * one))
            return rowv[:, None] * colv[None, :]

        nfull = float((self.imax + 2) * (self.jmax + 2))
        gmasks = self.masks
        if gmasks is not None:
            from ..ops.obstacle import (
                adapt_uv_obstacle,
                apply_obstacle_velocity_bc,
                mask_fg,
                shard_masks,
            )

            # ragged ceil-division overhang (0 when divisible): the HI-side
            # zero-pad that keeps trailing-shard mask slices from clamping
            # (dead cells read zero masks)
            from ..parallel.stencil2d import ceil_overhang

            over_j = ceil_overhang(Pj, jl, self.jmax)
            over_i = ceil_overhang(Pi, il, self.imax)

            def local_masks():
                # must run INSIDE the shard_map trace (mesh offsets)
                return shard_masks(gmasks, jl, il, over_j, over_i)

            def fused_flag_blocks():
                """Per-shard deep-halo and extended slices of the global 0/1
                fluid flag for the fused kernels (the shard_masks
                global-constant-slice convention: overlapping slices agree
                across shards), in the kernels' padded layouts. Beyond-global
                deep-halo cells read flag 0 — their outputs are stripped or
                interior-gated. Loop-invariant constant gathers: XLA hoists
                them out of the chunk's while loop."""
                H = FUSE_DEEP_HALO
                joff = get_offsets("j", jl)
                ioff = get_offsets("i", il)
                fl = gmasks.fluid
                wide = jnp.pad(
                    fl, ((H - 1, over_j + H - 1), (H - 1, over_i + H - 1))
                )
                deep = lax.dynamic_slice(
                    wide, (joff, ioff), (jl + 2 * H, il + 2 * H)
                )
                hi = jnp.pad(fl, ((0, over_j), (0, over_i)))
                ext = lax.dynamic_slice(hi, (joff, ioff), (jl + 2, il + 2))
                return pad_deep(deep), pad_ext(ext)

        def normalize_pressure(p):
            if gmasks is not None:
                # fluid-weighted mean (obstacle cells excluded), ghost ring
                # counted once via the wall gate — ≙ normalize_pressure_fluid
                w = wall_weight() * local_masks().fluid
                total = reduction(jnp.sum(p * w), comm, "sum")
                count = reduction(jnp.sum(w), comm, "sum")
                return p - total / count
            s = reduction(jnp.sum(p * wall_weight()), comm, "sum")
            return p - s / nfull

        # -- CFL timestep (maxElement incl. ghosts + Allreduce MAX) ------
        def cfl_from_maxima(umax, vmax):
            # the scalar tail, shared with the overlapped step (whose
            # maxima ride the carry from the previous POST kernel)
            inf = jnp.asarray(jnp.inf, dtype)
            dt = jnp.minimum(
                jnp.asarray(self.dt_bound, dtype),
                jnp.minimum(
                    jnp.where(umax > 0, dx / umax, inf),
                    jnp.where(vmax > 0, dy / vmax, inf),
                ),
            )
            return dt * param.tau

        def compute_dt(u, v):
            umax = reduction(jnp.max(jnp.abs(u)), comm, "max")
            vmax = reduction(jnp.max(jnp.abs(v)), comm, "max")
            return cfl_from_maxima(umax, vmax)

        adaptive = param.tau > 0.0

        # -- one full timestep ------------------------------------------
        def step_phases(u, v, p, nt, cap=None):
            """All phases of one timestep up to (and incl.) the pressure
            solve; step() appends the projection, debug_kernel returns the
            intermediates (the automated heir of the reference's test.c
            halo dump, SURVEY.md §4.1). `cap` is the residual-adaptive
            sweep budget (None = the historical static-itermax trace)."""
            u, v, p = _fi.apply_field_faults(field_faults, nt, u=u, v=v, p=p)
            u = halo_exchange(u, comm)
            v = halo_exchange(v, comm)
            dt = compute_dt(u, v) if adaptive else jnp.asarray(param.dt, dtype)
            dt = clamped_dt(dt, dt_scale)
            u, v = set_bcs(u, v)
            u = set_special_bc(u)
            u = halo_exchange(u, comm)
            v = halo_exchange(v, comm)
            if gmasks is not None:
                # needs the fully-exchanged post-BC state (the single-device
                # op reads the whole array at once); its own halo-cell
                # outputs are refreshed by one more exchange
                u, v = apply_obstacle_velocity_bc(u, v, local_masks())
                u = halo_exchange(u, comm)
                v = halo_exchange(v, comm)
            f, g = ops.compute_fg_interior(
                u, v, dt, param.re, param.gx, param.gy, param.gamma, dx, dy
            )
            f, g = fg_fixups(f, g, u, v)
            if gmasks is not None:
                f, g = mask_fg(f, g, u, v, local_masks())
            f = halo_shift(f, comm, "i")
            g = halo_shift(g, comm, "j")
            rhs = ops.compute_rhs(f, g, dt, dx, dy)
            p = lax.cond(nt % 100 == 0, normalize_pressure, lambda q: q, p)
            p, res, it = (solve(p, rhs, cap) if cap is not None
                          else solve(p, rhs))
            return u, v, f, g, rhs, p, dt, res, it

        def step(u, v, p, t, nt, cap=None):
            u, v, f, g, _rhs, p, dt, res, it = step_phases(u, v, p, nt,
                                                           cap)

            def adapt(u, v):
                if gmasks is not None:
                    return adapt_uv_obstacle(
                        u, v, f, g, p, dt, dx, dy, local_masks()
                    )
                return ops.adapt_uv(u, v, f, g, p, dt, dx, dy)

            if not self.ragged:
                u, v = adapt(u, v)
            else:
                # ragged projection: update ONLY the true global interior.
                # The single-device adapt never touches ghost rows, but here
                # the global ghost ring can be interior-stored — clobbering
                # it would change what next step's ghost-inclusive CFL scan
                # (maxElement quirk) sees; dead cells are zeroed so halo
                # garbage cannot reach that scan either. One gating block
                # for the plain AND obstacle projections — the discipline
                # cannot drift between them.
                from ..parallel import ragged2d as rg

                gj, gi = rg.global_index_vectors(comm, jl, il)
                interior = (
                    (gj >= 1) & (gj <= self.jmax)
                    & (gi >= 1) & (gi <= self.imax)
                )
                live = rg.live_masks(comm, jl, il, self.jmax, self.imax, dtype)
                ua, va = adapt(u, v)
                u = jnp.where(interior, ua, u) * live
                v = jnp.where(interior, va, v) * live
            # t accumulates in high precision regardless of the field dtype
            # (bfloat16 would stall t once ulp/2 > dt and never reach te)
            t_next = t + dt.astype(idx_dtype)
            if _flags.verbose():
                # printed AFTER t += dt, matching A5 main.c:52-57
                master_print(comm, "TIME {} , TIMESTEP {}", t_next, dt)
            capt = (next_cap(res, it),) if cap is not None else ()
            if metrics:
                # mesh-global |u|/|v| maxima (replicated, like res) — the
                # in-band telemetry scalars; Allreduce MAX only on this path
                um = reduction(jnp.max(jnp.abs(u)), comm, "max")
                vm = reduction(jnp.max(jnp.abs(v)), comm, "max")
                return (u, v, p, t_next, nt + 1, res, it, dt, um, vm) + capt
            return (u, v, p, t_next, nt + 1) + capt

        def step_fused(u, v, p, t, nt, cap=None, strips=None):
            """The fused-phase twin of step(): one deep exchange feeds the
            PRE kernel (BCs+FG+RHS per shard, redundant halo recompute
            bitwise-consistent across shards), the solve is unchanged, the
            POST kernel projects on the exchanged extended blocks.
            `strips` is the depth-scheduled variant (tpu_exchange_depth):
            the slow-tier axis's ghost strips come from the K-block's
            captured exchange (parallel/comm.paste_axis_strips) instead
            of a fresh per-step collective — relaxed parity, staleness
            bounded by the depth block."""
            pre_k, post_k = fused_k
            H = FUSE_DEEP_HALO
            u, v, p = _fi.apply_field_faults(field_faults, nt, u=u, v=v, p=p)
            if strips is None:
                ud = halo_exchange(embed_deep(u, H), comm, depth=H)
                vd = halo_exchange(embed_deep(v, H), comm, depth=H)
            else:
                from ..parallel.comm import paste_axis_strips

                (lo_u, hi_u), (lo_v, hi_v) = strips
                ud = paste_axis_strips(
                    embed_deep(u, H), comm, dax, H, lo_u, hi_u)
                vd = paste_axis_strips(
                    embed_deep(v, H), comm, dax, H, lo_v, hi_v)
            # ghost-inclusive CFL max: the deep block carries the same
            # global value set (owned + fresh neighbour copies + wall
            # ghosts + dead zeros), so the max reduction is unchanged
            dt = compute_dt(ud, vd) if adaptive else jnp.asarray(param.dt, dtype)
            dt = clamped_dt(dt, dt_scale)
            joff = get_offsets("j", jl)
            ioff = get_offsets("i", il)
            offs = jnp.stack([joff, ioff]).astype(jnp.int32)
            dt11 = jnp.full((1, 1), dt, dtype)
            pre_extra = post_extra = ()
            if gmasks is not None:
                flg_deep, flg_ext = fused_flag_blocks()
                pre_extra = (flg_deep,)
                post_extra = (flg_ext,)
            upd, vpd, fpd, gpd, rpd = pre_k(
                offs, dt11, pad_deep(ud), pad_deep(vd), *pre_extra
            )
            u = strip_deep(unpad_deep(upd), H)
            v = strip_deep(unpad_deep(vpd), H)
            f = strip_deep(unpad_deep(fpd), H)
            g = strip_deep(unpad_deep(gpd), H)
            rhs = strip_deep(unpad_deep(rpd), H)
            p = lax.cond(nt % 100 == 0, normalize_pressure, lambda q: q, p)
            p, _res, _it = (solve(p, rhs, cap) if cap is not None
                            else solve(p, rhs))
            up, vp, um_l, vm_l = post_k(
                offs, dt11, pad_ext(u), pad_ext(v), pad_ext(f), pad_ext(g),
                pad_ext(p), *post_extra,
            )
            u = unpad_ext(up)
            v = unpad_ext(vp)
            t_next = t + dt.astype(idx_dtype)
            if _flags.verbose():
                master_print(comm, "TIME {} , TIMESTEP {}", t_next, dt)
            capt = (next_cap(_res, _it),) if cap is not None else ()
            if metrics:
                # the POST kernel's carried maxima are per-shard: one
                # Allreduce MAX makes them the global telemetry scalars
                um = reduction(um_l, comm, "max")
                vm = reduction(vm_l, comm, "max")
                return (u, v, p, t_next, nt + 1, _res, _it, dt,
                        um, vm) + capt
            return (u, v, p, t_next, nt + 1) + capt

        if overlap:
            # -- overlapped fused step (parallel/overlap.py): the deep
            # exchange for step N+1 is posted right after step N's POST
            # and carried as a double-buffered (ud, vd) pair + the CFL
            # maxima + a generation tag; PRE runs twice — interior half
            # on the stale re-embedded block (no dependency on the
            # exchange anywhere in its cone), boundary half on the
            # buffered exchanged block — merged by the interior mask.
            # Trajectory == step_fused's bitwise (the interior cone
            # avoids the strips; max is reduction-order exact).
            from ..ops import ns2d_fused as nf
            from ..ops.ns2d_fused import OVERLAP_RIM
            from ..parallel import overlap as _ovl
            from ..parallel.comm import persistent_exchange

            H = FUSE_DEEP_HALO
            deep_sched = persistent_exchange(comm, H, dtype)
            # axis-aware rim: a size-1 mesh axis exchanges nothing, so
            # its sides are bit-identical between the stale block and
            # the double buffer — the rim (and the boundary half's
            # sweep) drops there (parallel/overlap.interior_slices)
            part = (Pj > 1, Pi > 1)
            int_mask = _ovl.interior_mask((jl, il), OVERLAP_RIM,
                                          partitioned=part)
            # grid restriction (tpu_overlap_restrict): band the two PRE
            # halves over the leading axis — interior core rows for the
            # interior half, OVERLAP_RIM bands (plus every row when the
            # column axis is partitioned) for the boundary half
            br_, _hh, wp_, nb_ = nf.fused_deep_layout_2d(
                jl, il, dtype, H - 1)
            from ..ops.sor_pallas import _align

            plan = _ovl.region_plan((jl, il), OVERLAP_RIM, H - 1,
                                    br_, nb_, wp_, part,
                                    align=_align(dtype))
            restrict = _dispatch.resolve_overlap_restrict(
                param, "overlap_grid_ns2d_dist", plan)
            self._overlap_plan = plan if restrict else None
            pre_int = pre_bnd = None
            if restrict:
                fl_arg = True if self.masks is not None else None
                pre_int = nf.make_fused_pre_2d(
                    param, self.jmax, self.imax, dx, dy, dtype,
                    jl=jl, il=il, ext_pad=H - 1, fluid=fl_arg,
                    prof_dtype=idx_dtype,
                    grid_bands=plan["int_bands"])[0]
                pre_bnd = nf.make_fused_pre_2d(
                    param, self.jmax, self.imax, dx, dy, dtype,
                    jl=jl, il=il, ext_pad=H - 1, fluid=fl_arg,
                    prof_dtype=idx_dtype,
                    grid_bands=plan["bnd_bands"])[0]

            def exchange_buffers(u, v):
                """Post the next step's deep exchange (the double
                buffer's fill half)."""
                return (deep_sched(embed_deep(u, H)),
                        deep_sched(embed_deep(v, H)))

            def buffer_maxima(ud, vd):
                """Ghost-inclusive CFL maxima of the freshly exchanged
                deep blocks — the serial step's compute_dt inputs, used
                only for the chunk-prologue generation (steps >= 2 carry
                the POST kernel's maxima instead)."""
                return (reduction(jnp.max(jnp.abs(ud)), comm, "max"),
                        reduction(jnp.max(jnp.abs(vd)), comm, "max"))

            def step_overlap(u, v, p, t, nt, ud, vd, um, vm, gen,
                             cap=None):
                pre_k, post_k = fused_k
                # the restricted halves (when dispatched) are the SAME
                # kernel on banded grids; values inside each band are
                # bitwise the full sweep's (globally gated writes), and
                # the merge mask selects only band-covered cells
                pre_i = pre_int if pre_int is not None else pre_k
                pre_b = pre_bnd if pre_bnd is not None else pre_k
                dt = (cfl_from_maxima(um, vm) if adaptive
                      else jnp.asarray(param.dt, dtype))
                # stale-buffer detector: a generation-skewed double
                # buffer poisons dt (NaN t -> drive-loop divergence)
                dt = _ovl.generation_guard(dt, gen, nt)
                dt = clamped_dt(dt, dt_scale)
                joff = get_offsets("j", jl)
                ioff = get_offsets("i", il)
                offs = jnp.stack([joff, ioff]).astype(jnp.int32)
                dt11 = jnp.full((1, 1), dt, dtype)
                pre_extra = post_extra = ()
                if gmasks is not None:
                    flg_deep, flg_ext = fused_flag_blocks()
                    pre_extra = (flg_deep,)
                    post_extra = (flg_ext,)
                ints = pre_i(offs, dt11, pad_deep(embed_deep(u, H)),
                             pad_deep(embed_deep(v, H)), *pre_extra)
                bnds = pre_b(offs, dt11, pad_deep(ud), pad_deep(vd),
                             *pre_extra)
                u, v, f, g, rhs = _ovl.merge_halves(
                    int_mask,
                    [strip_deep(unpad_deep(a), H) for a in ints],
                    [strip_deep(unpad_deep(b), H) for b in bnds])
                p = lax.cond(nt % 100 == 0, normalize_pressure,
                             lambda q: q, p)
                p, _res, _it = (solve(p, rhs, cap) if cap is not None
                                else solve(p, rhs))
                up, vp, um_l, vm_l = post_k(
                    offs, dt11, pad_ext(u), pad_ext(v), pad_ext(f),
                    pad_ext(g), pad_ext(p), *post_extra,
                )
                u = unpad_ext(up)
                v = unpad_ext(vp)
                # next step's CFL maxima: POST's carried per-shard maxima
                # over the valid extended cells — the same global value
                # set the serial step's exchanged-block scan sees
                um = reduction(um_l, comm, "max")
                vm = reduction(vm_l, comm, "max")
                # post step N+1's exchange NOW: its results feed only the
                # carried buffers (the boundary half, one iteration
                # later) — nothing else in the trace depends on them
                ud, vd = exchange_buffers(u, v)
                t_next = t + dt.astype(idx_dtype)
                if _flags.verbose():
                    master_print(comm, "TIME {} , TIMESTEP {}", t_next, dt)
                capt = (next_cap(_res, _it),) if cap is not None else ()
                return (u, v, p, t_next, nt + 1, ud, vd, um, vm, nt + 1,
                        _res, _it, dt) + capt

        step_impl = step if fused_k is None else step_fused
        te = param.te
        chunk = self.CHUNK
        # K-step fused chunks (ISSUE 17): K=1 keeps the historical
        # while-body verbatim (jaxpr-hash identity); K>=2 advances the
        # loop by one lax.scan of K time-gated steps per trip — the step
        # body traces ONCE, so the chunk's static launch count covers K
        # steps. The overlapped schedule keeps K=1: its double-buffered
        # exchange pipeline is its own cross-step fusion.
        kfuse = _dispatch.resolve_chunk_fuse(
            param, "ns2d_dist_chunk_fuse", chunk,
            why_not=("overlapped chunk carries its own cross-step "
                     "exchange pipeline") if overlap else None)
        # per-tier exchange depth (tpu_exchange_depth axis=H): the dcn
        # axis's u/v strips come from ONE depth-H capture per H scan
        # steps (parallel/comm.capture_axis_strips) — explicit opt-in,
        # relaxed parity (staleness bounded by the block)
        depth_why = None
        if fused_k is None:
            depth_why = "needs the fused deep-halo step (tpu_fuse_phases)"
        elif self.ragged:
            depth_why = "ragged decomposition"
        elif field_faults:
            depth_why = "PAMPI_FAULTS field faults armed"
        part_names = [n for n in comm.axis_names if comm.axis_size(n) > 1]
        part_ext = [
            {"j": jl, "i": il}[n] for n in part_names]
        depths = _dispatch.resolve_exchange_depth(
            param, "ns2d_dist_exchange_depth", kfuse, dict(comm.tiers),
            part_names, part_ext,
            FUSE_DEEP_HALO if fused_k is not None else 1,
            why_not=depth_why)
        dax, ddepth = next(iter(depths.items())) if depths else (None, 0)
        self._exchange_depths = depths

        def fuse_block_scan(c, kblock):
            """Advance the scan carry by kfuse gated steps: the plain
            K-scan, or — with a depth map armed — kfuse/H depth blocks,
            each capturing the slow axis's strips once and scanning H
            pasted steps."""
            if dax is None:
                c, _ = lax.scan(kblock(None), c, None, length=kfuse)
                return c
            from ..parallel.comm import capture_axis_strips

            def dblock(c, _):
                s = tuple(
                    capture_axis_strips(x, comm, dax, ddepth,
                                        FUSE_DEEP_HALO)
                    for x in (c[0], c[1]))
                c, _ = lax.scan(kblock(s), c, None, length=ddepth)
                return c, None

            c, _ = lax.scan(dblock, c, None, length=kfuse // ddepth)
            return c

        def chunk_kernel(u, v, p, t, nt):
            def cond(c):
                return jnp.logical_and(c[3] <= te, c[5] < chunk)

            if kfuse > 1:
                def kblock(strips):
                    skw = {} if strips is None else {"strips": strips}

                    def blk(c, _):
                        def live(c):
                            if use_cap:
                                u, v, p, t, nt, cap = c
                                return step_impl(u, v, p, t, nt, cap,
                                                 **skw)
                            u, v, p, t, nt = c
                            return step_impl(u, v, p, t, nt, **skw)

                        return lax.cond(c[3] <= te, live,
                                        lambda c: c, c), None

                    return blk

                def body(c):
                    sc = fuse_block_scan(c[:5] + c[6:], kblock)
                    return sc[:5] + (c[5] + kfuse,) + sc[5:]
            else:
                def body(c):
                    if use_cap:
                        u, v, p, t, nt, k, cap = c
                        u, v, p, t, nt, cap = step_impl(u, v, p, t, nt, cap)
                        return u, v, p, t, nt, k + 1, cap
                    u, v, p, t, nt, k = c
                    u, v, p, t, nt = step_impl(u, v, p, t, nt)
                    return u, v, p, t, nt, k + 1

            init = (u, v, p, t, nt, jnp.asarray(0, jnp.int32))
            if use_cap:
                # the budget resets to the full itermax per chunk
                # dispatch (external arity unchanged)
                init = init + (itermax_i,)
            out = lax.while_loop(cond, body, init)
            return out[0], out[1], out[2], out[3], out[4]

        def chunk_kernel_metrics(u, v, p, t, nt, m):
            # the telemetry twin: replicated f32 metrics scalars ride the
            # carry, packed into the in-band vector at the chunk boundary
            def cond(c):
                return jnp.logical_and(c[3] <= te, c[5] < chunk)

            if kfuse > 1:
                def kblock(strips):
                    skw = {} if strips is None else {"strips": strips}

                    def blk(c, _):
                        def live(c):
                            if use_cap:
                                (u, v, p, t, nt, res, it, dtv, um, vm,
                                 bad, cap) = c
                                (u, v, p, t, nt, res, it, dtv, um, vm,
                                 cap) = step_impl(u, v, p, t, nt, cap,
                                                  **skw)
                            else:
                                (u, v, p, t, nt, res, it, dtv, um, vm,
                                 bad) = c
                                (u, v, p, t, nt, res, it, dtv, um,
                                 vm) = step_impl(u, v, p, t, nt, **skw)
                            # POST-step nt: the divergence record names
                            # the true step inside the K-block
                            res, it, dtv, um, vm, bad = _tm.metrics_step(
                                bad, nt, res, it, dtv, um, vm)
                            out = (u, v, p, t, nt, res, it, dtv, um, vm,
                                   bad)
                            return out + ((cap,) if use_cap else ())

                        return lax.cond(c[3] <= te, live,
                                        lambda c: c, c), None

                    return blk

                def body(c):
                    sc = fuse_block_scan(c[:5] + c[6:], kblock)
                    return sc[:5] + (c[5] + kfuse,) + sc[5:]
            else:
                def body(c):
                    if use_cap:
                        (u, v, p, t, nt, k, res, it, dtv, um, vm, bad,
                         cap) = c
                        u, v, p, t, nt, res, it, dtv, um, vm, cap = step_impl(
                            u, v, p, t, nt, cap)
                    else:
                        u, v, p, t, nt, k, res, it, dtv, um, vm, bad = c
                        u, v, p, t, nt, res, it, dtv, um, vm = step_impl(
                            u, v, p, t, nt
                        )
                    res, it, dtv, um, vm, bad = _tm.metrics_step(
                        bad, nt, res, it, dtv, um, vm)
                    out = (u, v, p, t, nt, k + 1, res, it, dtv, um, vm, bad)
                    return out + ((cap,) if use_cap else ())

            init = (u, v, p, t, nt, jnp.asarray(0, jnp.int32),
                    m[_tm.M_RES], m[_tm.M_IT], m[_tm.M_DT],
                    m[_tm.M_UMAX], m[_tm.M_VMAX], m[_tm.M_BAD])
            if use_cap:
                init = init + (itermax_i,)
            out = lax.while_loop(cond, body, init)
            (u, v, p, t, nt, _k, res, it, dtv, um, vm, bad) = out[:12]
            return u, v, p, t, nt, _tm.metrics_pack(
                res, it, dtv, um, vm, 0.0, bad)

        if overlap:
            # the overlapped chunk: one prologue exchange fills the first
            # generation of the double buffer (per CHUNK dispatch, off
            # the per-step path); the loop carries (ud, vd, um, vm, gen)
            # internally — the chunk's EXTERNAL state arity is unchanged,
            # so checkpoints, recovery and every tool keep working
            def chunk_kernel_overlap(u, v, p, t, nt):
                ud, vd = exchange_buffers(u, v)
                um, vm = buffer_maxima(ud, vd)

                def cond(c):
                    return jnp.logical_and(c[3] <= te, c[5] < chunk)

                def body(c):
                    if use_cap:
                        u, v, p, t, nt, k, ud, vd, um, vm, gen, cap = c
                        (u, v, p, t, nt, ud, vd, um, vm, gen,
                         _res, _it, _dt, cap) = step_overlap(
                            u, v, p, t, nt, ud, vd, um, vm, gen, cap)
                        return (u, v, p, t, nt, k + 1, ud, vd, um, vm,
                                gen, cap)
                    u, v, p, t, nt, k, ud, vd, um, vm, gen = c
                    (u, v, p, t, nt, ud, vd, um, vm, gen,
                     _res, _it, _dt) = step_overlap(
                        u, v, p, t, nt, ud, vd, um, vm, gen)
                    return u, v, p, t, nt, k + 1, ud, vd, um, vm, gen

                init = (u, v, p, t, nt, jnp.asarray(0, jnp.int32),
                        ud, vd, um, vm, nt)
                if use_cap:
                    init = init + (itermax_i,)
                out = lax.while_loop(cond, body, init)
                return out[0], out[1], out[2], out[3], out[4]

            def chunk_kernel_overlap_metrics(u, v, p, t, nt, m):
                ud, vd = exchange_buffers(u, v)
                um, vm = buffer_maxima(ud, vd)

                def cond(c):
                    return jnp.logical_and(c[3] <= te, c[5] < chunk)

                def body(c):
                    if use_cap:
                        (u, v, p, t, nt, k, ud, vd, um, vm, gen,
                         res, it, dtv, mum, mvm, bad, cap) = c
                        (u, v, p, t, nt, ud, vd, um, vm, gen,
                         res, it, dtv, cap) = step_overlap(
                            u, v, p, t, nt, ud, vd, um, vm, gen, cap)
                    else:
                        (u, v, p, t, nt, k, ud, vd, um, vm, gen,
                         res, it, dtv, mum, mvm, bad) = c
                        (u, v, p, t, nt, ud, vd, um, vm, gen,
                         res, it, dtv) = step_overlap(
                            u, v, p, t, nt, ud, vd, um, vm, gen)
                    res, it, dtv, mum, mvm, bad = _tm.metrics_step(
                        bad, nt, res, it, dtv, um, vm)
                    out = (u, v, p, t, nt, k + 1, ud, vd, um, vm, gen,
                           res, it, dtv, mum, mvm, bad)
                    return out + ((cap,) if use_cap else ())

                init = (u, v, p, t, nt, jnp.asarray(0, jnp.int32),
                        ud, vd, um, vm, nt,
                        m[_tm.M_RES], m[_tm.M_IT], m[_tm.M_DT],
                        m[_tm.M_UMAX], m[_tm.M_VMAX], m[_tm.M_BAD])
                if use_cap:
                    init = init + (itermax_i,)
                out = lax.while_loop(cond, body, init)
                (u, v, p, t, nt, _k, _ud, _vd, _um, _vm, _gen,
                 res, it, dtv, mum, mvm, bad) = out[:17]
                return u, v, p, t, nt, _tm.metrics_pack(
                    res, it, dtv, mum, mvm, 0.0, bad)

        def init_kernel():
            shape = (jl + 2, il + 2)
            u = jnp.full(shape, param.u_init, dtype)
            v = jnp.full(shape, param.v_init, dtype)
            p = jnp.full(shape, param.p_init, dtype)
            return u, v, p

        spec = P("j", "i")
        self._debug_sm = jax.jit(
            comm.shard_map(
                step_phases,
                in_specs=(spec, spec, spec, P()),
                out_specs=(spec,) * 6 + (P(), P(), P()),
                check_vma=not pallas_q,
            )
        )
        self._init_sm = jax.jit(
            comm.shard_map(init_kernel, in_specs=(), out_specs=(spec,) * 3)
        )
        mextra = (P(),) if metrics else ()
        if overlap:
            chunk_fn = (chunk_kernel_overlap_metrics if metrics
                        else chunk_kernel_overlap)
        else:
            chunk_fn = chunk_kernel_metrics if metrics else chunk_kernel
        self._chunk_sm = jax.jit(
            comm.shard_map(
                chunk_fn,
                in_specs=(spec, spec, spec, P(), P()) + mextra,
                out_specs=(spec, spec, spec, P(), P()) + mextra,
                check_vma=not pallas_q,
            )
        )
        _tm.emit("build", family="ns2d_dist",
                 grid=[self.jmax, self.imax], mesh=list(comm.dims),
                 trace_wall_s=round(time.perf_counter() - self._t0_build, 3),
                 phases=_dispatch.last("ns2d_dist_phases"))
        # static per-shard halo-exchange byte counts (the step-level
        # exchanges of the path actually dispatched; the pressure
        # solve's internal exchanges depend on CA depth/iteration count
        # and are excluded). Built unconditionally: the telemetry `halo`
        # record and the commcheck trace census (analysis/commcheck.py)
        # read the SAME dict, both priced by comm.halo_exchange_bytes.
        isz = jnp.dtype(dtype).itemsize
        rec = {
            "family": "ns2d_dist", "mesh": list(comm.dims),
            "shard": [jl, il], "dtype": str(jnp.dtype(dtype)),
            "path": "fused" if fused_k is not None else "jnp",
            "exchange_bytes_depth1":
                halo_exchange_bytes((jl, il), 1, isz),
        }
        if fused_k is not None:
            from ..ops.ns2d_fused import fused_deep_layout_2d

            fbr, _fh, fwp, fnb = fused_deep_layout_2d(
                jl, il, dtype, FUSE_DEEP_HALO - 1)
            full_cells = fnb * fbr * fwp
            rec.update(
                deep_halo=FUSE_DEEP_HALO,
                deep_exchange_bytes=halo_exchange_bytes(
                    (jl, il), FUSE_DEEP_HALO, isz),
                exchanges_per_step={"deep": 2},
                # the per-step PRE grid sweep (swept padded cells):
                # 1x full serial, 2x full for the PR 8 split halves,
                # the banded plan's sum when grid-restricted — the
                # BENCH/smoke metric the restriction is judged by
                pre_grid_cells=full_cells,
            )
            if self._exchange_depths:
                # per-tier depth map (ISSUE 17): the mapped dcn axis's
                # per-step strips are replaced by ONE depth-H capture
                # pair per H-step block — `exchanges_per_step["deep"]`
                # then covers the UNMAPPED (ici) axes only, and the
                # block-amortized capture rides exchanges_per_block.
                # The byte helpers (comm.exchange_schedule_*bytes) and
                # the commcheck census both read these four keys.
                rec.update(
                    exchange_depths=dict(self._exchange_depths),
                    depth_block=max(self._exchange_depths.values()),
                    exchanges_per_block={"deep": 2},
                    axes=list(comm.axis_names),
                )
            if overlap:
                # same per-step schedule (2 deep exchanges), but posted
                # at the end of the step into the double buffer; the
                # chunk prologue fills the first generation — commcheck's
                # census cross-check counts both classes
                rec.update(path="fused_overlap",
                           overlap="double_buffered",
                           exchanges_per_chunk={"deep": 2},
                           pre_grid_cells=(
                               self._overlap_plan["cells"]
                               if self._overlap_plan is not None
                               else 2 * full_cells),
                           pre_grid_cells_full=2 * full_cells)
        else:
            rec.update(exchanges_per_step={
                "depth1": 4 + (2 if gmasks is not None else 0),
                "shift": 2,
            })
        # hierarchical-exchange accounting (ROADMAP item 3): the axis->
        # tier map and the per-step DCN-tier bytes — 0 on single-tier
        # meshes, the first-class slow-fabric BENCH metric on a
        # multi-slice pod (tools/bench_trend.py gates it downward)
        from ..parallel.comm import exchange_schedule_tier_bytes

        rec["tier_map"] = dict(comm.tiers)
        rec["dcn_exchange_bytes"] = exchange_schedule_tier_bytes(
            comm, rec).get("dcn", 0)
        self._halo_rec = rec
        if _tm.enabled():
            _tm.emit("halo", **rec)

    # ------------------------------------------------------------------
    def _halo_record(self) -> dict:
        """The static halo-exchange accounting of the path this build
        dispatched — the dict the telemetry `halo` record emits, exposed
        so analysis/commcheck.py can cross-check it against the traced
        collective census without arming PAMPI_TELEMETRY (which would
        change the traced program)."""
        return dict(self._halo_rec)

    def _rebuild_chunk(self):
        """Rebuild every traced kernel against the solver's CURRENT
        attributes (recovery dt clamp) — the rollback-recovery rebuild hook
        (models/_driver.RingRecovery). Advances the fault-injection
        generation (see models/ns2d._rebuild_chunk)."""
        self._field_faults = _fi.take_field_faults()
        self._build()
        return self._chunk_sm

    def initial_state(self) -> tuple:
        """(u, v, p, t, nt[, metrics]) matching the built chunk's arity
        (the NS-2D convention — see models/ns2d.initial_state)."""
        time_dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
        scalars = (jnp.asarray(self.t, time_dtype),
                   jnp.asarray(self.nt, jnp.int32))
        if self._metrics:
            scalars = scalars + (_tm.metrics_init(),)
        return (self.u, self.v, self.p) + tuple(
            self.comm.replicate(x) for x in scalars)

    def run(self, progress: bool = True, on_sync=None) -> None:
        """The dist drive loop now IS models/_driver.drive_chunks (PR 4):
        same chunk semantics as before (dispatch, read t, sync — the
        historical while-t<=te loop), plus the shared failure protocol the
        single-device families already had — transient-fault retry with a
        replenishing budget and divergence rollback-recovery when a ring
        is armed. No pallas rebuild hook here (the per-shard kernels have
        no per-backend rebuild path), so non-transient chunk failures
        propagate unchanged."""
        from ._driver import coord_ckpt_cadence, drive_chunks, make_recovery

        bar = Progress(self.param.te, enabled=progress and not _flags.verbose())
        state = self.initial_state()
        rec = (_tm.ChunkRecorder("ns2d_dist", self.nt)
               if self._metrics else None)
        recover = make_recovery(self, "ns2d_dist", time_index=3,
                                recorder=rec)

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
        # multi-process transient retry rides the chunk-boundary agreement
        # protocol (parallel/coordinator.py): every rank takes the same
        # retry/rollback/checkpoint decision from the allgathered fault
        # word, so the PR 4 single-controller ban (transient_budget=0 —
        # a rank-local re-dispatch would desynchronize collectives) is
        # lifted whenever the coordinator is armed. tpu_coord off
        # restores the ban: a fault kills the job cleanly.
        from ..parallel.coordinator import make_coordinator

        coord = make_coordinator(self.param, "ns2d_dist")
        budget = 1 if (coord is not None or jax.process_count() == 1) else 0
        ckpt_every, on_ckpt = coord_ckpt_cadence(self, coord, publish)
        # PAMPI_XPROF: device-trace the drive loop (no-op when unset);
        # the step count rides the xprof record so report tooling can
        # normalize device times per step
        nt0 = self.nt
        with _xprof.capture("ns2d_dist", steps=lambda: self.nt - nt0):
            state = drive_chunks(
                state, self._chunk_sm, self.param.te, 3, bar,
                retry=lambda: None, on_state=on_state,
                replenish_after=self.param.tpu_retry_replenish,
                recover=recover, transient_budget=budget,
                coordinator=coord, ckpt_every=ckpt_every,
                on_ckpt=on_ckpt, family="ns2d_dist",
                ledger=getattr(self, "_fault_ledger", None))
            publish(state)
        self._emit_exchange_span()

    def _emit_exchange_span(self) -> None:
        """The ROADMAP-mandated `exchange` span: the serial critical-path
        cost of one step's declared halo schedule, measured on an
        exchange-only program (parallel/comm.time_exchange_ms) AFTER the
        drive loop so the probe dispatches never pollute chunk timings or
        the captured trace. Together with the xprof record's exchange
        device/exposed split this is the comm-hidden-fraction input
        (tools/telemetry_report.comm_hidden_fraction)."""
        if not _tm.enabled():
            return
        from ..parallel.comm import exchange_schedule_bytes, time_exchange_ms

        rec = self._halo_record()
        _tm.emit_span(
            f"{rec['family']}.exchange",
            time_exchange_ms(self.comm, rec),
            path=rec["path"], mesh=rec["mesh"], shard=rec["shard"],
            bytes_per_step=exchange_schedule_bytes(rec),
            mode="serial_probe")

    # -- collect: stacked extended blocks -> full reference-layout array -
    def _assemble(self, stacked) -> np.ndarray:
        """Rebuild the (jmax+2, imax+2) array from stacked extended blocks:
        interiors everywhere, ghost strips taken from wall shards
        (≙ commCollectResult's ghost-strip + assembly, comm.c:246-427)."""
        arr = self.comm.collect(stacked)  # multihost-safe host gather
        Pj, Pi = self.comm.dims
        jl, il = self.jl, self.il
        # assemble at the PADDED global shape, crop the dead tail at the end
        # (identity when divisible); the global ghost ring rows/cols land in
        # block interiors when ragged, so the crop keeps them
        full = np.zeros((Pj * jl + 2, Pi * il + 2))
        for cj in range(Pj):
            for ci in range(Pi):
                b = arr[
                    cj * (jl + 2) : (cj + 1) * (jl + 2),
                    ci * (il + 2) : (ci + 1) * (il + 2),
                ]
                full[1 + cj * jl : 1 + (cj + 1) * jl, 1 + ci * il : 1 + (ci + 1) * il] = b[
                    1:-1, 1:-1
                ]
                if cj == 0:
                    full[0, 1 + ci * il : 1 + (ci + 1) * il] = b[0, 1:-1]
                if cj == Pj - 1:
                    full[-1, 1 + ci * il : 1 + (ci + 1) * il] = b[-1, 1:-1]
                if ci == 0:
                    full[1 + cj * jl : 1 + (cj + 1) * jl, 0] = b[1:-1, 0]
                if ci == Pi - 1:
                    full[1 + cj * jl : 1 + (cj + 1) * jl, -1] = b[1:-1, -1]
                if cj == 0 and ci == 0:
                    full[0, 0] = b[0, 0]
                if cj == 0 and ci == Pi - 1:
                    full[0, -1] = b[0, -1]
                if cj == Pj - 1 and ci == 0:
                    full[-1, 0] = b[-1, 0]
                if cj == Pj - 1 and ci == Pi - 1:
                    full[-1, -1] = b[-1, -1]
        return full[: self.jmax + 2, : self.imax + 2]

    def fields(self):
        return self._assemble(self.u), self._assemble(self.v), self._assemble(self.p)

    # -- elastic-checkpoint contract (utils/checkpoint.save_elastic) ---
    def global_shape(self) -> tuple:
        return (self.jmax + 2, self.imax + 2)

    def global_fields(self) -> dict:
        """MESH-INDEPENDENT reference-layout globals: same assembly as
        `_assemble` (interiors everywhere, ghost ring from wall shards)
        through the shared dtype-preserving N-D helper — what makes an
        elastic checkpoint restorable on a DIFFERENT mesh. Collective
        under a multi-process launch (CartComm.collect)."""
        from ..utils.checkpoint import assemble_global

        return {
            f: assemble_global(
                self.comm.collect(getattr(self, f)), self.comm.dims,
                (self.jl, self.il), (self.jmax, self.imax))
            for f in ("u", "v", "p")
        }

    def set_global_fields(self, fields: dict) -> None:
        """The elastic-restore resharding step: re-block the global
        array for THIS solver's mesh and place it on the solver's own
        NamedSharding — the saved mesh never constrains the target."""
        from ..utils.checkpoint import scatter_blocks

        for f, arr in fields.items():
            cur = getattr(self, f)
            stacked = scatter_blocks(
                np.asarray(arr), self.comm.dims, (self.jl, self.il))
            new = jnp.asarray(stacked, cur.dtype)
            sh = getattr(cur, "sharding", None)
            if sh is not None:
                new = jax.device_put(new, sh)
            setattr(self, f, new)

    def write_result(
        self, pressure_path: str = "pressure.dat", velocity_path: str = "velocity.dat"
    ) -> None:
        # fields() gathers collectively — all processes join; rank 0 writes
        u, v, p = self.fields()
        if self.comm.is_master:
            write_pressure(p, self.dx, self.dy, pressure_path)
            write_velocity(u, v, self.dx, self.dy, velocity_path)
