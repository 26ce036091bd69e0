"""Distributed NS-3D over a 3-D ("k","j","i") device mesh.

This COMPLETES the capability assignment-6 hands out as a skeleton: the
reference's `comm.c` ships its `_MPI` bodies unfinished (`// fill`,
comm.c:124-239,479-483), so the 3-D Cartesian-decomposed solver never runs
distributed in the reference tree. Here the full 3-D choreography runs over
the mesh comm layer (halo_exchange = 6-face ppermute, halo_shift = staggered
donor edges, psum/pmax reductions), with the same EXACT-sequential-parity
policy as NS-2D (see models/ns2d_dist.py): halos refreshed before every
cross-shard read makes the distributed trajectory equal the single-device
solver bitwise (mod reduction order) on any mesh shape.

Exchange points per step (mirroring the reference's own calls where they
exist): u/v/w at step start (maxElement ghost parity), u/v/w after BCs
(≙ computeFG's commExchange, solver.c:635-637), F/G/H one-directional shift
before RHS (≙ commShift, solver.c:161), p once per n fused red-black
iterations at halo depth 2n (communication-avoiding; ≙ solve's per-pass
commExchange :208, traded latency-for-bandwidth the ICI way) and after the
solve loop (≙ trailing :288).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..ops import ns3d as ops
from .ns3d import sor_coefficients_3d, write_vtk_result
from ..parallel.comm import (
    master_print,
    CartComm,
    halo_exchange,
    halo_exchange_bytes,
    halo_shift,
    reduction,
)
from ..parallel.stencil2d import (
    ca_halo,
    ca_inner,
    ca_supported,
    embed_deep,
    strip_deep,
)
from ..parallel.octants_dist import (
    o_exchange,
    octants_dispatch,
    pack_ext_to_o,
    unpack_o_to_ext,
)
from ..parallel.stencil3d import (
    ca_masks_3d,
    ca_rb_iters_3d,
    face_flags,
    rb_exchange_per_sweep_3d,
)
from ..utils import dispatch as _dispatch
from ..utils import faultinject as _fi
from ..utils import flags as _flags
from ..utils import profiling as _prof
from ..utils import telemetry as _tm
from ..utils import xprof as _xprof
from ._driver import clamped_dt
from ..utils.grid import Grid
from ..utils.params import Parameter
from ..utils.precision import resolve_dtype
from ..utils.progress import Progress
from ..utils.vtkio import VtkWriter

NOSLIP, SLIP, OUTFLOW, PERIODIC = 1, 2, 3, 4


def _sel(pred, new, old):
    return jnp.where(pred, new, old)


class NS3DDistSolver:
    """Mesh-parallel NS-3D solver; same .par interface as NS3DSolver."""

    CHUNK = 32

    def __init__(self, param: Parameter, comm: CartComm | None = None, dtype=None):
        self._t0_build = time.perf_counter()
        # trace-time telemetry gate (utils/flags.py convention)
        metrics = _tm.enabled()
        self._metrics = metrics
        if dtype is None:
            dtype = resolve_dtype(param.tpu_dtype,
                                  record_key="ns3d_dist_dtype")
        self.param = param
        self.dtype = dtype
        self.comm = comm if comm is not None else CartComm(
            ndims=3, extents=(param.kmax, param.jmax, param.imax),
            tiers=param.tpu_mesh_tiers,
        )
        self.grid = Grid(
            imax=param.imax,
            jmax=param.jmax,
            kmax=param.kmax,
            xlength=param.xlength,
            ylength=param.ylength,
            zlength=param.zlength,
        )
        g = self.grid
        # ragged pad-with-mask decomposition (parallel/ragged3d.py): any
        # grid runs on any mesh (≙ sizeOfRank, assignment-6/src/comm.c:19-22)
        self.kl, self.jl, self.il = self.comm.local_shape(
            (g.kmax, g.jmax, g.imax), ragged=True
        )
        Pk, Pj, Pi = self.comm.dims
        self.ragged = (
            self.kl * Pk != g.kmax or self.jl * Pj != g.jmax
            or self.il * Pi != g.imax
        )
        param = _dispatch.resolve_solver(
            param, obstacles=bool(param.obstacles.strip()),
            ragged=self.ragged,
        )
        self.param = param
        # round 5 (VERDICT r4 item 2): obstacles compose with ragged
        # decompositions in 3-D too (the jnp CA path; the 3-D kernel stays
        # divisible-only — obstacle3d.make_dist_obstacle_solver_3d).
        # mg/fft stay divisible-only (coarsening/diagonalization need
        # exact extents).
        if self.ragged and param.tpu_solver in ("mg", "fft"):
            raise ValueError(
                f"tpu_solver {param.tpu_solver} needs a divisible grid/mesh "
                f"(grid {g.kmax}x{g.jmax}x{g.imax} on {self.comm.dims}); "
                "ragged pad-with-mask runs use tpu_solver sor (obstacles "
                "compose)"
            )
        inv_sqr_sum = 1.0 / g.dx**2 + 1.0 / g.dy**2 + 1.0 / g.dz**2
        self.dt_bound = 0.5 * param.re / inv_sqr_sum
        self.t = 0.0
        self.nt = 0
        # flag-field obstacles: GLOBAL static geometry; every shard slices
        # its mask blocks inside the kernel (ops/obstacle3d.shard_masks_3d)
        if param.obstacles.strip():
            if param.tpu_solver == "fft":
                raise ValueError(
                    "tpu_solver fft cannot solve obstacle flag fields (the "
                    "stencil is not constant-coefficient); use sor or mg"
                )
            from ..ops import obstacle3d as obst3

            fluid = obst3.build_fluid_3d(
                g.imax, g.jmax, g.kmax, g.dx, g.dy, g.dz, param.obstacles
            )
            self.masks = obst3.make_masks_3d(
                fluid, g.dx, g.dy, g.dz, param.omg, dtype
            )
        else:
            self.masks = None
        self._dt_scale = 1.0  # recovery dt clamp (models/_driver.clamped_dt)
        # fault-injection generation: taken here and in _rebuild_chunk
        # only (see models/ns2d.py for the rationale)
        self._field_faults = _fi.take_field_faults()
        self._build()
        self.u, self.v, self.w, self.p = self._init_sm()

    # ------------------------------------------------------------------
    def _build(self):
        comm = self.comm
        param = self.param
        g = self.grid
        dtype = self.dtype
        metrics = self._metrics  # trace-time telemetry gate (see __init__)
        # field-fault injection + recovery dt clamp: both trace-time, both
        # identity when unarmed (the PAMPI_FAULTS-unset jaxpr contract);
        # the generation is taken by __init__/_rebuild_chunk, not here
        field_faults = self._field_faults
        dt_scale = self._dt_scale
        kl, jl, il = self.kl, self.jl, self.il
        dx, dy, dz = g.dx, g.dy, g.dz

        bcs = {
            "top": param.bcTop,
            "bottom": param.bcBottom,
            "left": param.bcLeft,
            "right": param.bcRight,
            "front": param.bcFront,
            "back": param.bcBack,
        }
        problem = param.name.replace("3d", "")

        # -- wall-gated BCs (≙ commIsBoundary-guarded face loops) --------
        def set_bcs_divisible(u, v, w):
            return ops.set_boundary_conditions_3d(
                u, v, w, bcs, flags=face_flags(comm)
            )

        def set_special_bc_divisible(u):
            flags = face_flags(comm)
            if problem == "dcavity":
                # lid plane u[k, jl+1, i], global k in 1..kmax-1, i in
                # 1..imax-1: exclude last interior k/i on the hi-wall shards
                # (reference loop-bound quirk, solver.c:587-594)
                kmask = jnp.zeros(kl + 2, dtype).at[1:-1].set(1.0)
                kmask = kmask.at[-2].mul(1.0 - flags["back"].astype(dtype))
                imask = jnp.zeros(il + 2, dtype).at[1:-1].set(1.0)
                imask = imask.at[-2].mul(1.0 - flags["right"].astype(dtype))
                m2 = kmask[:, None] * imask[None, :]
                lid = 2.0 - u[:, -2, :]
                new_plane = jnp.where(m2 > 0, lid, u[:, -1, :])
                u = u.at[:, -1, :].set(_sel(flags["top"], new_plane, u[:, -1, :]))
            elif problem == "canal":
                cur = u[:, :, 0]
                new_plane = cur.at[1:-1, 1:-1].set(2.0)
                u = u.at[:, :, 0].set(_sel(flags["left"], new_plane, cur))
            return u

        def fgh_fixups_divisible(f, g_, h, u, v, w):
            flags = face_flags(comm)
            f = f.at[1:-1, 1:-1, 0].set(
                _sel(flags["left"], u[1:-1, 1:-1, 0], f[1:-1, 1:-1, 0])
            )
            f = f.at[1:-1, 1:-1, -2].set(
                _sel(flags["right"], u[1:-1, 1:-1, -2], f[1:-1, 1:-1, -2])
            )
            g_ = g_.at[1:-1, 0, 1:-1].set(
                _sel(flags["bottom"], v[1:-1, 0, 1:-1], g_[1:-1, 0, 1:-1])
            )
            g_ = g_.at[1:-1, -2, 1:-1].set(
                _sel(flags["top"], v[1:-1, -2, 1:-1], g_[1:-1, -2, 1:-1])
            )
            h = h.at[0, 1:-1, 1:-1].set(
                _sel(flags["front"], w[0, 1:-1, 1:-1], h[0, 1:-1, 1:-1])
            )
            h = h.at[-2, 1:-1, 1:-1].set(
                _sel(flags["back"], w[-2, 1:-1, 1:-1], h[-2, 1:-1, 1:-1])
            )
            return f, g_, h

        # -- ragged pad-with-mask wall handling (parallel/ragged3d.py) ---
        if self.ragged:
            from ..parallel import ragged3d as rg3

            def set_bcs(u, v, w):
                return rg3.set_bcs_3d_ragged(
                    u, v, w, bcs, comm, kl, jl, il, g.kmax, g.jmax, g.imax
                )

            def set_special_bc(u):
                return rg3.set_special_bc_3d_ragged(
                    u, problem, comm, kl, jl, il, g.kmax, g.jmax, g.imax
                )

            def fgh_fixups(f, g_, h, u, v, w):
                return rg3.fgh_fixups_ragged(
                    f, g_, h, u, v, w, comm, kl, jl, il,
                    g.kmax, g.jmax, g.imax,
                )
        else:
            set_bcs = set_bcs_divisible
            set_special_bc = set_special_bc_divisible
            fgh_fixups = fgh_fixups_divisible

        # -- pressure solve --------------------------------------------
        factor, idx2, idy2, idz2 = sor_coefficients_3d(dx, dy, dz, param.omg)
        epssq = param.eps * param.eps
        norm = float(g.imax * g.jmax * g.kmax)

        def _solve_sor(p, rhs, cap=None):
            """Communication-avoiding red-black solve (stencil3d.ca_*): one
            depth-2n halo exchange per n exact local iterations, n clamped by
            the shard extents (tpu_ca_inner; n=1 still halves the per-
            iteration message count vs exchange-per-half-sweep while keeping
            the trajectory identical). Shards with an extent of 1 cannot ship
            depth-2 strips from owned cells — they use the classic
            exchange-per-half-sweep fallback. `cap` is the residual-adaptive
            budget (tpu_itermax_adaptive); None = the historical trace."""
            limit = param.itermax if cap is None else cap
            supported = ca_supported(kl, jl, il)
            n = ca_inner(param, kl, jl, il) if supported else 1
            H = ca_halo(n, ragged=self.ragged) if supported else 1
            masks = ca_masks_3d(kl, jl, il, H, g.kmax, g.jmax, g.imax, dtype)
            pd = embed_deep(p, H)
            rd = halo_exchange(embed_deep(rhs, H), comm, depth=H)

            def cond(c):
                return jnp.logical_and(c[1] >= epssq, c[2] < limit)

            def body(c):
                pd, _, it = c
                if supported:
                    pd = halo_exchange(pd, comm, depth=H)
                    pd, r2 = ca_rb_iters_3d(
                        pd, rd, n, masks, factor, idx2, idy2, idz2
                    )
                else:
                    pd, r2 = rb_exchange_per_sweep_3d(
                        pd, rd, masks, comm, factor, idx2, idy2, idz2,
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
            overlapped schedule — see models/ns2d_dist._solve_sor_split):
            same n-iteration residual cadence, bitwise the CA
            trajectory, every depth-1 exchange posted behind an
            interior update (stencil3d.rb_split_iter_3d)."""
            from ..parallel import overlap as _ovl
            from ..parallel.comm import persistent_exchange
            from ..parallel.stencil3d import rb_split_iter_3d

            limit = param.itermax if cap is None else cap
            supported = ca_supported(kl, jl, il)
            n = ca_inner(param, kl, jl, il) if supported else 1
            masks = ca_masks_3d(kl, jl, il, 1, g.kmax, g.jmax, g.imax,
                                dtype)
            int_mask = _ovl.interior_mask(
                (kl, jl, il), 2,
                partitioned=tuple(d > 1 for d in comm.dims))
            sched1 = persistent_exchange(comm, 1, dtype)

            def cond(c):
                return jnp.logical_and(c[1] >= epssq, c[2] < limit)

            def body(c):
                p, _, it = c
                r2 = None
                for _k in range(n):
                    p, r2 = rb_split_iter_3d(
                        p, rhs, masks, sched1, int_mask, factor, idx2,
                        idy2, idz2, ragged=self.ragged)
                res = reduction(r2, comm, "sum") / norm
                if _flags.debug():
                    master_print(comm, "{} Residuum: {}", it + (n - 1), res)
                return p, res, it + n

            p, res, it = lax.while_loop(
                cond, body,
                (p, jnp.asarray(1.0, dtype), jnp.asarray(0, jnp.int32)),
            )
            return halo_exchange(p, comm), res, it

        # -- octant-layout production pressure solve (the round-3 wiring of
        # the 4.9x/iteration octant kernel into the distributed path; same
        # dispatch contract as models/ns2d_dist's quarters) ---------------
        plain_sor = param.tpu_solver not in ("mg", "fft") and self.masks is None
        rb_o, og, n_o, pallas_o = octants_dispatch(
            param, g.kmax, g.jmax, g.imax, kl, jl, il, dx, dy, dz, dtype,
            "ns3d_dist", plain_sor=plain_sor and not self.ragged,
            dims=comm.dims,
        )
        if rb_o is None:
            tag = (
                "jnp_ca" if plain_sor else f"other_{param.tpu_solver}"
                if self.masks is None else "obstacle_jnp"
            )
            if self.ragged:
                tag += " ragged"
            _dispatch.record("ns3d_dist", tag)
        self._pallas_o = pallas_o

        def _solve_sor_octants(p, rhs, cap=None):
            """Stacked-octant CA solve on the halo-1 extended blocks; returns
            the exchanged halo-1 block like _solve_sor (adaptUVW reads p
            across shard edges, ≙ the trailing commExchange solver.c:288)."""
            from ..parallel.comm import get_offsets

            limit = param.itermax if cap is None else cap
            koff = get_offsets("k", kl)
            joff = get_offsets("j", jl)
            ioff = get_offsets("i", il)
            qoffs = jnp.stack([
                (koff // 2).astype(jnp.int32),
                (joff // 2).astype(jnp.int32),
                (ioff // 2).astype(jnp.int32),
            ])
            ro = o_exchange(pack_ext_to_o(rhs, og), comm, og)
            xo = pack_ext_to_o(p, og)

            def cond(c):
                return jnp.logical_and(c[1] >= epssq, c[2] < limit)

            def body(c):
                xo, _, it = c
                xo = o_exchange(xo, comm, og)
                xo, r2 = rb_o(qoffs, xo, ro)
                res = reduction(r2, comm, "sum") / norm
                if _flags.debug():
                    master_print(comm, "{} Residuum: {}", it + (n_o - 1), res)
                return xo, res, it + n_o

            xo, res, it = lax.while_loop(
                cond, body,
                (xo, jnp.asarray(1.0, dtype), jnp.asarray(0, jnp.int32)),
            )
            return halo_exchange(unpack_o_to_ext(xo, og), comm), res, it

        # pre-resolution of the overlap knob for the solve builders (see
        # models/ns2d_dist.py — selects the sweep-split smoother forms,
        # bitwise the serial forms either way; statically-known
        # ineligibility mirrored, fused-probe failure healed by the
        # serial MG rebuild at the sweep_split record)
        ovl_pre = (param.tpu_overlap != "off"
                   and not field_faults
                   and param.tpu_fuse_phases != "off"
                   and (param.tpu_overlap == "on"
                        or jax.default_backend() == "tpu"))
        mg_serial_rebuild = None
        if param.tpu_solver == "fft":
            from ..ops.dctpoisson import make_dist_dct_solve_3d

            solve = make_dist_dct_solve_3d(
                comm, g.imax, g.jmax, g.kmax, kl, jl, il, dx, dy, dz, dtype
            )
        elif param.tpu_solver == "mg":
            if self.masks is not None:
                # 3-D obstacle multigrid on a mesh (round 4)
                from ..ops.multigrid import make_dist_obstacle_mg_solve_3d

                solve, mg_pallas = make_dist_obstacle_mg_solve_3d(
                    comm, g.imax, g.jmax, g.kmax, kl, jl, il, dx, dy, dz,
                    param.eps, param.itermax, self.masks, dtype,
                    stall_rtol=param.tpu_mg_stall_rtol,
                    fused=param.tpu_mg_fused,
                )
                # the MG factory reports per-shard Pallas smoothing:
                # relax check_vma (the obstacle-solver contract)
                pallas_o = pallas_o or mg_pallas
                self._pallas_o = pallas_o
            else:
                from ..ops.multigrid import make_dist_mg_solve_3d

                solve, mg_pallas = make_dist_mg_solve_3d(
                    comm, g.imax, g.jmax, g.kmax, kl, jl, il, dx, dy, dz,
                    param.eps, param.itermax, dtype,
                    stall_rtol=param.tpu_mg_stall_rtol, split=ovl_pre,
                    fused=param.tpu_mg_fused,
                )
                pallas_o = pallas_o or mg_pallas
                self._pallas_o = pallas_o
                if ovl_pre:
                    def mg_serial_rebuild():
                        s2, _ = make_dist_mg_solve_3d(
                            comm, g.imax, g.jmax, g.kmax, kl, jl, il,
                            dx, dy, dz, param.eps, param.itermax, dtype,
                            stall_rtol=param.tpu_mg_stall_rtol,
                            split=False, fused=param.tpu_mg_fused,
                        )
                        return s2
        elif self.masks is not None:
            from ..ops.obstacle3d import make_dist_obstacle_solver_3d

            solve, obs_pallas = make_dist_obstacle_solver_3d(
                comm, g.imax, g.jmax, g.kmax, kl, jl, il, dx, dy, dz,
                param.eps, param.itermax, self.masks, dtype,
                ca_n=param.tpu_ca_inner, sor_inner=param.tpu_sor_inner,
                ragged=self.ragged,
            )
            # relax check_vma when the obstacle solver reports it
            # dispatched its per-shard Pallas kernel
            pallas_o = pallas_o or obs_pallas
            self._pallas_o = pallas_o
        elif rb_o is not None:
            solve = _solve_sor_octants
        else:
            solve = _solve_sor

        # -- fused step-phase kernels (ops/ns3d_fused.py): the per-shard
        # non-solve phases collapse into two global-coordinate-gated Pallas
        # kernels around the solve (PRE on the depth-H deep-halo block, POST
        # on the plain extended block) — the 3-D twin of the NS-2D wiring
        # (models/ns2d_dist.py). Ragged shards are the same kernels at
        # uneven block bounds (global gating + the POST live-mask multiply);
        # obstacle runs feed the per-shard global-constant flag slices at
        # call time (fluid=True).
        from ..ops.ns3d_fused import FUSE_DEEP_HALO, probe_fused_3d

        fuse_why_not = None
        if min(kl, jl, il) < FUSE_DEEP_HALO:
            fuse_why_not = f"shard extents < deep halo {FUSE_DEEP_HALO}"
        fused_k = None
        if _dispatch.resolve_fuse_phases(
            param, "auto", dtype, probe_fused_3d, "ns3d_dist_phases",
            why_not=fuse_why_not,
        ):
            from ..ops import ns3d_fused as nf3

            try:
                pre_k, pad_deep, unpad_deep, _hk = nf3.make_fused_pre_3d(
                    param, g.kmax, g.jmax, g.imax, dx, dy, dz, dtype,
                    kl=kl, jl=jl, il=il, ext_pad=FUSE_DEEP_HALO - 1,
                    fluid=True if self.masks is not None else None,
                )
                post_k, pad_ext, unpad_ext, _hk2 = nf3.make_fused_post_3d(
                    param, g.kmax, g.jmax, g.imax, dx, dy, dz, dtype,
                    kl=kl, jl=jl, il=il,
                    fluid=True if self.masks is not None else None,
                    ragged=self.ragged,
                )
                fused_k = (pre_k, post_k)
                pallas_o = True
                self._pallas_o = True
            except ValueError as exc:  # VMEM-infeasible shard geometry
                _dispatch.record("ns3d_dist_phases", f"jnp ({exc})")

        # -- comm/compute overlap: the 3-D twin of the NS-2D wiring (see
        # models/ns2d_dist.py — double-buffered deep blocks, split PRE,
        # carried CFL maxima; `off` stays bitwise the serial schedule)
        ovl_why = None
        if fused_k is None:
            ovl_why = "needs the fused deep-halo step (tpu_fuse_phases)"
        elif field_faults:
            ovl_why = ("PAMPI_FAULTS field faults armed (in-step writes "
                       "would postdate the posted exchange)")
        overlap = _dispatch.resolve_overlap(
            param, "overlap_ns3d_dist", why_not=ovl_why)
        self._overlap = overlap
        self._overlap_plan = None  # set by the overlap block when the
        #   grid-restricted halves dispatch (tpu_overlap_restrict)
        # sweep split (see models/ns2d_dist.py)
        if overlap and solve is _solve_sor:
            solve = _solve_sor_split
            _dispatch.record("sweep_split_ns3d_dist", "split (jnp rb-sor)")
        elif overlap and param.tpu_solver == "mg" and self.masks is None:
            _dispatch.record("sweep_split_ns3d_dist",
                             "split (mg jnp-smoother levels)")
        elif not overlap and mg_serial_rebuild is not None:
            # the pre-resolution guessed overlap but the fused probe
            # failed at build: drop the split smoother so the traced
            # program matches the recorded serial schedule
            solve = mg_serial_rebuild()
        elif overlap:
            _dispatch.record("sweep_split_ns3d_dist",
                             "serial (pallas/other solve)")

        # residual-adaptive itermax (see models/ns2d_dist.py): the cap
        # rides the chunk carry only, resets per chunk dispatch; dist
        # SOR paths only
        adapt_n = int(param.tpu_itermax_adaptive)
        use_cap = adapt_n > 0 and solve in (
            _solve_sor, _solve_sor_split, _solve_sor_octants)
        # the solve (its layout pack/unpack and exchanges included) under
        # one named scope, the rest of the step under "phases"
        solve = _prof.scoped("solve", solve)
        if adapt_n > 0:
            _dispatch.record(
                "itermax_adaptive_ns3d_dist",
                f"adaptive (+{adapt_n} slack)" if use_cap
                else "static (solve path carries no sweep budget)")
        itermax_i = jnp.asarray(param.itermax, jnp.int32)

        def next_cap(res, it):
            return jnp.where(res < epssq,
                             jnp.minimum(itermax_i, it + adapt_n),
                             itermax_i)

        gmasks = self.masks
        if gmasks is not None:
            from ..ops.obstacle3d import (
                adapt_uvw_obstacle,
                apply_obstacle_velocity_bc_3d,
                mask_fgh,
                shard_masks_3d,
            )

            # ragged ceil-division overhang (0 when divisible): HI-side
            # zero-pad so trailing-shard mask slices never clamp
            from ..parallel.stencil2d import ceil_overhang

            over_k = ceil_overhang(comm.axis_size("k"), kl, g.kmax)
            over_j = ceil_overhang(comm.axis_size("j"), jl, g.jmax)
            over_i = ceil_overhang(comm.axis_size("i"), il, g.imax)

            def local_masks():
                # must run INSIDE the shard_map trace (mesh offsets)
                return shard_masks_3d(gmasks, kl, jl, il,
                                      over_k, over_j, over_i)

            def fused_flag_blocks():
                """Per-shard deep-halo and extended slices of the global 0/1
                fluid flag for the fused kernels (the shard_masks_3d
                global-constant-slice convention), in the kernels' padded
                layouts — see models/ns2d_dist.py's twin for the invariants."""
                from ..parallel.comm import get_offsets

                H = FUSE_DEEP_HALO
                koff = get_offsets("k", kl)
                joff = get_offsets("j", jl)
                ioff = get_offsets("i", il)
                fl = gmasks.fluid
                wide = jnp.pad(fl, (
                    (H - 1, over_k + H - 1), (H - 1, over_j + H - 1),
                    (H - 1, over_i + H - 1),
                ))
                deep = lax.dynamic_slice(
                    wide, (koff, joff, ioff),
                    (kl + 2 * H, jl + 2 * H, il + 2 * H),
                )
                hi = jnp.pad(fl, ((0, over_k), (0, over_j), (0, over_i)))
                ext = lax.dynamic_slice(
                    hi, (koff, joff, ioff), (kl + 2, jl + 2, il + 2)
                )
                return pad_deep(deep), pad_ext(ext)

        def cfl_from_maxima(umax, vmax, wmax):
            # the scalar tail, shared with the overlapped step (whose
            # maxima ride the carry from the previous POST kernel)
            inf = jnp.asarray(jnp.inf, dtype)
            dt = jnp.minimum(
                jnp.asarray(self.dt_bound, dtype),
                jnp.minimum(
                    jnp.where(umax > 0, dx / umax, inf),
                    jnp.minimum(
                        jnp.where(vmax > 0, dy / vmax, inf),
                        jnp.where(wmax > 0, dz / wmax, inf),
                    ),
                ),
            )
            return dt * param.tau

        def compute_dt(u, v, w):
            umax = reduction(jnp.max(jnp.abs(u)), comm, "max")
            vmax = reduction(jnp.max(jnp.abs(v)), comm, "max")
            wmax = reduction(jnp.max(jnp.abs(w)), comm, "max")
            return cfl_from_maxima(umax, vmax, wmax)

        adaptive = param.tau > 0.0
        idx_dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32

        def step(u, v, w, p, t, nt, cap=None):
            with jax.named_scope("phases"):
                u, v, w, p = _fi.apply_field_faults(field_faults, nt, u=u, v=v,
                                                    w=w, p=p)
                u = halo_exchange(u, comm)
                v = halo_exchange(v, comm)
                w = halo_exchange(w, comm)
                dt = compute_dt(u, v, w) if adaptive else jnp.asarray(param.dt, dtype)
                dt = clamped_dt(dt, dt_scale)
                u, v, w = set_bcs(u, v, w)
                u = set_special_bc(u)
                u = halo_exchange(u, comm)
                v = halo_exchange(v, comm)
                w = halo_exchange(w, comm)
                if gmasks is not None:
                    # needs the fully-exchanged post-BC state (the single-device
                    # op reads the whole array at once); its own halo-cell
                    # outputs are refreshed by one more exchange
                    u, v, w = apply_obstacle_velocity_bc_3d(u, v, w, local_masks())
                    u = halo_exchange(u, comm)
                    v = halo_exchange(v, comm)
                    w = halo_exchange(w, comm)
                f, g_, h = ops.compute_fgh_interior(
                    u, v, w, dt, param.re, param.gx, param.gy, param.gz,
                    param.gamma, dx, dy, dz,
                )
                f, g_, h = fgh_fixups(f, g_, h, u, v, w)
                if gmasks is not None:
                    f, g_, h = mask_fgh(f, g_, h, u, v, w, local_masks())
                f = halo_shift(f, comm, "i")
                g_ = halo_shift(g_, comm, "j")
                h = halo_shift(h, comm, "k")
                rhs = ops.compute_rhs(f, g_, h, dt, dx, dy, dz)
            p, res, it = (solve(p, rhs, cap) if cap is not None
                          else solve(p, rhs))

            with jax.named_scope("phases"):
                def adapt(u, v, w):
                    if gmasks is not None:
                        return adapt_uvw_obstacle(
                            u, v, w, f, g_, h, p, dt, dx, dy, dz, local_masks()
                        )
                    return ops.adapt_uvw(u, v, w, f, g_, h, p, dt, dx, dy, dz)

                if not self.ragged:
                    u, v, w = adapt(u, v, w)
                else:
                    # ragged projection: only the true global interior updates;
                    # interior-stored ghost planes keep their BC-era values and
                    # dead cells are zeroed (see models/ns2d_dist.py). One
                    # gating block for the plain AND obstacle projections.
                    from ..parallel import ragged3d as rg3

                    gk, gj, gi = rg3.global_index_grids(comm, kl, jl, il)
                    interior = (
                        (gk >= 1) & (gk <= g.kmax)
                        & (gj >= 1) & (gj <= g.jmax)
                        & (gi >= 1) & (gi <= g.imax)
                    )
                    live = rg3.live_masks_3d(
                        comm, kl, jl, il, g.kmax, g.jmax, g.imax, dtype
                    )
                    ua, va, wa = adapt(u, v, w)
                    u = jnp.where(interior, ua, u) * live
                    v = jnp.where(interior, va, v) * live
                    w = jnp.where(interior, wa, w) * live
                t_next = t + dt.astype(idx_dtype)
                if _flags.verbose():
                    # printed AFTER t += dt, matching A6 main.c:58-62
                    master_print(comm, "TIME {} , TIMESTEP {}", t_next, dt)
                capt = (next_cap(res, it),) if cap is not None else ()
                if metrics:
                    # mesh-global maxima (replicated) — telemetry scalars
                    um = reduction(jnp.max(jnp.abs(u)), comm, "max")
                    vm = reduction(jnp.max(jnp.abs(v)), comm, "max")
                    wm = reduction(jnp.max(jnp.abs(w)), comm, "max")
                    return (u, v, w, p, t_next, nt + 1, res, it, dt,
                            um, vm, wm) + capt
                return (u, v, w, p, t_next, nt + 1) + capt

        def step_fused(u, v, w, p, t, nt, cap=None, strips=None):
            """The fused-phase twin of step() (see models/ns2d_dist.py):
            one deep exchange feeds the PRE kernel, the solve is unchanged,
            the POST kernel projects on the exchanged extended blocks.
            `strips` is the depth-scheduled variant (tpu_exchange_depth,
            see models/ns2d_dist.step_fused): the slow-tier axis pastes
            the K-block's captured strips instead of exchanging."""
            from ..parallel.comm import get_offsets

            with jax.named_scope("phases"):
                pre_k, post_k = fused_k
                H = FUSE_DEEP_HALO
                u, v, w, p = _fi.apply_field_faults(field_faults, nt, u=u, v=v,
                                                    w=w, p=p)
                if strips is None:
                    ud = halo_exchange(embed_deep(u, H), comm, depth=H)
                    vd = halo_exchange(embed_deep(v, H), comm, depth=H)
                    wd = halo_exchange(embed_deep(w, H), comm, depth=H)
                else:
                    from ..parallel.comm import paste_axis_strips

                    (lo_u, hi_u), (lo_v, hi_v), (lo_w, hi_w) = strips
                    ud = paste_axis_strips(
                        embed_deep(u, H), comm, dax, H, lo_u, hi_u)
                    vd = paste_axis_strips(
                        embed_deep(v, H), comm, dax, H, lo_v, hi_v)
                    wd = paste_axis_strips(
                        embed_deep(w, H), comm, dax, H, lo_w, hi_w)
                # ghost-inclusive CFL max over the deep blocks: same global
                # value set as the exchanged extended blocks
                dt = (compute_dt(ud, vd, wd) if adaptive
                      else jnp.asarray(param.dt, dtype))
                dt = clamped_dt(dt, dt_scale)
                offs = jnp.stack([
                    get_offsets("k", kl), get_offsets("j", jl),
                    get_offsets("i", il),
                ]).astype(jnp.int32)
                dt11 = jnp.full((1, 1), dt, dtype)
                pre_extra = post_extra = ()
                if gmasks is not None:
                    flg_deep, flg_ext = fused_flag_blocks()
                    pre_extra = (flg_deep,)
                    post_extra = (flg_ext,)
                upd, vpd, wpd, fpd, gpd, hpd, rpd = pre_k(
                    offs, dt11, pad_deep(ud), pad_deep(vd), pad_deep(wd),
                    *pre_extra,
                )
                u = strip_deep(unpad_deep(upd), H)
                v = strip_deep(unpad_deep(vpd), H)
                w = strip_deep(unpad_deep(wpd), H)
                f = strip_deep(unpad_deep(fpd), H)
                g_ = strip_deep(unpad_deep(gpd), H)
                h = strip_deep(unpad_deep(hpd), H)
                rhs = strip_deep(unpad_deep(rpd), H)
            p, _res, _it = (solve(p, rhs, cap) if cap is not None
                            else solve(p, rhs))
            with jax.named_scope("phases"):
                up, vp, wp, um_l, vm_l, wm_l = post_k(
                    offs, dt11, pad_ext(u), pad_ext(v), pad_ext(w),
                    pad_ext(f), pad_ext(g_), pad_ext(h), pad_ext(p),
                    *post_extra,
                )
                u = unpad_ext(up)
                v = unpad_ext(vp)
                w = unpad_ext(wp)
                t_next = t + dt.astype(idx_dtype)
                if _flags.verbose():
                    master_print(comm, "TIME {} , TIMESTEP {}", t_next, dt)
                capt = (next_cap(_res, _it),) if cap is not None else ()
                if metrics:
                    # the POST kernel's maxima are per-shard: Allreduce MAX
                    # makes them the global telemetry scalars
                    um = reduction(um_l, comm, "max")
                    vm = reduction(vm_l, comm, "max")
                    wm = reduction(wm_l, comm, "max")
                    return (u, v, w, p, t_next, nt + 1, _res, _it, dt,
                            um, vm, wm) + capt
                return (u, v, w, p, t_next, nt + 1) + capt

        if overlap:
            # -- overlapped fused step (parallel/overlap.py; see
            # models/ns2d_dist.py for the full invariants): the deep
            # exchange for step N+1 is posted after step N's POST and
            # carried double-buffered; PRE runs as interior (stale
            # blocks) + boundary (buffered exchanged blocks) halves
            # merged by the interior mask; dt from the carried maxima.
            from ..ops import ns3d_fused as nf3
            from ..ops.ns3d_fused import OVERLAP_RIM
            from ..parallel import overlap as _ovl
            from ..parallel.comm import get_offsets, persistent_exchange

            H3 = FUSE_DEEP_HALO
            deep_sched = persistent_exchange(comm, H3, dtype)
            # axis-aware rim + grid restriction over the leading k axis
            # (see models/ns2d_dist.py — same plan, k-plane bands)
            part3 = tuple(d > 1 for d in comm.dims)
            int_mask = _ovl.interior_mask((kl, jl, il), OVERLAP_RIM,
                                          partitioned=part3)
            bk_, _hh3, pw_, nbk_ = nf3.fused_deep_layout_3d(
                kl, jl, il, dtype, H3 - 1,
                masked=self.masks is not None)
            plan3 = _ovl.region_plan((kl, jl, il), OVERLAP_RIM, H3 - 1,
                                     bk_, nbk_, pw_, part3)
            restrict3 = _dispatch.resolve_overlap_restrict(
                param, "overlap_grid_ns3d_dist", plan3)
            self._overlap_plan = plan3 if restrict3 else None
            pre_int = pre_bnd = None
            if restrict3:
                fl_arg = True if self.masks is not None else None
                pre_int = nf3.make_fused_pre_3d(
                    param, g.kmax, g.jmax, g.imax, dx, dy, dz, dtype,
                    kl=kl, jl=jl, il=il, ext_pad=H3 - 1, fluid=fl_arg,
                    grid_bands=plan3["int_bands"])[0]
                pre_bnd = nf3.make_fused_pre_3d(
                    param, g.kmax, g.jmax, g.imax, dx, dy, dz, dtype,
                    kl=kl, jl=jl, il=il, ext_pad=H3 - 1, fluid=fl_arg,
                    grid_bands=plan3["bnd_bands"])[0]

            def exchange_buffers(u, v, w):
                return (deep_sched(embed_deep(u, H3)),
                        deep_sched(embed_deep(v, H3)),
                        deep_sched(embed_deep(w, H3)))

            def buffer_maxima(ud, vd, wd):
                return (reduction(jnp.max(jnp.abs(ud)), comm, "max"),
                        reduction(jnp.max(jnp.abs(vd)), comm, "max"),
                        reduction(jnp.max(jnp.abs(wd)), comm, "max"))

            def step_overlap(u, v, w, p, t, nt, ud, vd, wd,
                             um, vm, wm, gen, cap=None):
                with jax.named_scope("phases"):
                    pre_k, post_k = fused_k
                    pre_i = pre_int if pre_int is not None else pre_k
                    pre_b = pre_bnd if pre_bnd is not None else pre_k
                    dt = (cfl_from_maxima(um, vm, wm) if adaptive
                          else jnp.asarray(param.dt, dtype))
                    dt = _ovl.generation_guard(dt, gen, nt)
                    dt = clamped_dt(dt, dt_scale)
                    offs = jnp.stack([
                        get_offsets("k", kl), get_offsets("j", jl),
                        get_offsets("i", il),
                    ]).astype(jnp.int32)
                    dt11 = jnp.full((1, 1), dt, dtype)
                    pre_extra = post_extra = ()
                    if gmasks is not None:
                        flg_deep, flg_ext = fused_flag_blocks()
                        pre_extra = (flg_deep,)
                        post_extra = (flg_ext,)
                    ints = pre_i(offs, dt11, pad_deep(embed_deep(u, H3)),
                                 pad_deep(embed_deep(v, H3)),
                                 pad_deep(embed_deep(w, H3)), *pre_extra)
                    bnds = pre_b(offs, dt11, pad_deep(ud), pad_deep(vd),
                                 pad_deep(wd), *pre_extra)
                    u, v, w, f, g_, h, rhs = _ovl.merge_halves(
                        int_mask,
                        [strip_deep(unpad_deep(a), H3) for a in ints],
                        [strip_deep(unpad_deep(b), H3) for b in bnds])
                p, _res, _it = (solve(p, rhs, cap) if cap is not None
                                else solve(p, rhs))
                with jax.named_scope("phases"):
                    up, vp, wp, um_l, vm_l, wm_l = post_k(
                        offs, dt11, pad_ext(u), pad_ext(v), pad_ext(w),
                        pad_ext(f), pad_ext(g_), pad_ext(h), pad_ext(p),
                        *post_extra,
                    )
                    u = unpad_ext(up)
                    v = unpad_ext(vp)
                    w = unpad_ext(wp)
                    um = reduction(um_l, comm, "max")
                    vm = reduction(vm_l, comm, "max")
                    wm = reduction(wm_l, comm, "max")
                    # post the next step's exchange into the double buffer
                    ud, vd, wd = exchange_buffers(u, v, w)
                    t_next = t + dt.astype(idx_dtype)
                    if _flags.verbose():
                        master_print(comm, "TIME {} , TIMESTEP {}", t_next, dt)
                    capt = (next_cap(_res, _it),) if cap is not None else ()
                    return (u, v, w, p, t_next, nt + 1, ud, vd, wd,
                            um, vm, wm, nt + 1, _res, _it, dt) + capt

        step_impl = step if fused_k is None else step_fused
        te = param.te
        chunk = self.CHUNK
        # K-step fused chunks + per-tier exchange depth (ISSUE 17; see
        # models/ns2d_dist.py for the full invariants): K=1 keeps the
        # historical while-body verbatim, K>=2 advances by one scan of
        # K time-gated steps whose body traces once
        kfuse = _dispatch.resolve_chunk_fuse(
            param, "ns3d_dist_chunk_fuse", chunk,
            why_not=("overlapped chunk carries its own cross-step "
                     "exchange pipeline") if overlap else None)
        depth_why = None
        if fused_k is None:
            depth_why = "needs the fused deep-halo step (tpu_fuse_phases)"
        elif self.ragged:
            depth_why = "ragged decomposition"
        elif field_faults:
            depth_why = "PAMPI_FAULTS field faults armed"
        part_names = [n for n in comm.axis_names if comm.axis_size(n) > 1]
        part_ext = [{"k": kl, "j": jl, "i": il}[n] for n in part_names]
        depths = _dispatch.resolve_exchange_depth(
            param, "ns3d_dist_exchange_depth", kfuse, dict(comm.tiers),
            part_names, part_ext,
            FUSE_DEEP_HALO if fused_k is not None else 1,
            why_not=depth_why)
        dax, ddepth = next(iter(depths.items())) if depths else (None, 0)
        self._exchange_depths = depths

        def fuse_block_scan(c, kblock):
            # see models/ns2d_dist.fuse_block_scan
            if dax is None:
                c, _ = lax.scan(kblock(None), c, None, length=kfuse)
                return c
            from ..parallel.comm import capture_axis_strips

            def dblock(c, _):
                s = tuple(
                    capture_axis_strips(x, comm, dax, ddepth,
                                        FUSE_DEEP_HALO)
                    for x in (c[0], c[1], c[2]))
                c, _ = lax.scan(kblock(s), c, None, length=ddepth)
                return c, None

            c, _ = lax.scan(dblock, c, None, length=kfuse // ddepth)
            return c

        def chunk_kernel(u, v, w, p, t, nt):
            def cond(c):
                return jnp.logical_and(c[4] <= te, c[6] < chunk)

            if kfuse > 1:
                def kblock(strips):
                    skw = {} if strips is None else {"strips": strips}

                    def blk(c, _):
                        def live(c):
                            if use_cap:
                                u, v, w, p, t, nt, cap = c
                                return step_impl(u, v, w, p, t, nt, cap,
                                                 **skw)
                            u, v, w, p, t, nt = c
                            return step_impl(u, v, w, p, t, nt, **skw)

                        return lax.cond(c[4] <= te, live,
                                        lambda c: c, c), None

                    return blk

                def body(c):
                    sc = fuse_block_scan(c[:6] + c[7:], kblock)
                    return sc[:6] + (c[6] + kfuse,) + sc[6:]
            else:
                def body(c):
                    if use_cap:
                        u, v, w, p, t, nt, k, cap = c
                        u, v, w, p, t, nt, cap = step_impl(u, v, w, p, t, nt,
                                                           cap)
                        return u, v, w, p, t, nt, k + 1, cap
                    u, v, w, p, t, nt, k = c
                    u, v, w, p, t, nt = step_impl(u, v, w, p, t, nt)
                    return u, v, w, p, t, nt, k + 1

            init = (u, v, w, p, t, nt, jnp.asarray(0, jnp.int32))
            if use_cap:
                init = init + (itermax_i,)
            out = lax.while_loop(cond, body, init)
            return out[0], out[1], out[2], out[3], out[4], out[5]

        def chunk_kernel_metrics(u, v, w, p, t, nt, m):
            # the telemetry twin (see models/ns2d_dist.py)
            def cond(c):
                return jnp.logical_and(c[4] <= te, c[6] < chunk)

            if kfuse > 1:
                def kblock(strips):
                    skw = {} if strips is None else {"strips": strips}

                    def blk(c, _):
                        def live(c):
                            if use_cap:
                                (u, v, w, p, t, nt, res, it, dtv, um,
                                 vm, wm, bad, cap) = c
                                (u, v, w, p, t, nt, res, it, dtv, um,
                                 vm, wm, cap) = step_impl(
                                    u, v, w, p, t, nt, cap, **skw)
                            else:
                                (u, v, w, p, t, nt, res, it, dtv, um,
                                 vm, wm, bad) = c
                                (u, v, w, p, t, nt, res, it, dtv, um,
                                 vm, wm) = step_impl(u, v, w, p, t, nt,
                                                     **skw)
                            # POST-step nt: divergence records name the
                            # true step inside the K-block
                            (res, it, dtv, um, vm, wm,
                             bad) = _tm.metrics_step(
                                bad, nt, res, it, dtv, um, vm, wm)
                            out = (u, v, w, p, t, nt, res, it, dtv, um,
                                   vm, wm, bad)
                            return out + ((cap,) if use_cap else ())

                        return lax.cond(c[4] <= te, live,
                                        lambda c: c, c), None

                    return blk

                def body(c):
                    sc = fuse_block_scan(c[:6] + c[7:], kblock)
                    return sc[:6] + (c[6] + kfuse,) + sc[6:]
            else:
                def body(c):
                    if use_cap:
                        (u, v, w, p, t, nt, k, res, it, dtv, um, vm, wm,
                         bad, cap) = c
                        (u, v, w, p, t, nt, res, it, dtv, um, vm, wm,
                         cap) = step_impl(u, v, w, p, t, nt, cap)
                    else:
                        (u, v, w, p, t, nt, k, res, it, dtv, um, vm, wm,
                         bad) = c
                        (u, v, w, p, t, nt,
                         res, it, dtv, um, vm, wm) = step_impl(u, v, w, p,
                                                               t, nt)
                    res, it, dtv, um, vm, wm, bad = _tm.metrics_step(
                        bad, nt, res, it, dtv, um, vm, wm)
                    out = (u, v, w, p, t, nt, k + 1,
                           res, it, dtv, um, vm, wm, bad)
                    return out + ((cap,) if use_cap else ())

            init = (u, v, w, p, t, nt, jnp.asarray(0, jnp.int32),
                    m[_tm.M_RES], m[_tm.M_IT], m[_tm.M_DT],
                    m[_tm.M_UMAX], m[_tm.M_VMAX], m[_tm.M_WMAX],
                    m[_tm.M_BAD])
            if use_cap:
                init = init + (itermax_i,)
            out = lax.while_loop(cond, body, init)
            (u, v, w, p, t, nt, _k,
             res, it, dtv, um, vm, wm, bad) = out[:14]
            return u, v, w, p, t, nt, _tm.metrics_pack(
                res, it, dtv, um, vm, wm, bad)

        if overlap:
            # the overlapped chunk (see models/ns2d_dist.py): prologue
            # exchange fills the first double-buffer generation; the
            # internal carry grows (ud, vd, wd, um, vm, wm, gen) while
            # the chunk's EXTERNAL state arity stays unchanged
            def chunk_kernel_overlap(u, v, w, p, t, nt):
                ud, vd, wd = exchange_buffers(u, v, w)
                um, vm, wm = buffer_maxima(ud, vd, wd)

                def cond(c):
                    return jnp.logical_and(c[4] <= te, c[6] < chunk)

                def body(c):
                    if use_cap:
                        (u, v, w, p, t, nt, k, ud, vd, wd, um, vm, wm,
                         gen, cap) = c
                        (u, v, w, p, t, nt, ud, vd, wd, um, vm, wm, gen,
                         _res, _it, _dt, cap) = step_overlap(
                            u, v, w, p, t, nt, ud, vd, wd, um, vm, wm,
                            gen, cap)
                        return (u, v, w, p, t, nt, k + 1, ud, vd, wd,
                                um, vm, wm, gen, cap)
                    u, v, w, p, t, nt, k, ud, vd, wd, um, vm, wm, gen = c
                    (u, v, w, p, t, nt, ud, vd, wd, um, vm, wm, gen,
                     _res, _it, _dt) = step_overlap(
                        u, v, w, p, t, nt, ud, vd, wd, um, vm, wm, gen)
                    return (u, v, w, p, t, nt, k + 1, ud, vd, wd,
                            um, vm, wm, gen)

                init = (u, v, w, p, t, nt, jnp.asarray(0, jnp.int32),
                        ud, vd, wd, um, vm, wm, nt)
                if use_cap:
                    init = init + (itermax_i,)
                out = lax.while_loop(cond, body, init)
                return out[0], out[1], out[2], out[3], out[4], out[5]

            def chunk_kernel_overlap_metrics(u, v, w, p, t, nt, m):
                ud, vd, wd = exchange_buffers(u, v, w)
                um, vm, wm = buffer_maxima(ud, vd, wd)

                def cond(c):
                    return jnp.logical_and(c[4] <= te, c[6] < chunk)

                def body(c):
                    if use_cap:
                        (u, v, w, p, t, nt, k, ud, vd, wd, um, vm, wm,
                         gen, res, it, dtv, mum, mvm, mwm, bad, cap) = c
                        (u, v, w, p, t, nt, ud, vd, wd, um, vm, wm, gen,
                         res, it, dtv, cap) = step_overlap(
                            u, v, w, p, t, nt, ud, vd, wd, um, vm, wm,
                            gen, cap)
                    else:
                        (u, v, w, p, t, nt, k, ud, vd, wd, um, vm, wm,
                         gen, res, it, dtv, mum, mvm, mwm, bad) = c
                        (u, v, w, p, t, nt, ud, vd, wd, um, vm, wm, gen,
                         res, it, dtv) = step_overlap(
                            u, v, w, p, t, nt, ud, vd, wd, um, vm, wm,
                            gen)
                    res, it, dtv, mum, mvm, mwm, bad = _tm.metrics_step(
                        bad, nt, res, it, dtv, um, vm, wm)
                    out = (u, v, w, p, t, nt, k + 1, ud, vd, wd,
                           um, vm, wm, gen,
                           res, it, dtv, mum, mvm, mwm, bad)
                    return out + ((cap,) if use_cap else ())

                init = (u, v, w, p, t, nt, jnp.asarray(0, jnp.int32),
                        ud, vd, wd, um, vm, wm, nt,
                        m[_tm.M_RES], m[_tm.M_IT], m[_tm.M_DT],
                        m[_tm.M_UMAX], m[_tm.M_VMAX], m[_tm.M_WMAX],
                        m[_tm.M_BAD])
                if use_cap:
                    init = init + (itermax_i,)
                out = lax.while_loop(cond, body, init)
                (u, v, w, p, t, nt, _k, _ud, _vd, _wd, _um, _vm, _wm,
                 _gen, res, it, dtv, mum, mvm, mwm, bad) = out[:21]
                return u, v, w, p, t, nt, _tm.metrics_pack(
                    res, it, dtv, mum, mvm, mwm, bad)

        def init_kernel():
            shape = (kl + 2, jl + 2, il + 2)
            return (
                jnp.full(shape, param.u_init, dtype),
                jnp.full(shape, param.v_init, dtype),
                jnp.full(shape, param.w_init, dtype),
                jnp.full(shape, param.p_init, dtype),
            )

        def collect_kernel(u, v, w, p):
            """Cell-centered interiors (≙ commCollectResult, comm.c:246-427):
            staggered→center averaging needs fresh minus-side halos."""
            u = halo_exchange(u, comm)
            v = halo_exchange(v, comm)
            w = halo_exchange(w, comm)
            pg = p[1:-1, 1:-1, 1:-1]
            ug = (u[1:-1, 1:-1, 1:-1] + u[1:-1, 1:-1, :-2]) / 2.0
            vg = (v[1:-1, 1:-1, 1:-1] + v[1:-1, :-2, 1:-1]) / 2.0
            wg = (w[1:-1, 1:-1, 1:-1] + w[:-2, 1:-1, 1:-1]) / 2.0
            return ug, vg, wg, pg

        spec = P("k", "j", "i")
        self._init_sm = jax.jit(
            comm.shard_map(init_kernel, in_specs=(), out_specs=(spec,) * 4)
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
                in_specs=(spec,) * 4 + (P(), P()) + mextra,
                out_specs=(spec,) * 4 + (P(), P()) + mextra,
                check_vma=not pallas_o,
            )
        )
        self._collect_sm = jax.jit(
            comm.shard_map(collect_kernel, in_specs=(spec,) * 4, out_specs=(spec,) * 4)
        )
        _tm.emit("build", family="ns3d_dist",
                 grid=[g.kmax, g.jmax, g.imax], mesh=list(comm.dims),
                 trace_wall_s=round(time.perf_counter() - self._t0_build, 3),
                 phases=_dispatch.last("ns3d_dist_phases"))
        # static per-shard halo-exchange byte counts (step-level
        # exchanges of the dispatched path; solve internals excluded).
        # Built unconditionally: the telemetry `halo` record and the
        # commcheck trace census read the SAME dict, both priced by
        # comm.halo_exchange_bytes (see models/ns2d_dist._halo_record).
        isz = jnp.dtype(dtype).itemsize
        rec = {
            "family": "ns3d_dist", "mesh": list(comm.dims),
            "shard": [kl, jl, il], "dtype": str(jnp.dtype(dtype)),
            "path": "fused" if fused_k is not None else "jnp",
            "exchange_bytes_depth1":
                halo_exchange_bytes((kl, jl, il), 1, isz),
        }
        if fused_k is not None:
            from ..ops.ns3d_fused import fused_deep_layout_3d

            fbk, _fh3, fpw, fnb3 = fused_deep_layout_3d(
                kl, jl, il, dtype, FUSE_DEEP_HALO - 1,
                masked=gmasks is not None)
            full_cells = fnb3 * fbk * fpw
            rec.update(
                deep_halo=FUSE_DEEP_HALO,
                deep_exchange_bytes=halo_exchange_bytes(
                    (kl, jl, il), FUSE_DEEP_HALO, isz),
                exchanges_per_step={"deep": 3},
                pre_grid_cells=full_cells,
            )
            if self._exchange_depths:
                # per-tier depth map (ISSUE 17; see models/ns2d_dist.py):
                # the mapped dcn axis captures once per block, the
                # per-step deep strips then cover the unmapped axes only
                rec.update(
                    exchange_depths=dict(self._exchange_depths),
                    depth_block=max(self._exchange_depths.values()),
                    exchanges_per_block={"deep": 3},
                    axes=list(comm.axis_names),
                )
            if overlap:
                # same per-step schedule, posted into the double buffer;
                # the chunk prologue fills the first generation (see
                # models/ns2d_dist.py)
                rec.update(path="fused_overlap",
                           overlap="double_buffered",
                           exchanges_per_chunk={"deep": 3},
                           pre_grid_cells=(
                               self._overlap_plan["cells"]
                               if self._overlap_plan is not None
                               else 2 * full_cells),
                           pre_grid_cells_full=2 * full_cells)
        else:
            rec.update(exchanges_per_step={
                "depth1": 6 + (3 if gmasks is not None else 0),
                "shift": 3,
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
        """The static halo-exchange accounting of the dispatched path —
        see models/ns2d_dist._halo_record (the commcheck cross-check
        hook)."""
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
        """(u, v, w, p, t, nt[, metrics]) matching the built chunk's arity
        (the NS-2D convention — see models/ns2d.initial_state)."""
        time_dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
        scalars = (jnp.asarray(self.t, time_dtype),
                   jnp.asarray(self.nt, jnp.int32))
        if self._metrics:
            scalars = scalars + (_tm.metrics_init(),)
        return (self.u, self.v, self.w, self.p) + tuple(
            self.comm.replicate(x) for x in scalars)

    def run(self, progress: bool = True, on_sync=None) -> None:
        """The shared drive loop (models/_driver.drive_chunks) — see
        models/ns2d_dist.run for the migration contract."""
        from ._driver import coord_ckpt_cadence, drive_chunks, make_recovery

        bar = Progress(self.param.te, enabled=progress and not _flags.verbose())
        state = self.initial_state()
        rec = (_tm.ChunkRecorder("ns3d_dist", self.nt)
               if self._metrics else None)
        recover = make_recovery(self, "ns3d_dist", time_index=4,
                                recorder=rec)

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
        # multi-process transient retry rides the agreement protocol —
        # see models/ns2d_dist.run for the lifted single-controller ban
        from ..parallel.coordinator import make_coordinator

        coord = make_coordinator(self.param, "ns3d_dist")
        budget = 1 if (coord is not None or jax.process_count() == 1) else 0
        ckpt_every, on_ckpt = coord_ckpt_cadence(self, coord, publish)
        nt0 = self.nt
        with _xprof.capture("ns3d_dist", steps=lambda: self.nt - nt0):
            state = drive_chunks(
                state, self._chunk_sm, self.param.te, 4, bar,
                retry=lambda: None, on_state=on_state,
                replenish_after=self.param.tpu_retry_replenish,
                recover=recover, transient_budget=budget,
                coordinator=coord, ckpt_every=ckpt_every,
                on_ckpt=on_ckpt, family="ns3d_dist",
                ledger=getattr(self, "_fault_ledger", None))
            publish(state)
        self._emit_exchange_span()

    def _emit_exchange_span(self) -> None:
        """The `exchange` span — see models/ns2d_dist._emit_exchange_span
        (the serial critical-path probe of the declared halo schedule)."""
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

    def collect(self):
        """Gather cell-centered global fields to the host. The collect
        kernel outputs interior-only blocks, so the shard_map output IS the
        assembled (kmax, jmax, imax) global array — no assembly code (the
        80-line subarray dance of assembleResult, comm.c:104-156, vanishes)."""
        ug, vg, wg, pg = self._collect_sm(self.u, self.v, self.w, self.p)
        fetch = self.comm.collect  # multihost-safe host gather
        out = (fetch(ug), fetch(vg), fetch(wg), fetch(pg))
        g = self.grid
        # ragged decompositions carry trailing dead cells — strip them
        return tuple(a[: g.kmax, : g.jmax, : g.imax] for a in out)

    # -- elastic-checkpoint contract (utils/checkpoint.save_elastic) ---
    def global_shape(self) -> tuple:
        g = self.grid
        return (g.kmax + 2, g.jmax + 2, g.imax + 2)

    def global_fields(self) -> dict:
        """Mesh-independent reference-layout globals — see
        models/ns2d_dist.global_fields (same helper, 3-D mesh)."""
        from ..utils.checkpoint import assemble_global

        g = self.grid
        return {
            f: assemble_global(
                self.comm.collect(getattr(self, f)), self.comm.dims,
                (self.kl, self.jl, self.il), (g.kmax, g.jmax, g.imax))
            for f in ("u", "v", "w", "p")
        }

    def set_global_fields(self, fields: dict) -> None:
        from ..utils.checkpoint import scatter_blocks

        for f, arr in fields.items():
            cur = getattr(self, f)
            stacked = scatter_blocks(
                np.asarray(arr), self.comm.dims,
                (self.kl, self.jl, self.il))
            new = jnp.asarray(stacked, cur.dtype)
            sh = getattr(cur, "sharding", None)
            if sh is not None:
                new = jax.device_put(new, sh)
            setattr(self, f, new)

    def write_result(self, path=None, fmt: str = "ascii") -> None:
        # collect() is collective; only rank 0 writes the serial VTK file
        fields = self.collect()
        if self.comm.is_master:
            write_vtk_result(self.param, self.grid, fields, path, fmt)

    def write_result_sharded(self, path=None) -> None:
        """MPI-IO-pattern parallel write (binary VTK): the collect kernel's
        output is a mesh-sharded global array, and every addressable shard's
        slab goes straight to its byte offsets in the shared file — no global
        gather to the host (≙ the reference's scaffolded MPI_File_set_view
        path, vtkWriter.c:118-143, completed)."""
        from ..utils.vtkio import ShardedVtkWriter, shards_of

        if self.ragged:
            # per-shard slabs would carry dead cells at wrong file offsets;
            # the gathered serial write strips them instead
            self.write_result(path=path, fmt="binary")
            return
        ug, vg, wg, pg = self._collect_sm(self.u, self.v, self.w, self.p)
        problem = self.param.name.replace("3d", "")  # same naming as serial
        writer = ShardedVtkWriter(problem, self.grid, path=path)
        writer.scalar("pressure", shards_of(pg))
        us, vs, ws = shards_of(ug), shards_of(vg), shards_of(wg)
        vec = []
        for (du, o1), (dv, o2), (dw, o3) in zip(us, vs, ws):
            assert o1 == o2 == o3, "component shard layouts diverged"
            vec.append((du, dv, dw, o1))
        writer.vector("velocity", vec)
        writer.close()
