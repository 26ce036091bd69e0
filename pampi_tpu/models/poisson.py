"""2-D Poisson solver: red-black SOR with a residual-convergence loop in jit.

Capability parity with /root/reference/assignment-4 (initSolver:83, solve:126,
solveRB:179, solveRBA:240, writeResult:301) designed TPU-first:

- The whole convergence loop is ONE jitted `lax.while_loop` — carry (p, res, it),
  condition `res >= eps² && it < itermax` — so XLA keeps the field in device
  memory across iterations and fuses stencil + mask + reduction per half-sweep.
- All THREE reference solver variants are selectable modes:
  `tpu_solver sor` (default) → `solveRB`, the performance path (pallas on
  TPU); `tpu_solver sor_lex` → lexicographic `solve` as a scan/
  associative-scan oracle (`make_lex_step`; reproduces the committed golden
  p.dat byte-identically); `tpu_solver sor_rba` → `solveRBA` (separable-ω
  red-black, `make_rba_step`). All three converge in 2388 iterations on the
  reference's poisson.par, exactly matching the C binary (each variant
  compiled + run; see tests/test_poisson.py::test_solver_trio_iteration_parity).
- Equivalence policy for the performance path (SURVEY.md §7): match the
  *red-black* iteration trajectory exactly (same cells, same update order
  red→black, same residual accumulation & norm), and validate the converged
  field against the committed golden `p.dat` to discretization-level
  tolerance after removing the Neumann nullspace.

Init parity (initSolver:105-123): p = sin(4π·i·dx) + sin(4π·j·dy) on the FULL
array incl. ghosts; rhs = sin(2π·i·dx) for problem 2, else 0.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.sor import checkerboard_mask, lex_sweep, neumann_bc, sor_pass
from ..utils import flags as _flags
from ..utils.datio import write_matrix
from ..utils.params import Parameter
from ..utils.precision import resolve_dtype


def init_fields(param: Parameter, problem: int = 2, dtype=jnp.float64):
    """Initial p and rhs per assignment-4/src/solver.c:105-123."""
    imax, jmax = param.imax, param.jmax
    dx = param.xlength / imax
    dy = param.ylength / jmax
    i = np.arange(imax + 2)[None, :]
    j = np.arange(jmax + 2)[:, None]
    p = np.sin(2.0 * math.pi * i * dx * 2.0) + np.sin(2.0 * math.pi * j * dy * 2.0)
    if problem == 2:
        rhs = np.broadcast_to(np.sin(2.0 * math.pi * i * dx), p.shape).copy()
    else:
        rhs = np.zeros_like(p)
    return jnp.asarray(p, dtype=dtype), jnp.asarray(rhs, dtype=dtype)


def _pallas_why_not(backend: str, dtype=jnp.float32, probe=None):
    """Backend-decision contract shared by every pallas-dispatched solver:
    None when the Pallas kernel runs, else why the jnp path does. Explicit
    "pallas" forces, "auto" requires a real TPU, a Mosaic-lowerable dtype,
    and a passing one-time probe (which raises on a TPU where the kernel
    family fails). `probe` defaults to the 2-D kernel's smoke test; the
    3-D solver passes its own (models/ns3d._use_pallas_3d)."""
    if backend == "pallas":
        return None
    if backend == "jnp":
        return "retry fallback backend"
    if backend != "auto":
        return f"backend {backend!r}"
    if jax.default_backend() != "tpu":
        return "no TPU"
    if jnp.dtype(dtype).itemsize > 4:
        return "dtype not Mosaic-lowerable"  # XLA emulates f64, pallas can't
    if probe is None:
        from ..ops import sor_pallas as sp

        ok = sp.pltpu is not None and sp.probe_pallas()
    else:
        ok = probe()
    return None if ok else "probe failed"


def _use_pallas(backend: str, dtype=jnp.float32, probe=None) -> bool:
    return _pallas_why_not(backend, dtype, probe) is None


def _try_quarters(imax, jmax, dx, dy, omega, dtype, n_inner, layout):
    """The quarters-layout resolution of make_rb_loop, factored out so the
    p-layout fold (models/ns2d) asks the solver's OWN decision instead of
    re-deriving the policy by hand: the built (rb_iter, brq, h) when the
    pallas solve smooths on the stacked quarters layout, None when
    checkerboard is the solve home (layout forced to checkerboard, odd
    dims under auto, or quarters construction VMEM-infeasible). A forced
    layout="quarters" propagates construction errors."""
    if layout not in ("auto", "quarters"):
        return None
    even = imax % 2 == 0 and jmax % 2 == 0
    if layout == "quarters" and not even:
        raise ValueError("quarters layout needs even imax and jmax")
    if not even:
        return None
    from ..ops import sor_pallas as sp

    # construction raises on pre-checked conditions (odd dims, f64)
    # and on VMEM infeasibility (quarters_feasible): forced layout
    # propagates the error, auto falls back to checkerboard; runtime
    # kernel failures surface at first dispatch and are handled by
    # the callers' jnp fallback
    try:
        return sp.make_rb_iter_tblock_quarters(
            imax, jmax, dx, dy, omega, dtype, n_inner=n_inner
        )
    except ValueError:
        if layout == "quarters":
            raise
        return None


def make_rb_loop(imax, jmax, dx, dy, omega, dtype, backend: str = "auto",
                 n_inner: int = 1, layout: str = "auto"):
    """Public dispatcher for loop-carried use: returns
    (step, prep, post, eff_inner) where prep/post convert the loop-carried
    array at the boundary (padded layout under pallas, identity under jnp)
    and eff_inner is the number of red-black iterations one `step` call
    ACTUALLY performs. The single decision point for the backend choice —
    bench.py and the solvers both go through here.

    n_inner > 1 selects the temporal-blocked pallas kernel: one `step` call
    performs n_inner red-black iterations (+BCs) in a single HBM sweep and
    reports the residual of the last one. The jnp path always steps one
    iteration at a time — eff_inner tells the caller which happened, so
    iteration accounting stays honest on both paths.

    layout (`tpu_sor_layout` .par key): "auto" dispatches the QUARTER
    decomposition kernel (ops/sor_quarters.py, 2.25× the checkerboard at
    4096² f32 — 107G vs 47.5G updates/s on v5e) when eligible (pallas
    active, even imax/jmax); "checkerboard" keeps the masked kernel (whose
    per-cell trajectory is numerically identical to the jnp path — quarters
    is ulp-equivalent, compiler fma/fusion differences only);
    "quarters" forces the quarter kernel (error if ineligible)."""
    if layout not in ("auto", "checkerboard", "quarters"):
        raise ValueError(
            f"2-D SOR layout must be auto|checkerboard|quarters, got "
            f"{layout!r} (octants is the 3-D layout)"
        )
    from ..utils.dispatch import record

    why = _pallas_why_not(backend, dtype)
    if why is None:
        from ..ops import sor_pallas as sp

        q = _try_quarters(imax, jmax, dx, dy, omega, dtype, n_inner, layout)
        if q is not None:
            rb_iter, brq, h = q
            norm = float(imax * jmax)

            def step(p_stacked, rhs_stacked):
                p_stacked, rsq = rb_iter(p_stacked, rhs_stacked)
                # bf16 storage accumulates the residual in f32 — keep
                # it there: the convergence scalar must not be
                # re-quantized to 8 mantissa bits on its way to the
                # res >= eps² check (the loop carries res at >= f32)
                return p_stacked, rsq / norm

            def prep(x):
                return sp.pad_quarters(x, brq, h)

            def post(xq):
                return sp.unpad_quarters(xq, jmax, imax, h)

            record("sor2d", f"pallas_quarters (n_inner={n_inner})")
            return step, prep, post, n_inner
        kernel = "tblock" if n_inner > 1 else "fused"
        try:
            step, prep, post = make_rb_step_padded(
                imax, jmax, dx, dy, omega, dtype, kernel=kernel,
                n_inner=n_inner,
            )
            record("sor2d", f"pallas_tblock (n_inner={n_inner})")
            return step, prep, post, n_inner
        except ValueError:
            if backend == "pallas":
                raise
            # VMEM-infeasible on this grid (tblock_feasible): the safe
            # fallback is jnp — the checkerboard kernel would crash Mosaic
            # at first dispatch on the same grids that trip quarters
            why = "tblock VMEM-infeasible"
    record("sor2d", f"jnp ({why})")
    step = make_rb_step(imax, jmax, dx, dy, omega, dtype, backend="jnp")
    ident = lambda x: x  # noqa: E731
    return step, ident, ident, 1


def make_rb_step_padded(imax, jmax, dx, dy, omega, dtype, interpret=None,
                        kernel: str = "fused", n_inner: int = 4):
    """Pallas-backed red-black iteration on the PADDED layout
    (ops/sor_pallas.py): returns (step, pad, unpad) where step is
    (p_pad, rhs_pad) -> (p_pad', normalized res) incl. the Neumann ghost
    copy. The caller carries the padded array through its loop and converts
    at the boundary only.

    kernel: "tblock" (the production kernel: n_inner iterations per HBM
    sweep, double-buffered DMA, BCs fused inside; "fused" is an alias for
    n_inner=1) or "blocked" (two phases, one in-place sweep each — the
    simple aliased-I/O reference kernel)."""
    from ..ops import sor_pallas as sp

    norm = float(imax * jmax)
    if kernel == "fused":
        kernel, n_inner = "tblock", 1
    if kernel == "tblock":
        rb_iter, block_rows, halo = sp.make_rb_iter_tblock(
            imax, jmax, dx, dy, omega, dtype, n_inner=n_inner,
            interpret=interpret,
        )
        if rb_iter is None:
            raise ValueError("pallas backend unavailable")

        def step(p_pad, rhs_pad):
            p_pad, rsq = rb_iter(p_pad, rhs_pad)
            return p_pad, rsq / norm

        def pad(x):
            return sp.pad_array(x, block_rows, halo)

        def unpad(xp):
            return sp.unpad_array(xp, jmax, imax, halo)

        return step, pad, unpad

    rb_iter, block_rows = sp.make_rb_iter_pallas(
        imax, jmax, dx, dy, omega, dtype, interpret=interpret
    )
    if rb_iter is None:
        raise ValueError("pallas backend unavailable")

    def step(p_pad, rhs_pad):
        p_pad, rsq = rb_iter(p_pad, rhs_pad)
        return sp.neumann_bc_padded(p_pad, jmax, imax), rsq / norm

    def pad(x):
        return sp.pad_array(x, block_rows)

    def unpad(xp):
        return sp.unpad_array(xp, jmax, imax)

    return step, pad, unpad


def make_rb_step(imax, jmax, dx, dy, omega, dtype, backend: str = "auto",
                 factor=None):
    """Build one red-black SOR iteration: red half-sweep, black half-sweep
    (seeing red's updates), Neumann ghost copy, normalized residual.

    backend: "jnp" (masked fused-XLA passes), "pallas" (ops/sor_pallas.py
    blocked in-place kernel, pad/unpad per call — for loop-carried use go
    through make_rb_step_padded), or "auto" (pallas on TPU).
    factor: override for the relaxation factor (solveRBA's separable-ω
    association, make_rba_step); default is solveRB's (ω·0.5·dx²dy²)/(dx²+dy²)."""
    norm = float(imax * jmax)
    if factor is None and _use_pallas(backend, dtype):
        try:
            pstep, pad, unpad = make_rb_step_padded(
                imax, jmax, dx, dy, omega, dtype
            )
        except ValueError:
            if backend == "pallas":
                raise
            pstep = None  # VMEM-infeasible grid: jnp fallback below
        if pstep is not None:
            def step(p, rhs):
                p_pad, res = pstep(pad(p), pad(rhs))
                return unpad(p_pad), res

            return step

    dx2, dy2 = dx * dx, dy * dy
    idx2, idy2 = 1.0 / dx2, 1.0 / dy2
    if factor is None:
        factor = omega * 0.5 * (dx2 * dy2) / (dx2 + dy2)
    red = checkerboard_mask(jmax, imax, 0, dtype)
    black = checkerboard_mask(jmax, imax, 1, dtype)

    def step(p, rhs):
        p, r0 = sor_pass(p, rhs, red, factor, idx2, idy2)
        p, r1 = sor_pass(p, rhs, black, factor, idx2, idy2)
        p = neumann_bc(p)
        return p, (r0 + r1) / norm

    return step


def make_lex_step(imax, jmax, dx, dy, omega, dtype):
    """One lexicographic Gauss-Seidel SOR iteration + Neumann ghost copy —
    the reference's `solve` (assignment-4/src/solver.c:126-176) as a
    scan/associative-scan program (ops/sor.lex_sweep). Oracle-grade: always
    the jnp path (f64-capable), iteration-count parity with the C binary."""
    norm = float(imax * jmax)
    dx2, dy2 = dx * dx, dy * dy
    idx2, idy2 = 1.0 / dx2, 1.0 / dy2
    factor = omega * 0.5 * (dx2 * dy2) / (dx2 + dy2)

    def step(p, rhs):
        p, rsq = lex_sweep(p, rhs, factor, idx2, idy2)
        return neumann_bc(p), rsq / norm

    return step


def make_rba_step(imax, jmax, dx, dy, omega, dtype):
    """Red-black SOR with ω applied separately — the reference's `solveRBA`
    (assignment-4/src/solver.c:240-296). Identical cell visitation to
    `solveRB`; the only difference is the factor's floating-point
    association: ω·(0.5·dx²dy²/(dx²+dy²)) instead of (ω·0.5·dx²dy²)/(dx²+dy²).
    Oracle-grade jnp path, sharing make_rb_step's sweep body."""
    dx2, dy2 = dx * dx, dy * dy
    factor = omega * (0.5 * (dx2 * dy2) / (dx2 + dy2))
    return make_rb_step(imax, jmax, dx, dy, omega, dtype, backend="jnp",
                        factor=factor)


def make_padded_solver_fn(imax, jmax, dx, dy, omega, eps, itermax, dtype,
                          n_inner: int = 1, block_rows: int | None = None,
                          interpret: bool | None = None, flat: bool = False):
    """The rb convergence loop operating ENTIRELY in the sor_pallas padded
    layout: (p_pad, rhs_pad) -> (p_pad', res, it), no layout conversion
    inside. This is the p-layout fold of the fused NS-2D step
    (models/ns2d._build_fused_chunk): when the fused phase kernels share
    the solve's (block_rows, halo) geometry, the per-step pad/unpad passes
    around the solve vanish — p and rhs stay padded across the whole chunk.
    Input halo/tail rows may be UNDEFINED (the fused PRE never stores
    them): the tblock kernel consumes p/rhs only at
    logical-coordinate-gated cells (jnp.where selects, not multiplies), so
    garbage there cannot reach any stored value or the residual.

    Built on the checkerboard tblock kernel (the quarters layout is a
    different stacked data layout the fused kernels cannot share); raises
    ValueError when that kernel is unavailable or VMEM-infeasible. Same
    n_inner/flat contracts as make_solver_fn. Returns
    (solve, block_rows, halo)."""
    from ..ops import sor_pallas as sp
    from ..utils.precision import check_eps_floor

    check_eps_floor(eps, imax * jmax, dtype,
                    f"sor_tblock {imax}x{jmax}")
    eff = max(1, n_inner)
    rb_iter, block_rows, halo = sp.make_rb_iter_tblock(
        imax, jmax, dx, dy, omega, dtype, n_inner=eff,
        block_rows=block_rows, interpret=interpret,
    )
    if rb_iter is None:
        raise ValueError("pallas backend unavailable")
    norm = float(imax * jmax)
    epssq = eps * eps
    res_dtype = jnp.promote_types(dtype, jnp.float32)

    def solve(p_pad, rhs_pad):
        def cond(carry):
            _, res, it = carry
            return jnp.logical_and(res >= epssq, it < itermax)

        def body(carry):
            p, _, it = carry
            p, rsq = rb_iter(p, rhs_pad)
            res = (rsq / norm).astype(res_dtype)
            if _flags.debug():
                jax.debug.print("{} Residuum: {}", it + (eff - 1), res)
            return p, res, it + eff

        init = (p_pad, jnp.asarray(1.0, res_dtype),
                jnp.asarray(0, jnp.int32))
        if flat:
            trips = -(-itermax // eff)
            return jax.lax.fori_loop(0, trips, lambda _t, c: body(c), init)
        return jax.lax.while_loop(cond, body, init)

    return solve, block_rows, halo


def make_solver_fn(imax, jmax, dx, dy, omega, eps, itermax, dtype,
                   backend="auto", n_inner: int = 1, method: str = "rb",
                   layout: str = "auto", flat: bool = False):
    """The full convergence loop as one jittable function (p0, rhs) -> (p, res, it).

    method: "rb" (the performance path, pallas on TPU), "lex" (the
    reference's lexicographic `solve` as an oracle mode), or "rba"
    (`solveRBA`, separable-ω red-black). lex/rba always run the jnp path.

    On the pallas backend the loop carries the PADDED array (one pad before,
    one unpad after — no per-iteration layout conversion). With n_inner > 1
    (pallas only) each loop step runs n_inner red-black iterations in one
    HBM sweep; convergence is then checked every n_inner iterations, so the
    solve may do up to n_inner-1 more iterations than a per-iteration check
    would (the extra iterations only lower the residual further). `it`
    reports the true iteration count on every path.

    `flat=True` (.par key tpu_flat_solve, round 5): run EXACTLY
    ceil(itermax/n) loop trips under `lax.fori_loop` with no res-gated
    cond. On configs whose solves always hit itermax (the north-star
    4096² cavity, the reference's own canal configs) the cond can never
    fire early, so the flat trajectory is BITWISE identical. On
    converging configs it overdrives to the cap (result still valid —
    extra sweeps only lower the residual; `res` is the final residual) —
    an extension of the n_inner check-granularity contract to the whole
    solve. Opt-in, default off. Perf note: measured NEUTRAL at 4096²
    (interleaved A/B, 19.01 vs 19.04 ms/step) — the loop trip overhead,
    not the residual gating, is the per-trip cost."""
    from ..utils.precision import check_eps_floor

    check_eps_floor(eps, imax * jmax, dtype, f"sor {imax}x{jmax}")
    epssq = eps * eps
    res_dtype = jnp.promote_types(dtype, jnp.float32)
    if method == "lex":
        step = make_lex_step(imax, jmax, dx, dy, omega, dtype)
        prep = post = lambda x: x  # noqa: E731
        eff = 1
    elif method == "rba":
        step = make_rba_step(imax, jmax, dx, dy, omega, dtype)
        prep = post = lambda x: x  # noqa: E731
        eff = 1
    else:
        step, prep, post, eff = make_rb_loop(
            imax, jmax, dx, dy, omega, dtype, backend, n_inner, layout
        )

    def solve(p0, rhs):
        rhs = prep(rhs)

        def cond(carry):
            _, res, it = carry
            return jnp.logical_and(res >= epssq, it < itermax)

        def body(carry):
            p, _, it = carry
            p, res = step(p, rhs)
            # carry the convergence scalar at f32 or wider regardless of the
            # storage dtype (a scalar costs nothing; bf16 would re-quantize
            # the kernels' deliberately-f32 residual accumulation)
            res = res.astype(res_dtype)
            if _flags.debug():
                # ≙ -DDEBUG "%d Residuum: %e" (solver.c:169-171); 0-based
                # index of the last completed iteration, like the reference.
                # solveRBA additionally echoes omega (solver.c:289-291).
                if method == "rba":
                    jax.debug.print(
                        "{} Residuum: {} Omega: {}", it + (eff - 1), res, omega
                    )
                else:
                    jax.debug.print("{} Residuum: {}", it + (eff - 1), res)
            return p, res, it + eff

        init = (prep(p0), jnp.asarray(1.0, res_dtype),
                jnp.asarray(0, jnp.int32))
        if flat:
            trips = -(-itermax // eff)
            p, res, it = jax.lax.fori_loop(
                0, trips, lambda _t, c: body(c), init
            )
        else:
            p, res, it = jax.lax.while_loop(cond, body, init)
        return post(p), res, it

    return solve


class PoissonSolver:
    """Driver-facing wrapper (parity: the Solver struct + init/solve/writeResult)."""

    def __init__(self, param: Parameter, problem: int = 2, dtype=None):
        from ..utils.dispatch import resolve_solver

        param = resolve_solver(param, obstacles=False)
        if dtype is None:
            dtype = resolve_dtype(param.tpu_dtype,
                                  record_key="poisson_dtype")
        self.param = param
        self.dtype = dtype
        self.imax, self.jmax = param.imax, param.jmax
        self.dx = param.xlength / param.imax
        self.dy = param.ylength / param.jmax
        self.p, self.rhs = init_fields(param, problem, dtype)
        self._backend = "auto"
        self._solve = jax.jit(self._make_solve(backend="auto"))

    def _make_solve(self, backend: str):
        if self.param.tpu_solver == "mg":
            from ..ops.multigrid import make_mg_solve_2d

            return make_mg_solve_2d(
                self.imax, self.jmax, self.dx, self.dy,
                self.param.eps, self.param.itermax, self.dtype,
                stall_rtol=self.param.tpu_mg_stall_rtol, backend=backend,
                fused=self.param.tpu_mg_fused,
            )
        if self.param.tpu_solver == "fft":
            from ..ops.dctpoisson import make_dct_solve_2d

            return make_dct_solve_2d(
                self.imax, self.jmax, self.dx, self.dy, self.dtype
            )
        # the assignment-4 solver trio (solver.c:126/179/240): sor → solveRB
        # (the performance path), sor_lex → solve, sor_rba → solveRBA
        method = {"sor_lex": "lex", "sor_rba": "rba"}.get(
            self.param.tpu_solver, "rb"
        )
        return make_solver_fn(
            self.imax,
            self.jmax,
            self.dx,
            self.dy,
            self.param.omg,
            self.param.eps,
            self.param.itermax,
            self.dtype,
            backend=backend,
            n_inner=self.param.tpu_sor_inner,
            method=method,
            layout=self.param.tpu_sor_layout,
            flat=bool(self.param.tpu_flat_solve),
        )

    def solve(self):
        import math
        import time

        from ..utils import telemetry as _tm

        t0 = time.perf_counter()
        try:
            p, res, it = self._solve(self.p, self.rhs)
            # dispatch is async: force completion inside the try so a pallas
            # runtime fault surfaces here, not at the caller's readback
            out = int(it), float(res)
        except Exception:  # lint: allow(broad-except) — pallas runtime faults have no stable class; non-pallas paths re-raise below
            if self._backend == "jnp" or self.param.tpu_solver in (
                "mg", "fft", "sor_lex", "sor_rba",
            ):
                raise  # no pallas in play — genuine error, don't re-run it
            # shape-specific pallas failure the dispatcher probe missed:
            # fall back to the always-available jnp path (same arithmetic),
            # on the flight record like the chunk driver's fallback
            _tm.emit("retry", fault="pallas", action="jnp_fallback",
                     what="poisson solve")
            self._backend = "jnp"
            self._solve = jax.jit(self._make_solve(backend="jnp"))
            p, res, it = self._solve(self.p, self.rhs)
            out = int(it), float(res)
        self.p = p
        # host-plane flight record: the (it, res) pair already crosses to
        # the host here, so the record costs nothing extra on-device
        _tm.emit("solve", family="poisson", iters=out[0], res=out[1],
                 wall_s=round(time.perf_counter() - t0, 4),
                 backend=self._backend)
        if not math.isfinite(out[1]):
            _tm.emit("divergence", family="poisson", res=out[1],
                     iters=out[0])
        return out

    def write_result(self, path: str = "p.dat") -> None:
        write_matrix(np.asarray(jax.device_get(self.p)), path)
