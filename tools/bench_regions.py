"""Per-region device-time counter harness — the TPU twin of the reference's
perl likwid-mpirun scripts (assignment-3a/perl scripts/bench-node.pl:17-27
drive likwid hardware-counter sweeps per marker region; here each solver
phase is jitted and timed SEPARATELY to completion on the device, yielding
the counters a TPU exposes to the host: calls, device seconds/call, and
lattice-site update throughput).

Regions per problem (the reference's marker-candidate phases):
  poisson   : sor_iter (one red-black iteration at the production
              tpu_sor_inner granularity), solve (full convergence loop)
  dcavity/… : computeTimestep, setBC, computeFG, computeRHS, sor_iter,
              adaptUV   (solver.c phase names, assignment-5/-6)
  dcavity3d : 3-D versions of the same

Usage:  [PAMPI_PROFILE_CSV=out.csv] python tools/bench_regions.py <file.par> [reps]
Each phase: 2 warmup calls, then best-of-<reps> (default 10) wall time
around dispatch + block_until_ready — device-inclusive by construction.
Prints the table; writes the CSV via utils/profiling.py when
PAMPI_PROFILE_CSV is set (PAMPI_PROFILE is forced on for this harness).
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("PAMPI_PROFILE", "1")

import jax
import jax.numpy as jnp

from pampi_tpu.utils import profiling as prof
from pampi_tpu.utils.params import Parameter, read_parameter
from pampi_tpu.utils.precision import resolve_dtype

# best-of over MANY reps of a chained-application dispatch: per-dispatch
# overhead cancels in the two-point difference (_loop_timer)
REPS = int(sys.argv[2]) if len(sys.argv) > 2 else 30


def _loop_timer(fn, k, *args):
    """Seconds for ONE dispatch of k chained fn applications + scalar fence.

    The phase runs inside a fori_loop, serialized with an
    optimization_barrier tying each iteration's input to the previous
    iteration's output scalar — XLA can neither hoist, fold, nor overlap
    the applications (arithmetic perturbation tricks get constant-folded).
    Amortizes the per-dispatch latency, which single dispatches cannot
    escape."""
    x0, rest = args[0], args[1:]

    def loop(x, *rest):
        def body(_, carry):
            x, acc = carry
            x, acc = jax.lax.optimization_barrier((x, acc))
            out = fn(x, *rest)
            leaf = jax.tree_util.tree_leaves(out)[0]
            mid = leaf.size // 2
            return (x, jnp.ravel(leaf)[mid].astype(jnp.float32))

        return jax.lax.fori_loop(0, k, body, (x, jnp.float32(0)))[1]

    jloop = jax.jit(loop)
    float(jloop(x0, *rest))  # compile + warm
    best = float("inf")
    for _ in range(REPS):
        t0 = time.perf_counter()
        float(jloop(x0, *rest))
        best = min(best, time.perf_counter() - t0)
    return best


def _time(fn, *args):
    """Device-inclusive seconds per fn application by TWO-POINT differencing:
    per = (t(k_b) - t(k_a)) / (k_b - k_a). The dispatch-latency floor (which
    jitters 25 us .. 100 ms between dispatches, so it cannot be subtracted
    from separately-measured runs) appears in both terms and cancels; best-of
    REPS on each term suppresses the residual jitter. k_b is sized so the
    extra iterations carry >= ~0.25 s of phase work, refined once when the
    first estimate shows the probe overestimated the per-iteration cost."""
    ka = 16
    ta = _loop_timer(fn, ka, *args)
    kb = ka + max(32, min(16384, int(0.25 / max(ta / ka, 1e-6))))
    tb = _loop_timer(fn, kb, *args)
    per = max((tb - ta) / (kb - ka), 1e-9)
    if per * (kb - ka) < 0.3:  # diff too small vs jitter: one refinement
        # bound kc by MEASURED wall time per iteration (tb/kb, which includes
        # the latency floor), not the clamped difference — a negative diff
        # would otherwise size a multi-hour dispatch
        wall_cap = int(2.0 / max(tb / kb, 1e-7))
        kc = ka + max(32, min(262144, int(0.5 / per), wall_cap))
        if kc > kb * 2:
            tc = _loop_timer(fn, kc, *args)
            per = max((tc - ta) / (kc - ka), 1e-9)
        else:
            # the refinement could not run (the wall cap already bounds the
            # chain): the estimate comes from a < 0.3 s two-point difference
            # the code itself classifies as jitter-dominated — say so
            # instead of recording it silently (round-2 advisor finding)
            print(
                f"# WARNING: low-confidence estimate "
                f"(jitter-dominated {per * (kb - ka):.3f}s difference, "
                f"refinement infeasible at kc={kc} <= 2*kb={2 * kb})",
                file=sys.stderr,
            )
    return per


def _record(name, seconds, sites):
    prof.add_device_time(name, seconds)
    rate = sites / seconds if seconds > 0 else 0.0
    print(f"{name:<16} {seconds * 1e3:10.3f} ms  {rate / 1e9:8.2f}e9 sites/s")


def bench_poisson(param: Parameter, dtype):
    from pampi_tpu.models.poisson import (
        init_fields, make_rb_loop, make_solver_fn,
    )

    imax, jmax = param.imax, param.jmax
    dx, dy = param.xlength / imax, param.ylength / jmax
    p, rhs = init_fields(param, problem=2, dtype=dtype)
    step, prep, post, eff = make_rb_loop(
        imax, jmax, dx, dy, param.omg, dtype, "auto", param.tpu_sor_inner
    )
    pp, rr = prep(p), prep(rhs)
    t = _time(lambda a, b: step(a, b)[0], pp, rr)
    _record("sor_iter", t, imax * jmax * eff)

    # capped iteration count: the counter harness measures per-region rates,
    # not convergence (bench.py owns the convergence headline)
    solve = make_solver_fn(imax, jmax, dx, dy, param.omg, param.eps,
                           min(param.itermax, 500), dtype,
                           n_inner=param.tpu_sor_inner)
    jsolve = jax.jit(solve)
    it = int(jsolve(p, rhs)[2])  # scalar readback = the fence
    t0 = time.perf_counter()
    it = int(jsolve(p, rhs)[2])
    t = time.perf_counter() - t0
    _record("solve", t, imax * jmax * it)


def bench_ns2d(param: Parameter, dtype):
    from pampi_tpu.models.poisson import make_rb_loop
    from pampi_tpu.ops import ns2d as ops

    imax, jmax = param.imax, param.jmax
    dx, dy = param.xlength / imax, param.ylength / jmax
    shape = (jmax + 2, imax + 2)
    sites = imax * jmax
    u = jnp.full(shape, param.u_init, dtype)
    v = jnp.full(shape, param.v_init, dtype)
    p = jnp.full(shape, param.p_init, dtype)
    dt_bound = 0.5 * param.re / (1.0 / (dx * dx) + 1.0 / (dy * dy))
    dt = jnp.asarray(param.tau * dt_bound, dtype)

    _record("computeTimestep",
            _time(lambda a, b: ops.compute_timestep(a, b, dt_bound, dx, dy,
                                                    param.tau), u, v), sites)
    _record("setBC",
            _time(lambda a, b: ops.set_boundary_conditions(
                a, b, param.bcLeft, param.bcRight, param.bcBottom,
                param.bcTop), u, v), sites)
    f, g = ops.compute_fg(u, v, dt, param.re, param.gx, param.gy,
                          param.gamma, dx, dy)
    _record("computeFG",
            _time(lambda a, b: ops.compute_fg(a, b, dt, param.re, param.gx,
                                              param.gy, param.gamma, dx, dy),
                  u, v), sites)
    rhs = ops.compute_rhs(f, g, dt, dx, dy)
    _record("computeRHS",
            _time(lambda a, b: ops.compute_rhs(a, b, dt, dx, dy), f, g),
            sites)
    # the layout the NS-2D pressure solve actually ships for this config:
    # make_rb_loop's standard dispatch (auto -> quarters when eligible,
    # checkerboard otherwise — models/ns2d.make_pressure_solve round 3)
    step, prep, post, eff = make_rb_loop(
        imax, jmax, dx, dy, param.omg, dtype, "auto", param.tpu_sor_inner,
        layout=param.tpu_sor_layout,
    )
    _record("sor_iter",
            _time(lambda a, b: step(a, b)[0], prep(p), prep(rhs)),
            sites * eff)
    _record("adaptUV",
            _time(lambda a, b: ops.adapt_uv(a, b, f, g, p, dt, dx, dy), u, v),
            sites)


def bench_ns3d(param: Parameter, dtype):
    from pampi_tpu.models import ns3d as m3
    from pampi_tpu.ops import ns3d as ops

    imax, jmax, kmax = param.imax, param.jmax, param.kmax
    dx = param.xlength / imax
    dy = param.ylength / jmax
    dz = param.zlength / kmax
    shape = (kmax + 2, jmax + 2, imax + 2)
    sites = imax * jmax * kmax
    u = jnp.full(shape, param.u_init, dtype)
    v = jnp.full(shape, param.v_init, dtype)
    w = jnp.full(shape, param.w_init, dtype)
    p = jnp.full(shape, param.p_init, dtype)
    inv = 1.0 / (dx * dx) + 1.0 / (dy * dy) + 1.0 / (dz * dz)
    dt_bound = 0.5 * param.re / inv
    dt = jnp.asarray(param.tau * dt_bound, dtype)
    bcs = {
        "top": param.bcTop, "bottom": param.bcBottom,
        "left": param.bcLeft, "right": param.bcRight,
        "front": param.bcFront, "back": param.bcBack,
    }

    _record("computeTimestep",
            _time(lambda a, b, c: ops.compute_timestep_3d(
                a, b, c, dt_bound, dx, dy, dz, param.tau), u, v, w), sites)
    _record("setBC",
            _time(lambda a, b, c: ops.set_boundary_conditions_3d(a, b, c,
                                                                 bcs),
                  u, v, w), sites)
    f, g, h = ops.compute_fgh(u, v, w, dt, param.re, param.gx, param.gy,
                              param.gz, param.gamma, dx, dy, dz)
    _record("computeFG",
            _time(lambda a, b, c: ops.compute_fgh(
                a, b, c, dt, param.re, param.gx, param.gy, param.gz,
                param.gamma, dx, dy, dz), u, v, w), sites)
    rhs = ops.compute_rhs(f, g, h, dt, dx, dy, dz)
    _record("computeRHS",
            _time(lambda a, b, c: ops.compute_rhs(a, b, c, dt, dx, dy, dz),
                  f, g, h), sites)
    # per-iteration cost amortized over a fixed-count solve (eps=0 runs to
    # itermax; one pad/unpad per solve, like production use)
    cap = 48
    solve = m3.make_pressure_solve_3d(
        imax, jmax, kmax, dx, dy, dz, param.omg, 0.0, cap, dtype,
        n_inner=param.tpu_sor_inner,
    )
    jsolve = jax.jit(solve)
    it = int(jsolve(p, rhs)[2])  # scalar readback = the fence
    best = float("inf")
    for _ in range(max(2, REPS // 2)):
        t0 = time.perf_counter()
        it = int(jsolve(p, rhs)[2])
        best = min(best, time.perf_counter() - t0)
    _record("sor_iter", best / max(1, it), sites)
    _record("adaptUV",
            _time(lambda a, b, c: ops.adapt_uvw(a, b, c, f, g, h, p, dt,
                                                dx, dy, dz), u, v, w), sites)


def main():
    param = read_parameter(sys.argv[1], Parameter())
    if param.tpu_dtype == "float64":
        jax.config.update("jax_enable_x64", True)
    dtype = resolve_dtype(param.tpu_dtype)
    print(f"# {param.name} backend={jax.default_backend()} "
          f"dtype={param.tpu_dtype} reps={REPS}")
    prof.init()
    if param.name.startswith("poisson"):
        bench_poisson(param, dtype)
    elif param.name in ("dcavity3d", "canal3d"):
        bench_ns3d(param, dtype)
    else:
        bench_ns2d(param, dtype)
    prof.finalize()


if __name__ == "__main__":
    main()
