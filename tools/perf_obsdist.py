"""Distributed obstacle kernel at PRODUCTION shard size (VERDICT r3 item 4).

Round 3 measured the per-shard flag-masked Pallas kernel
(ops/sor_obsdist.py) at 4.2G site-updates/s on a 2048x512 shard — 36x off
the single-device masked kernel — and attributed the gap to per-block fixed
cost without measuring alternatives. This tool measures, on the real chip
at the canal_obstacle2048 geometry (2048x512 f32, one shard of a 1x1 mesh —
the same per-shard workload a v5e-8 run gives each chip):

- the single-device masked tblock kernel (make_obstacle_solver_fn) at
  several depths — the per-shard ceiling,
- the distributed solve (make_dist_obstacle_solver auto->pallas) at several
  CA depths — what the mesh path actually delivers per shard,

using fixed-iteration solves (eps below reach, itermax = ITS) under the
chained-dispatch timing protocol: chained solve dispatches fenced by a
SCALAR readback, per-solve cost by two-point differencing so the
per-dispatch latency floor (measured up to ~100 ms here) cancels. Writes
results/obsdist2048.json.

Run on the real chip:  python tools/perf_obsdist.py
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np

import jax
import jax.numpy as jnp

from pampi_tpu.utils.params import read_parameter

ITS = 512
REPS = 5
PAR = os.path.join(REPO, "configs", "canal_obstacle2048.par")


def main() -> dict:
    from pampi_tpu.ops import obstacle as obst
    from pampi_tpu.parallel.comm import CartComm
    from pampi_tpu.utils import dispatch as _dispatch
    from pampi_tpu.utils import telemetry
    from pampi_tpu.utils import xlacache

    xlacache.enable()  # the big-halo kernels are long compiles
    telemetry.start_run(tool="perf_obsdist")

    param = read_parameter(PAR)
    imax, jmax = param.imax, param.jmax
    dx, dy = param.xlength / imax, param.ylength / jmax
    DT = jnp.float32
    fluid = obst.build_fluid(imax, jmax, dx, dy, param.obstacles)
    m = obst.make_masks(fluid, dx, dy, param.omg, DT)
    rng = np.random.default_rng(0)
    p0 = jnp.asarray(rng.standard_normal((jmax + 2, imax + 2)), DT)
    rhs = jnp.asarray(rng.standard_normal((jmax + 2, imax + 2)), DT)
    sites = jmax * imax

    KA, KB = 1, 9

    def bench(fn):
        # warm (compile) + two-point differencing over chained solves:
        # per-solve = (t(KB) - t(KA)) / (KB - KA); solves chain through the
        # p carry so they serialize on device, the scalar fence avoids
        # transferring the field, and the dispatch-latency floor cancels
        out = fn(p0, rhs)
        float(out[1])

        def timed(k):
            best = float("inf")
            for _ in range(REPS):
                t0 = time.perf_counter()
                p = p0
                for _ in range(k):
                    p, res, it = fn(p, rhs)
                float(res)
                best = min(best, time.perf_counter() - t0)
            return best

        ta, tb = timed(KA), timed(KB)
        return max((tb - ta) / (KB - KA), 1e-9)

    rec = {
        "artifact": "obsdist2048",
        "config": f"canal_obstacle geometry {jmax}x{imax} f32, fixed "
                  f"{ITS}-iteration solves, one chip (= one shard's "
                  "workload), best-of-%d" % REPS,
        "backend": jax.default_backend(),
        "single_device": {},
        "dist_one_shard": {},
    }
    for n in (8, 16):
        solve = jax.jit(obst.make_obstacle_solver_fn(
            imax, jmax, dx, dy, 1e-30, ITS, m, DT, n_inner=n))
        t = bench(solve)
        rec["single_device"][f"n{n}"] = {
            "s": round(t, 4),
            "gups": round(sites * ITS / t / 1e9, 1),
        }
        telemetry.emit_span(f"obsdist2048.single[n{n}]", t * 1e3,
                            gups=rec["single_device"][f"n{n}"]["gups"])
        print(f"single n{n}: {t*1e3:.1f} ms "
              f"{rec['single_device'][f'n{n}']['gups']}G", flush=True)

    P = jax.sharding.PartitionSpec
    for can in (8, 16):
        comm = CartComm(ndims=2, dims=(1, 1))
        solve_d, used = obst.make_dist_obstacle_solver(
            comm, imax, jmax, jmax, imax, dx, dy, 1e-30, ITS, m, DT,
            ca_n=can, sor_inner=can)
        tag = _dispatch.last("obstacle_dist")

        def kern(p, r, _s=solve_d):
            return _s(p, r)

        sm = jax.jit(comm.shard_map(
            kern, in_specs=(P(), P()), out_specs=(P(), P(), P()),
            check_vma=not used,
        ))
        try:
            t = bench(sm)
        except Exception as e:  # record, don't lose the finished rows
            msg = str(e).splitlines()[0][:200] if str(e) else type(e).__name__
            rec["dist_one_shard"][f"ca{can}"] = {
                "error": msg, "dispatch": tag,
            }
            print(f"dist ca{can} [{tag}]: FAILED {e}"[:160], flush=True)
            continue
        rec["dist_one_shard"][f"ca{can}"] = {
            "s": round(t, 4),
            "gups": round(sites * ITS / t / 1e9, 1),
            "dispatch": tag,
        }
        telemetry.emit_span(f"obsdist2048.dist[ca{can}]", t * 1e3,
                            gups=rec["dist_one_shard"][f"ca{can}"]["gups"],
                            dispatch=tag)
        print(f"dist ca{can} [{tag}]: {t*1e3:.1f} ms "
              f"{rec['dist_one_shard'][f'ca{can}']['gups']}G", flush=True)

    # step-level solve/non-solve decomposition of the FUSED dist obstacle
    # run (PR 2: obstacle shards now ride the phase megakernels with
    # call-time flag slices) — bench.py's decomposition protocol on the
    # mesh, via tools/_artifact.dist_step_decomposition
    from pampi_tpu.models.ns2d_dist import NS2DDistSolver
    from tools._artifact import dist_step_decomposition

    def make_solver(itermax):
        p_step = param.replace(
            te=1e9, tau=0.5, eps=1e-30, itermax=itermax or param.itermax,
            tpu_dtype="float32", tpu_sor_inner=16, tpu_ca_inner=16,
        )
        return NS2DDistSolver(p_step, CartComm(ndims=2, dims=(1, 1)),
                              dtype=DT)

    rec["obstacle_step_decomposition"] = dist_step_decomposition(
        make_solver, "ns2d_dist_phases", reps=REPS)
    return rec


if __name__ == "__main__":
    rec = main()
    out = os.path.join(REPO, "results", "obsdist2048.json")
    with open(out, "w") as f:
        json.dump(rec, f, indent=2)
    print("wrote", out)
