"""Ragged-vs-divisible throughput on the real chip (VERDICT r4 item 2's
"measured ragged-vs-divisible throughput row", + item 6's missing
dist-quarters artifact).

What non-divisible grids cost on this framework: ragged runs ride the
flag-masked checkerboard per-shard kernel (ops/sor_obsdist, all-fluid
flags, halo 2n+1) because the compressed quarters layout structurally
needs even divisible extents. This tool measures, same-session:

1. dist-quarters solve-loop steady state at 4096^2, one shard of a (1,1)
   mesh (the round-4 95.2G protocol: capped 9600-iteration solves,
   solve-vs-solve two-point differencing 4800 vs 9600 iters so dispatch
   latency and the per-solve pack/init envelope cancel) — the committed
   artifact for the round-4 retune number;
2. the masked kernel, DIVISIBLE geometry (4096^2 single shard, H=2n),
   standalone chained-kernel differencing — what a flags path costs at
   this size;
3. the masked kernel, RAGGED geometry (4095^2 ceil-divided over a virtual
   (2,2) mesh, shard 0, H=2n+1), same protocol — the ragged fast path's
   actual rate.

Run on the real chip:  python tools/perf_ragged.py
Writes results/ragged_throughput.json (merge-preserving).
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax
import jax.numpy as jnp
import numpy as np

REPS = 3
N_INNER = 8  # the obsdist production depth (ca8; 16 OOMs at wide shards)


def quarters_solve_steady() -> dict:
    """The 95G protocol: one-shard 4096^2 DistPoissonSolver, quarters."""
    from pampi_tpu.models.poisson_dist import DistPoissonSolver
    from pampi_tpu.parallel.comm import CartComm
    from pampi_tpu.utils import dispatch
    from pampi_tpu.utils.params import Parameter

    def run(itermax):
        param = Parameter(imax=4096, jmax=4096, itermax=itermax, eps=1e-30,
                          omg=1.9, tpu_dtype="float32",
                          tpu_sor_layout="quarters", tpu_ca_inner=16,
                          tpu_sor_inner=16)
        s = DistPoissonSolver(param, CartComm(ndims=2, dims=(1, 1)),
                              problem=2)
        # memory pitfall 2: first call compiles _solve_first, second
        # _solve_resume — warm BOTH before timing
        s.solve()
        s.solve()
        best = float("inf")
        for _ in range(REPS):
            t0 = time.perf_counter()
            it, res = s.solve()
            best = min(best, time.perf_counter() - t0)
        assert it >= itermax, (it, itermax)
        return best, dispatch.last("poisson_dist")

    ta, _ = run(4800)
    tb, tag = run(9600)
    ups = 4096 * 4096 * 4800 / max(tb - ta, 1e-9)
    return {"updates_per_sec": round(ups / 1e9, 2), "unit": "G", "dispatch": tag,
            "protocol": "solve-vs-solve differencing 4800 vs 9600 iters"}


def masked_kernel_rate(gj, gi, jl, il, ragged: bool) -> dict:
    """Standalone chained-kernel rate (shard 0 offsets; the kernel takes
    offs as an argument, so no shard_map is needed — the multiblock-test
    pattern)."""
    from pampi_tpu.ops import sor_pallas as sp
    from pampi_tpu.ops.sor_obsdist import make_rb_iters_obsdist
    from pampi_tpu.parallel.stencil2d import ca_halo

    dx, dy = 1.0 / gi, 1.0 / gj
    rb, br, h = make_rb_iters_obsdist(
        gj, gi, jl, il, N_INNER, dx, dy, 1.9, jnp.float32, ragged=ragged,
    )
    H = ca_halo(N_INNER, ragged)
    rng = np.random.default_rng(5)
    ext = (jl + 2 * H, il + 2 * H)
    pd = jnp.asarray(rng.standard_normal(ext), jnp.float32)
    rd = jnp.asarray(rng.standard_normal(ext), jnp.float32)
    # all-fluid flags in the deep layout: 1 inside the global extended
    # domain, 0 beyond (the dead ring)
    gjv = np.arange(ext[0])[:, None] - (H - 1)
    giv = np.arange(ext[1])[None, :] - (H - 1)
    flg = ((gjv >= 0) & (gjv <= gj + 1) & (giv >= 0)
           & (giv <= gi + 1)).astype(np.float32)
    offs = jnp.asarray([0, 0], jnp.int32)
    p_p = sp.pad_array(pd, br, h)
    r_p = sp.pad_array(rd, br, h)
    f_p = sp.pad_array(jnp.asarray(flg), br, h)

    @jax.jit
    def chain(k, x):
        def body(_, c):
            x, acc = c
            x, r = rb(offs, x, r_p, f_p)
            return x, acc + r

        return jax.lax.fori_loop(
            0, k, body, (x, jnp.zeros((), jnp.float32))
        )

    def timed(k):
        out = chain(k, p_p)
        float(out[1])
        best = float("inf")
        for _ in range(REPS):
            t0 = time.perf_counter()
            float(chain(k, p_p)[1])
            best = min(best, time.perf_counter() - t0)
        return best

    # adaptive spans: the differential must be >= ~0.5 s or it sits inside
    # per-dispatch latency jitter (measurement pitfall; a 30 ms
    # differential once read 11.8G for a 21.0G kernel). Calibrate the
    # per-call cost LATENCY-FREE (two-point on the calibration itself —
    # ta/ka would fold the fixed dispatch+readback latency into the
    # estimate and undershoot the target exactly when latency is high)
    ka = 40
    ta = timed(ka)
    per = max((timed(2 * ka) - ta) / ka, 1e-6)
    kb = ka + max(80, int(0.6 / per))
    tb = timed(kb)
    iters = (kb - ka) * N_INNER
    ups = jl * il * iters / max(tb - ta, 1e-9)
    return {"updates_per_sec": round(ups / 1e9, 2), "unit": "G",
            "halo_depth": H, "shard": [jl, il], "n_inner": N_INNER,
            "spans": [ka, kb]}


def jnp_ca_ragged_rate(gj, gi, jl, il) -> dict:
    """The jnp CA path ragged runs took before round 5 (ca_rb_iters at
    n=1, H=3, under a 1x1 shard_map for the axis context) — the
    comparator the fast path replaced."""
    from jax.sharding import Mesh, PartitionSpec as P

    from pampi_tpu.parallel.stencil2d import ca_halo, ca_masks, ca_rb_iters

    n = 1
    H = ca_halo(n, True)
    dx, dy = 1.0 / gi, 1.0 / gj
    idx2, idy2 = 1.0 / (dx * dx), 1.0 / (dy * dy)
    factor = 0.5 * (dx * dx * dy * dy) / (dx * dx + dy * dy) * 1.9
    rng = np.random.default_rng(5)
    ext = (jl + 2 * H, il + 2 * H)
    pd = jnp.asarray(rng.standard_normal(ext), jnp.float32)
    rd = jnp.asarray(rng.standard_normal(ext), jnp.float32)
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("j", "i"))

    def kern(k, x, r):
        m = ca_masks(jl, il, H, gj, gi, jnp.float32)

        def body(_, c):
            x, acc = c
            x, rr = ca_rb_iters(x, r, n, m, factor, idx2, idy2)
            return x, acc + rr

        return jax.lax.fori_loop(0, k[0], body,
                                 (x, jnp.zeros((), jnp.float32)))

    from pampi_tpu.parallel.comm import compat_shard_map

    f = jax.jit(compat_shard_map(
        kern, mesh=mesh, in_specs=(P(), P(), P()), out_specs=(P(), P()),
        check_vma=False,
    ))

    def timed(k):
        ka = jnp.asarray([k], jnp.int32)
        float(f(ka, pd, rd)[1])
        best = float("inf")
        for _ in range(REPS):
            t0 = time.perf_counter()
            float(f(ka, pd, rd)[1])
            best = min(best, time.perf_counter() - t0)
        return best

    # latency-free span calibration — see masked_kernel_rate
    ka = 40
    ta = timed(ka)
    per = max((timed(2 * ka) - ta) / ka, 1e-6)
    kb = ka + max(80, int(0.6 / per))
    tb = timed(kb)
    ups = jl * il * (kb - ka) * n / max(tb - ta, 1e-9)
    return {"updates_per_sec": round(ups / 1e9, 2), "unit": "G",
            "halo_depth": H, "shard": [jl, il], "n_inner": n,
            "spans": [ka, kb]}


def ragged_step_decomposition() -> dict:
    """Step-level solve/non-solve decomposition of a RAGGED fused NS-2D
    run (PR 2: ragged shards now ride the fused phase megakernels) — the
    mesh twin of bench.py's decomposition line, via
    tools/_artifact.dist_step_decomposition. Needs >= 4 devices for a
    genuinely ragged (2, 2) mesh; below that no solver is built, so every
    field (including the dispatch tag) is null with a note — the record
    keeps the SAME key set either way so write_merged's recursive merge
    never sees keys appear and disappear across hosts. Timing fields are
    additionally null off-TPU (the dist_step_decomposition contract)."""
    from tools._artifact import dist_step_decomposition

    if len(jax.devices()) < 4:
        return {"phases": None, "steps_timed": None,
                "step_ms": None, "solve_iter_ms": None, "nonsolve_ms": None,
                "itermax": None,
                "decomposition_note": "needs >= 4 devices for a ragged mesh"}
    from pampi_tpu.models.ns2d_dist import NS2DDistSolver
    from pampi_tpu.parallel.comm import CartComm
    from pampi_tpu.utils.params import Parameter

    def make_solver(itermax):
        param = Parameter(
            name="dcavity", imax=4095, jmax=4095, re=1000.0, te=1e9,
            tau=0.5, itermax=itermax or 100, eps=1e-30, omg=1.7, gamma=0.9,
            tpu_dtype="float32", tpu_sor_inner=N_INNER,
            tpu_ca_inner=N_INNER,
        )
        s = NS2DDistSolver(param, CartComm(ndims=2, dims=(2, 2)),
                           dtype=jnp.float32)
        assert s.ragged
        return s

    return dist_step_decomposition(make_solver, "ns2d_dist_phases",
                                   reps=REPS)


if __name__ == "__main__":
    from pampi_tpu.utils import telemetry, xlacache

    xlacache.enable()  # the two-point builds recompile the same kernels
    telemetry.start_run(tool="perf_ragged")
    rec = {
        "artifact": "ragged_throughput",
        "backend": jax.default_backend(),
        "protocol": "chained-kernel / solve-vs-solve two-point "
                    "differencing, best-of-%d, scalar fences; tool: "
                    "tools/perf_ragged.py" % REPS,
    }
    rec["quarters_divisible_4096_solve"] = quarters_solve_steady()
    rec["masked_divisible_4096"] = masked_kernel_rate(
        4096, 4096, 4096, 4096, ragged=False)
    rec["masked_ragged_4095"] = masked_kernel_rate(
        4095, 4095, 2048, 2048, ragged=True)
    rec["jnp_ca_ragged_4095"] = jnp_ca_ragged_rate(4095, 4095, 2048, 2048)
    rec["ragged_step_decomposition_4095"] = ragged_step_decomposition()
    for name in ("quarters_divisible_4096_solve", "masked_divisible_4096",
                 "masked_ragged_4095", "jnp_ca_ragged_4095"):
        # kernel-rate rows as shared span records (ms=None: these are
        # steady-state rates, not single-span walls)
        telemetry.emit_span(f"ragged_throughput.{name}", None, **rec[name])
    from tools._artifact import write_merged

    write_merged(os.path.join(REPO, "results", "ragged_throughput.json"),
                 rec)
