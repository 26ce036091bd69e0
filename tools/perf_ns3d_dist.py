"""Decompose the NS-3D DISTRIBUTED step cost at 128^3 on the real chip.

Round-3 record was distributed 81.4 ms/step vs single-device 47.5 on a
(1,1,1) mesh shard. This tool's measurements located the cost in the octant
kernel's stored CA halos (2n planes on ALL axes even when the mesh axis has
size 1 — +25% window cells) and in runtime-qoff masks; the round-4 per-axis
deep-halo layout (parallel/octants_dist.OGeom.d) closed the gap to parity.

Modes (second argv word):
  full      chunk-vs-chunk + component timings        (default)
  envelope  itermax sweep: step-minus-solve envelope  (fixed-depth solves)
  solve     settled-state solve-vs-solve with iteration counts + field diff

Run on TPU: python tools/perf_ns3d_dist.py [chunk_steps] [mode]
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from pampi_tpu.models.ns3d import NS3DSolver, make_pressure_solve_3d
from pampi_tpu.models.ns3d_dist import NS3DDistSolver
from pampi_tpu.ops import ns3d as ops
from pampi_tpu.parallel import octants_dist as od
from pampi_tpu.parallel.comm import (
    CartComm, get_offsets, halo_exchange, reduction,
)
from pampi_tpu.utils.params import Parameter

STEPS = int(sys.argv[1]) if len(sys.argv) > 1 else 32
MODE = sys.argv[2] if len(sys.argv) > 2 else "full"
DT = jnp.float32

from pampi_tpu.utils import xlacache  # noqa: E402

xlacache.enable()  # the big dist solver builds become disk loads


def bench(fn, *args, reps=3):
    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best, out


def make_param(itermax=1000, eps=1e-3):
    param = Parameter()
    param.name = "dcavity3d"
    param.imax = param.jmax = param.kmax = 128
    param.xlength = param.ylength = param.zlength = 1.0
    param.re = 1000.0
    param.te = 1e9  # never stop inside the chunk
    param.tau = 0.5
    param.eps = eps
    param.itermax = itermax
    param.omg = 1.8
    param.tpu_dtype = "float32"
    return param


T0 = jnp.asarray(0.0, jnp.float32)
NT0 = jnp.asarray(0, jnp.int32)


def dist_chunk_msstep(param, comm, settle=2):
    d = NS3DDistSolver(param, comm=comm, dtype=DT)
    d.CHUNK = STEPS
    d._build()
    # initial_state matches the chunk's arity (telemetry appends the
    # in-band metrics vector); the u/v/w/p it carries ARE _init_sm's
    state = d.initial_state()
    for _ in range(settle):
        state = d._chunk_sm(*state)
    jax.block_until_ready(state)
    tsec, s2 = bench(d._chunk_sm, *state)
    return tsec * 1e3 / max(int(s2[5]) - int(state[5]), 1)


def single_chunk_msstep(param, settle=2):
    s = NS3DSolver(param, dtype=DT)
    s.CHUNK = STEPS
    s._chunk_fn = jax.jit(s._build_chunk())
    state = s.initial_state()
    for _ in range(settle):
        state = s._chunk_fn(*state)
    jax.block_until_ready(state)
    tsec, s2 = bench(s._chunk_fn, *state)
    return tsec * 1e3 / max(int(s2[5]) - int(state[5]), 1)


def build_ogeom(param, comm, d):
    kl, jl, il = d.kl, d.jl, d.il
    n_o = od.odist_clamp(
        max(param.tpu_ca_inner, param.tpu_sor_inner), kl, jl, il, comm.dims
    )
    return n_o, od.make_ogeom(param.kmax, param.jmax, param.imax,
                              kl, jl, il, n_o, DT, dims=comm.dims)


def settled_solve_inputs(param):
    """64 settled steps on the single-device solver, then the (p, rhs) that
    the NEXT pressure solve would see."""
    s = NS3DSolver(param, dtype=DT)
    s.CHUNK = 32
    s._chunk_fn = jax.jit(s._build_chunk())
    st = s.initial_state()
    for _ in range(2):
        st = s._chunk_fn(*st)
    jax.block_until_ready(st)
    g = s.grid
    bcs = {"top": param.bcTop, "bottom": param.bcBottom,
           "left": param.bcLeft, "right": param.bcRight,
           "front": param.bcFront, "back": param.bcBack}

    @jax.jit
    def nsi(u, v, w, p):
        dt = ops.compute_timestep_3d(
            u, v, w, jnp.asarray(s.dt_bound, DT), g.dx, g.dy, g.dz,
            param.tau)
        u, v, w = ops.set_boundary_conditions_3d(u, v, w, bcs)
        u = ops.set_special_bc_dcavity_3d(u)
        f, g_, h = ops.compute_fgh(u, v, w, dt, param.re, param.gx,
                                   param.gy, param.gz, param.gamma,
                                   g.dx, g.dy, g.dz)
        return p, ops.compute_rhs(f, g_, h, dt, g.dx, g.dy, g.dz)

    p0, rhs0 = nsi(st[0], st[1], st[2], st[3])
    jax.block_until_ready((p0, rhs0))
    return s, p0, rhs0


if MODE == "full":
    param = make_param()
    comm = CartComm(ndims=3)
    print(f"mesh dims: {comm.dims}")
    dist_ms = dist_chunk_msstep(param, comm)
    single_ms = single_chunk_msstep(param)
    print(f"dist chunk:   {dist_ms:7.2f} ms/step")
    print(f"single chunk: {single_ms:7.2f} ms/step")

    # the committed-artifact record (VERDICT r4 item 6: the 45.5-vs-45.3
    # parity number had no results/ file)
    import os

    from tools._artifact import write_merged

    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "results", "ns3d_dist_parity.json")
    write_merged(out, {
        "artifact": "ns3d_dist_parity",
        "config": f"dcavity3d 128^3 f32, Re=1000, eps=1e-3, itermax=1000, "
                  f"one shard of a {comm.dims} mesh, {STEPS} steps/chunk",
        "protocol": "settled 2 chunks, chunk-vs-chunk best-of-3 "
                    "(tools/perf_ns3d_dist.py full mode)",
        "backend": jax.default_backend(),
        "dist_ms_per_step": round(dist_ms, 2),
        "single_ms_per_step": round(single_ms, 2),
        "ratio": round(dist_ms / single_ms, 3),
    })

    dsolver = NS3DDistSolver(param, comm=comm, dtype=DT)
    n_o, og = build_ogeom(param, comm, dsolver)
    print(f"ogeom: n={og.n} d={og.d} bk={og.bk} "
          f"stored=({og.sp},{og.jp2},{og.ip2})")
    spec = P("k", "j", "i")
    pz = dsolver._init_sm()[3]

    def pack_unpack(pext):
        return od.unpack_o_to_ext(od.pack_ext_to_o(pext, og), og)

    pu = jax.jit(comm.shard_map(pack_unpack, in_specs=(spec,),
                                out_specs=spec))
    tsec, _ = bench(pu, pz)
    print(f"pack+unpack roundtrip (one small dispatch; latency "
          f"dominated): {tsec*1e3:8.2f} ms")

elif MODE == "envelope":
    comm = CartComm(ndims=3)
    for itermax in (4, 32, 64):
        param = make_param(itermax=itermax, eps=1e-30)
        dms = dist_chunk_msstep(param, comm, settle=1)
        sms = single_chunk_msstep(param, settle=1)
        print(f"itermax={itermax:3d}: dist {dms:7.2f} ms/step  "
              f"single {sms:7.2f} ms/step  gap {dms-sms:6.2f}")

elif MODE == "solve":
    param = make_param()
    s, p0, rhs0 = settled_solve_inputs(param)
    g = s.grid
    solve_s = jax.jit(make_pressure_solve_3d(
        g.imax, g.jmax, g.kmax, g.dx, g.dy, g.dz, param.omg, param.eps,
        param.itermax, DT, backend="auto", n_inner=param.tpu_sor_inner,
        solver="sor", layout="auto"))
    tsec, (ps, res, it) = bench(solve_s, p0, rhs0)
    print(f"single solve: {tsec*1e3:8.2f} ms  res={float(res):.3e} "
          f"it={int(it)}")

    comm = CartComm(ndims=3)
    d = NS3DDistSolver(param, comm=comm, dtype=DT)
    from pampi_tpu.ops.sor_odist import make_rb_iters_odist

    kl, jl, il = d.kl, d.jl, d.il
    n_o, og = build_ogeom(param, comm, d)
    rb_o = make_rb_iters_odist(og, g.dx, g.dy, g.dz, param.omg, DT)
    epssq = param.eps * param.eps
    norm = float(g.imax * g.jmax * g.kmax)

    def solve_d(p, rhs):
        qoffs = jnp.stack([
            (get_offsets("k", kl) // 2).astype(jnp.int32),
            (get_offsets("j", jl) // 2).astype(jnp.int32),
            (get_offsets("i", il) // 2).astype(jnp.int32)])
        ro = od.o_exchange(od.pack_ext_to_o(rhs, og), comm, og)
        xo = od.pack_ext_to_o(p, og)

        def cond(c):
            return jnp.logical_and(c[1] >= epssq, c[2] < param.itermax)

        def body(c):
            xo, _, it = c
            xo = od.o_exchange(xo, comm, og)
            xo, r2 = rb_o(qoffs, xo, ro)
            return xo, reduction(r2, comm, "sum") / norm, it + n_o

        xo, res, it = lax.while_loop(
            cond, body, (xo, jnp.asarray(1.0, DT), jnp.asarray(0, jnp.int32)))
        return halo_exchange(od.unpack_o_to_ext(xo, og), comm), res, it

    spec = P("k", "j", "i")
    solve_dj = jax.jit(comm.shard_map(
        solve_d, in_specs=(spec, spec), out_specs=(spec, P(), P()),
        check_vma=False))
    tsec, (pd, res, it) = bench(solve_dj, p0, rhs0)
    print(f"dist solve:   {tsec*1e3:8.2f} ms  res={float(res):.3e} "
          f"it={int(it)}")
    print(f"|pd-ps| max = {float(jnp.max(jnp.abs(pd - ps))):.3e}")

else:
    raise SystemExit(f"unknown mode {MODE!r}: full|envelope|solve")
