"""Fused NS-2D step-phase kernels (ops/ns2d_fused.py) vs the jnp chain.

Equivalence contract (module docstring of ns2d_fused): pure-copy phases
(BC strips, selects, maxes) are BITWISE identical — pinned with
array_equal; the compound F/G/RHS/projection arithmetic is the SAME
formula function and differs only by compiler fusion (fma), pinned at
ulp-scale tolerances relative to the field scale. Interpret-mode Pallas on
the CPU mesh throughout (the repo's kernel-parity discipline)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pampi_tpu.models.ns2d import NS2DSolver
from pampi_tpu.ops import ns2d as ops
from pampi_tpu.ops import ns2d_fused as nf
from pampi_tpu.utils import dispatch
from pampi_tpu.utils.params import Parameter


def _ulp_close(a, b, scale=None):
    a, b = np.asarray(a), np.asarray(b)
    tol = 1e-12 if a.dtype == np.float64 else 2e-5
    s = max(1.0, np.abs(b).max() if scale is None else scale)
    return np.abs(a - b).max() <= tol * s


def _jnp_chain(param, u, v, p, dt, dx, dy, dtype):
    u1, v1 = ops.set_boundary_conditions(
        u, v, param.bcLeft, param.bcRight, param.bcBottom, param.bcTop
    )
    if param.name == "dcavity":
        u1 = ops.set_special_bc_dcavity(u1)
    elif param.name in ("canal", "canal_obstacle"):
        u1 = ops.set_special_bc_canal(u1, dy, param.ylength, dtype)
    f, g = ops.compute_fg(u1, v1, dt, param.re, param.gx, param.gy,
                          param.gamma, dx, dy)
    rhs = ops.compute_rhs(f, g, dt, dx, dy)
    u2, v2 = ops.adapt_uv(u1, v1, f, g, p, dt, dx, dy)
    return u1, v1, f, g, rhs, u2, v2


@pytest.mark.parametrize("problem,bcs", [
    ("dcavity", (1, 1, 1, 1)),
    ("canal", (3, 3, 1, 1)),
    ("dcavity", (2, 2, 2, 2)),
    ("canal", (3, 1, 2, 1)),
])
@pytest.mark.parametrize("shape", [(32, 32), (40, 24)])
def test_phase_parity(problem, bcs, shape):
    jm, im = shape
    param = Parameter(name=problem, imax=im, jmax=jm, re=100.0, gamma=0.9,
                      bcLeft=bcs[0], bcRight=bcs[1], bcBottom=bcs[2],
                      bcTop=bcs[3])
    dx, dy = param.xlength / im, param.ylength / jm
    rng = np.random.default_rng(7)
    u = jnp.asarray(rng.normal(size=(jm + 2, im + 2)))
    v = jnp.asarray(rng.normal(size=(jm + 2, im + 2)))
    p = jnp.asarray(rng.normal(size=(jm + 2, im + 2)))
    dt = jnp.asarray(0.013)
    u1, v1, f, g, rhs, u2, v2 = _jnp_chain(
        param, u, v, p, dt, dx, dy, jnp.float64)

    pre, post, pad, unpad, _h = nf.make_fused_step_2d(
        param, jm, im, dx, dy, jnp.float64, interpret=True)
    offs = jnp.zeros((2,), jnp.int32)
    dt11 = jnp.full((1, 1), dt)
    up, vp, fp, gp, rp = pre(offs, dt11, pad(u), pad(v))
    # BC phases are pure copies/negations -> bitwise
    assert jnp.array_equal(unpad(up), u1)
    assert jnp.array_equal(unpad(vp), v1)
    # compound arithmetic: ulp-equivalent (shared formula, fma differences)
    assert _ulp_close(unpad(fp), f)
    assert _ulp_close(unpad(gp), g)
    assert _ulp_close(unpad(rp), rhs, scale=float(jnp.abs(rhs).max()))
    up2, vp2, um, vm = post(offs, dt11, up, vp, fp, gp, pad(p))
    assert _ulp_close(unpad(up2), u2)
    assert _ulp_close(unpad(vp2), v2)
    # max given equal inputs is exact; here inputs are ulp-apart
    assert abs(float(um) - float(ops.max_element(u2))) <= 1e-12
    assert abs(float(vm) - float(ops.max_element(v2))) <= 1e-12


def test_multiblock_pipeline():
    """Forced small block_rows exercises the double-buffered DMA pipeline,
    halo recompute, and the tail block across block boundaries."""
    jm, im = 100, 48
    param = Parameter(name="dcavity", imax=im, jmax=jm, re=50.0)
    dx, dy = 1.0 / im, 1.0 / jm
    rng = np.random.default_rng(3)
    u = jnp.asarray(rng.normal(size=(jm + 2, im + 2)))
    v = jnp.asarray(rng.normal(size=(jm + 2, im + 2)))
    p = jnp.asarray(rng.normal(size=(jm + 2, im + 2)))
    dt = jnp.asarray(0.01)
    u1, v1, f, g, rhs, u2, v2 = _jnp_chain(
        param, u, v, p, dt, dx, dy, jnp.float64)
    for br in (16, 40):
        pre, post, pad, unpad, _h = nf.make_fused_step_2d(
            param, jm, im, dx, dy, jnp.float64, interpret=True,
            block_rows=br)
        offs = jnp.zeros((2,), jnp.int32)
        dt11 = jnp.full((1, 1), dt)
        up, vp, fp, gp, rp = pre(offs, dt11, pad(u), pad(v))
        assert jnp.array_equal(unpad(up), u1), br
        assert _ulp_close(unpad(fp), f), br
        assert _ulp_close(unpad(rp), rhs, scale=float(jnp.abs(rhs).max()))
        up2, _vp2, um, _vm = post(offs, dt11, up, vp, fp, gp, pad(p))
        assert _ulp_close(unpad(up2), u2), br
        assert abs(float(um) - float(ops.max_element(u2))) <= 1e-12


def test_obstacle_phase_parity():
    """The flag-masked mode: obstacle velocity BC, F/G face mask and
    projection face mask vs the ops/obstacle.py jnp forms."""
    from pampi_tpu.ops import obstacle as obst

    jm, im = 32, 48
    param = Parameter(name="canal_obstacle", imax=im, jmax=jm, re=10.0,
                      bcLeft=3, bcRight=3, obstacles="0.3,0.3,0.6,0.6",
                      gamma=0.9, omg=1.7)
    dx, dy = param.xlength / im, param.ylength / jm
    fluid = obst.build_fluid(im, jm, dx, dy, param.obstacles)
    m = obst.make_masks(fluid, dx, dy, param.omg, jnp.float64)
    rng = np.random.default_rng(5)
    u = jnp.asarray(rng.normal(size=(jm + 2, im + 2)))
    v = jnp.asarray(rng.normal(size=(jm + 2, im + 2)))
    p = jnp.asarray(rng.normal(size=(jm + 2, im + 2)))
    dt = jnp.asarray(0.01)
    u1, v1 = ops.set_boundary_conditions(
        u, v, param.bcLeft, param.bcRight, param.bcBottom, param.bcTop)
    u1 = ops.set_special_bc_canal(u1, dy, param.ylength, jnp.float64)
    u1, v1 = obst.apply_obstacle_velocity_bc(u1, v1, m)
    f, g = ops.compute_fg(u1, v1, dt, param.re, 0.0, 0.0, param.gamma,
                          dx, dy)
    f, g = obst.mask_fg(f, g, u1, v1, m)
    rhs = ops.compute_rhs(f, g, dt, dx, dy)
    u2, v2 = obst.adapt_uv_obstacle(u1, v1, f, g, p, dt, dx, dy, m)

    pre, post, pad, unpad, _h = nf.make_fused_step_2d(
        param, jm, im, dx, dy, jnp.float64, fluid=m.fluid, interpret=True)
    offs = jnp.zeros((2,), jnp.int32)
    dt11 = jnp.full((1, 1), dt)
    up, vp, fp, gp, rp = pre(offs, dt11, pad(u), pad(v))
    assert jnp.array_equal(unpad(up), u1)  # flag multiplies of copies
    assert jnp.array_equal(unpad(vp), v1)
    assert _ulp_close(unpad(fp), f)
    assert _ulp_close(unpad(gp), g)
    assert _ulp_close(unpad(rp), rhs, scale=float(jnp.abs(rhs).max()))
    up2, vp2, um, vm = post(offs, dt11, up, vp, fp, gp, pad(p))
    assert _ulp_close(unpad(up2), u2)
    assert _ulp_close(unpad(vp2), v2)
    assert abs(float(um) - float(ops.max_element(u2))) <= 1e-12


def _run_solver(fuse, **kw):
    base = dict(name="dcavity", imax=32, jmax=32, re=10.0, te=0.04,
                tau=0.5, itermax=80, eps=1e-4, omg=1.7, gamma=0.9)
    base.update(kw)
    s = NS2DSolver(Parameter(tpu_fuse_phases=fuse, **base))
    s.run(progress=False)
    return s


@pytest.mark.parametrize("kw", [
    {},
    dict(name="canal", bcLeft=3, bcRight=3, te=0.02),
    dict(name="canal_obstacle", imax=48, bcLeft=3, bcRight=3,
         obstacles="0.3,0.3,0.6,0.6", te=0.02),
    dict(tau=-1.0, dt=0.002, te=0.02),
    dict(tpu_solver="fft", te=0.02),
])
def test_solver_e2e_fused_matches_jnp(kw):
    """Whole NS2DSolver runs: tpu_fuse_phases on (interpret kernels, the
    carried-padded-state chunk, carried CFL maxes) vs the jnp chain."""
    a, b = _run_solver("off", **kw), _run_solver("on", **kw)
    assert b._fused and not a._fused
    assert a.nt == b.nt
    for n in ("u", "v", "p"):
        d = np.abs(np.asarray(getattr(a, n)) - np.asarray(getattr(b, n)))
        assert np.isfinite(d).all() and d.max() < 1e-9, n


def test_dist_fused_matches_single():
    """NS2DDistSolver with fused per-shard kernels (deep-halo PRE, ext
    POST) vs the single-device jnp solver on the faked 8-device mesh."""
    from pampi_tpu.models.ns2d_dist import NS2DDistSolver
    from pampi_tpu.parallel.comm import CartComm

    param = Parameter(name="dcavity", imax=64, jmax=64, re=10.0, te=0.003,
                      tau=0.5, itermax=60, eps=1e-4, omg=1.7, gamma=0.9)
    single = NS2DSolver(param.replace(tpu_fuse_phases="off"))
    single.run(progress=False)
    for dims in [(4, 2), (1, 8)]:
        dist = NS2DDistSolver(param.replace(tpu_fuse_phases="on"),
                              CartComm(ndims=2, dims=dims))
        dist.run(progress=False)
        assert dispatch.last("ns2d_dist_phases") == "pallas_fused (forced)"
        ud, vd, pd = dist.fields()
        assert dist.nt == single.nt
        for n, (x, y) in {"u": (single.u, ud), "v": (single.v, vd),
                          "p": (single.p, pd)}.items():
            d = np.abs(np.asarray(x) - y)
            assert np.isfinite(d).all() and d.max() < 1e-10, (dims, n)


def test_dist_canal_fused_matches_single():
    """Canal exercises OUTFLOW walls and the global-j inflow profile
    (idx-dtype path) through the fused per-shard kernels."""
    from pampi_tpu.models.ns2d_dist import NS2DDistSolver
    from pampi_tpu.parallel.comm import CartComm

    param = Parameter(name="canal", imax=48, jmax=32, re=10.0, te=0.01,
                      tau=0.5, itermax=60, eps=1e-4, omg=1.7, gamma=0.9,
                      bcLeft=3, bcRight=3)
    single = NS2DSolver(param.replace(tpu_fuse_phases="off"))
    single.run(progress=False)
    dist = NS2DDistSolver(param.replace(tpu_fuse_phases="on"),
                          CartComm(ndims=2, dims=(2, 4)))
    dist.run(progress=False)
    ud, vd, pd = dist.fields()
    assert dist.nt == single.nt
    for n, (x, y) in {"u": (single.u, ud), "v": (single.v, vd),
                      "p": (single.p, pd)}.items():
        d = np.abs(np.asarray(x) - y)
        assert np.isfinite(d).all() and d.max() < 1e-10, n


def test_obstacle_calltime_flag_matches_baked():
    """The distributed-obstacle mode (fluid=True: the flag is a call-time
    argument) must be BITWISE the single-device baked-constant mode on the
    same geometry — same kernels, same windows, only the flag's delivery
    differs."""
    from pampi_tpu.ops import obstacle as obst

    jm, im = 32, 48
    param = Parameter(name="canal_obstacle", imax=im, jmax=jm, re=10.0,
                      bcLeft=3, bcRight=3, obstacles="0.3,0.3,0.6,0.6",
                      gamma=0.9, omg=1.7)
    dx, dy = param.xlength / im, param.ylength / jm
    fluid = obst.build_fluid(im, jm, dx, dy, param.obstacles)
    m = obst.make_masks(fluid, dx, dy, param.omg, jnp.float64)
    rng = np.random.default_rng(5)
    u = jnp.asarray(rng.normal(size=(jm + 2, im + 2)))
    v = jnp.asarray(rng.normal(size=(jm + 2, im + 2)))
    p = jnp.asarray(rng.normal(size=(jm + 2, im + 2)))
    dt11 = jnp.full((1, 1), 0.01)
    offs = jnp.zeros((2,), jnp.int32)
    pre_b, post_b, pad, unpad, _h = nf.make_fused_step_2d(
        param, jm, im, dx, dy, jnp.float64, fluid=m.fluid, interpret=True)
    pre_c, _p1, _u1, _h1 = nf.make_fused_pre_2d(
        param, jm, im, dx, dy, jnp.float64, fluid=True, interpret=True)
    post_c, _p2, _u2, _h2 = nf.make_fused_post_2d(
        param, jm, im, dx, dy, jnp.float64, fluid=True, interpret=True)
    flg = pad(m.fluid)
    outs_b = pre_b(offs, dt11, pad(u), pad(v))
    outs_c = pre_c(offs, dt11, pad(u), pad(v), flg)
    for a, b in zip(outs_b, outs_c):
        assert jnp.array_equal(unpad(a), unpad(b))
    up, vp, fp, gp, _r = outs_b
    got_b = post_b(offs, dt11, up, vp, fp, gp, pad(p))
    got_c = post_c(offs, dt11, up, vp, fp, gp, pad(p), flg)
    for a, b in zip(got_b[:2], got_c[:2]):
        assert jnp.array_equal(unpad(a), unpad(b))
    assert float(got_b[2]) == float(got_c[2])
    assert float(got_b[3]) == float(got_c[3])


def test_ragged_post_live_mask():
    """POST(ragged=True) must zero dead pad cells after the projection —
    bitwise the plain POST times the live mask (the jnp ragged chain's
    live_masks multiply), with the CFL max scanning live cells only."""
    jm_global, im_global = 27, 21   # trailing-shard view: block > global
    jl, il = 32, 24
    param = Parameter(name="dcavity", imax=im_global, jmax=jm_global,
                      re=10.0)
    dx, dy = 1.0 / im_global, 1.0 / jm_global
    rng = np.random.default_rng(9)
    shp = (jl + 2, il + 2)
    u = jnp.asarray(rng.normal(size=shp))
    v = jnp.asarray(rng.normal(size=shp))
    f = jnp.asarray(rng.normal(size=shp))
    g = jnp.asarray(rng.normal(size=shp))
    p = jnp.asarray(rng.normal(size=shp))
    dt11 = jnp.full((1, 1), 0.01)
    offs = jnp.zeros((2,), jnp.int32)
    kw = dict(jl=jl, il=il, interpret=True)
    post_r, pad, unpad, _h = nf.make_fused_post_2d(
        param, jm_global, im_global, dx, dy, jnp.float64, ragged=True, **kw)
    post_p, _p, _u, _h2 = nf.make_fused_post_2d(
        param, jm_global, im_global, dx, dy, jnp.float64, **kw)
    ur, vr, umr, vmr = post_r(offs, dt11, pad(u), pad(v), pad(f), pad(g),
                              pad(p))
    up, vp, _um, _vm = post_p(offs, dt11, pad(u), pad(v), pad(f), pad(g),
                              pad(p))
    gj = np.arange(jl + 2)[:, None]
    gi = np.arange(il + 2)[None, :]
    live = ((gj <= jm_global + 1) & (gi <= im_global + 1))
    assert jnp.array_equal(unpad(ur), unpad(up) * live)
    assert jnp.array_equal(unpad(vr), unpad(vp) * live)
    # the ragged CFL max never sees dead cells
    assert float(umr) == float(np.abs(np.asarray(unpad(ur))).max())
    assert float(vmr) == float(np.abs(np.asarray(unpad(vr))).max())


def test_dist_ragged_fused_matches_single():
    """Ragged shards on the fused kernels (uneven block bounds + the POST
    live-mask multiply) vs the single-device jnp chain — with and without
    an obstacle flag field riding along."""
    from pampi_tpu.models.ns2d_dist import NS2DDistSolver
    from pampi_tpu.parallel.comm import CartComm

    cases = [
        Parameter(name="dcavity", imax=50, jmax=50, re=10.0, te=0.003,
                  tau=0.5, itermax=60, eps=1e-4, omg=1.7, gamma=0.9),
        Parameter(name="canal_obstacle", imax=50, jmax=30, re=10.0,
                  te=0.003, tau=0.5, itermax=60, eps=1e-4, omg=1.7,
                  gamma=0.9, bcLeft=3, bcRight=3,
                  obstacles="0.3,0.3,0.6,0.6"),
    ]
    for param in cases:
        single = NS2DSolver(param.replace(tpu_fuse_phases="off"))
        single.run(progress=False)
        dist = NS2DDistSolver(param.replace(tpu_fuse_phases="on"),
                              CartComm(ndims=2, dims=(4, 2)))
        assert dist.ragged
        dist.run(progress=False)
        assert dispatch.last("ns2d_dist_phases") == "pallas_fused (forced)"
        ud, vd, pd = dist.fields()
        assert dist.nt == single.nt
        for n, (x, y) in {"u": (single.u, ud), "v": (single.v, vd),
                          "p": (single.p, pd)}.items():
            d = np.abs(np.asarray(x) - y)
            assert np.isfinite(d).all() and d.max() < 1e-9, (param.name, n)


def test_dist_obstacle_fused_matches_single():
    """Distributed obstacle flags through the fused kernels (per-shard
    call-time global-constant flag slices) vs the single-device jnp
    chain."""
    from pampi_tpu.models.ns2d_dist import NS2DDistSolver
    from pampi_tpu.parallel.comm import CartComm

    param = Parameter(name="canal_obstacle", imax=64, jmax=32, re=10.0,
                      te=0.003, tau=0.5, itermax=60, eps=1e-4, omg=1.7,
                      gamma=0.9, bcLeft=3, bcRight=3,
                      obstacles="0.3,0.3,0.6,0.6")
    single = NS2DSolver(param.replace(tpu_fuse_phases="off"))
    single.run(progress=False)
    dist = NS2DDistSolver(param.replace(tpu_fuse_phases="on"),
                          CartComm(ndims=2, dims=(2, 4)))
    assert not dist.ragged and dist.masks is not None
    dist.run(progress=False)
    assert dispatch.last("ns2d_dist_phases") == "pallas_fused (forced)"
    ud, vd, pd = dist.fields()
    assert dist.nt == single.nt
    for n, (x, y) in {"u": (single.u, ud), "v": (single.v, vd),
                      "p": (single.p, pd)}.items():
        d = np.abs(np.asarray(x) - y)
        assert np.isfinite(d).all() and d.max() < 1e-9, n


# the recursive pallas-launch counter lives in the shared analysis
# layer (one home for every jaxpr pin — see tools/lint.py)
from pampi_tpu.analysis.jaxprcheck import count_prim as _count_prim


def _while_body(jaxpr):
    for e in jaxpr.eqns:
        if e.primitive.name == "while":
            return e.params["body_jaxpr"].jaxpr
    raise AssertionError("no while loop in chunk jaxpr")


def test_launch_count_regression():
    """The fused chunk's step must lower to exactly TWO pallas kernels
    (pre + post; fft solve contributes none) and collapse the jnp chain's
    op count — the launch-amortization property this PR exists for."""
    param = Parameter(name="dcavity", imax=32, jmax=32, re=10.0, te=0.05,
                      tau=0.5, itermax=40, eps=1e-4, tpu_solver="fft")
    fused = NS2DSolver(param.replace(tpu_fuse_phases="on"))
    plain = NS2DSolver(param.replace(tpu_fuse_phases="off"))
    state = (plain.u, plain.v, plain.p, jnp.asarray(0.0, jnp.float64),
             jnp.asarray(0, jnp.int32))
    jx_f = jax.make_jaxpr(fused._build_chunk())(*state)
    jx_p = jax.make_jaxpr(plain._build_chunk())(*state)
    assert _count_prim(jx_f.jaxpr, "pallas_call") == 2
    assert _count_prim(jx_p.jaxpr, "pallas_call") == 0
    body_f = _while_body(jx_f.jaxpr)
    body_p = _while_body(jx_p.jaxpr)
    # the fused step body is a handful of launches (2 kernels + layout
    # slices + the solve + scalar math) vs the ~40-op jnp phase chain
    assert len(body_f.eqns) * 2 < len(body_p.eqns), (
        len(body_f.eqns), len(body_p.eqns))


def test_dist_fused_launch_count():
    """Each newly fused dist family's per-shard chunk lowers to exactly
    TWO pallas kernels per step (pre + post; the jnp CA solve contributes
    none) — the launch-amortization property, per family."""
    from pampi_tpu.models.ns2d_dist import NS2DDistSolver
    from pampi_tpu.parallel.comm import CartComm

    cases = [
        ("ragged", Parameter(name="dcavity", imax=50, jmax=50, re=10.0,
                             te=0.05, tau=0.5, itermax=20, eps=1e-3),
         (4, 2)),
        ("obstacle", Parameter(name="canal_obstacle", imax=64, jmax=32,
                               re=10.0, te=0.05, tau=0.5, itermax=20,
                               eps=1e-3, bcLeft=3, bcRight=3,
                               obstacles="0.3,0.3,0.6,0.6"), (2, 4)),
    ]
    for tag, param, dims in cases:
        fused = NS2DDistSolver(param.replace(tpu_fuse_phases="on"),
                               CartComm(ndims=2, dims=dims))
        plain = NS2DDistSolver(param.replace(tpu_fuse_phases="off"),
                               CartComm(ndims=2, dims=dims))
        state = (fused.u, fused.v, fused.p, jnp.asarray(0.0, jnp.float64),
                 jnp.asarray(0, jnp.int32))
        jx_f = jax.make_jaxpr(fused._chunk_sm)(*state)
        jx_p = jax.make_jaxpr(plain._chunk_sm)(*state)
        assert _count_prim(jx_f.jaxpr, "pallas_call") == 2, tag
        assert _count_prim(jx_p.jaxpr, "pallas_call") == 0, tag


def test_p_layout_fold():
    """The p-layout fold (the ROADMAP post-fusion knob): on the
    checkerboard solve layout the pressure solve runs DIRECTLY on the
    fused padded layout — dispatch records the fold, the chunk lowers to
    exactly THREE pallas calls (pre + tblock solve + post, no layout
    passes between them), and results match the jnp chain. The auto
    layout on even grids keeps quarters with explicit conversions."""
    base = dict(name="dcavity", imax=32, jmax=32, re=10.0, te=0.04,
                tau=0.5, itermax=80, eps=1e-4, omg=1.7, gamma=0.9,
                tpu_sor_layout="checkerboard", tpu_sor_inner=1)
    a = _run_solver("off", **base)
    b = _run_solver("on", **base)
    assert dispatch.last("ns2d_p_layout").startswith("folded")
    assert b._fused and a.nt == b.nt
    for n in ("u", "v", "p"):
        d = np.abs(np.asarray(getattr(a, n)) - np.asarray(getattr(b, n)))
        assert np.isfinite(d).all() and d.max() < 1e-9, n
    state = (a.u, a.v, a.p, jnp.asarray(0.0, jnp.float64),
             jnp.asarray(0, jnp.int32))
    jx = jax.make_jaxpr(b._build_chunk())(*state)
    assert _count_prim(jx.jaxpr, "pallas_call") == 3
    # auto on an even grid: quarters stays the solve home, no fold
    NS2DSolver(Parameter(tpu_fuse_phases="on",
                         **{**base, "tpu_sor_layout": "auto"}))
    assert dispatch.last("ns2d_p_layout") == "explicit pad/unpad"


def test_retry_backend_disables_fusion():
    """models/_driver.pallas_retry rebuilds the chunk with backend='jnp';
    the fused path must then stand down (and _uses_pallas with it)."""
    param = Parameter(name="dcavity", imax=16, jmax=16, re=10.0, te=0.02,
                      tau=0.5, itermax=20, eps=1e-3,
                      tpu_fuse_phases="on")
    s = NS2DSolver(param)
    assert s._fused and s._uses_pallas()
    s._build_chunk(backend="jnp")
    assert not s._fused
    assert dispatch.last("ns2d_phases") == "jnp (retry fallback backend)"


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
def test_probe_failure_raises_on_tpu(monkeypatch, backend):
    """A kernel family whose probe fails is an error on a TPU (the
    compiler's message raised), and a reported-unavailable family
    elsewhere — never a silent drop to the jnp chain on the chip."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    exc = ValueError("Mosaic says no")
    if backend == "tpu":
        with pytest.raises(RuntimeError, match="Mosaic says no"):
            dispatch.probe_failed("the kernel", exc)
    else:
        with pytest.warns(UserWarning, match="unavailable"):
            assert dispatch.probe_failed("the kernel", exc) is False


@pytest.mark.parametrize("backend,want", [
    ("auto", "jnp (no TPU)"),
    ("jnp", "jnp (retry fallback backend)"),
    ("pallas", "pallas_quarters (n_inner=2)"),
])
def test_sor2d_dispatch_recorded(backend, want):
    """The 2-D SOR backend decision lands in the snapshot the chip smoke
    checks (`sor2d`), with the reason on the jnp side."""
    from pampi_tpu.models.poisson import make_rb_loop

    dispatch.reset()
    make_rb_loop(16, 16, 1 / 16, 1 / 16, 1.7, jnp.float32,
                 backend=backend, n_inner=2)
    assert dispatch.snapshot() == {"sor2d": want}


def test_fuse_knob_validation():
    with pytest.raises(ValueError, match="tpu_fuse_phases"):
        NS2DSolver(Parameter(name="dcavity", imax=16, jmax=16,
                             tpu_fuse_phases="always"))
