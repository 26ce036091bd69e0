"""Distributed quarter-layout SOR (parallel/quarters_dist + ops/sor_qdist):
the round-3 production multi-chip path. Parity ladder:

1. jnp twin == interpret-mode Pallas kernel, bitwise, on raw stacked planes
   (arbitrary global offsets — the mask formulas must be in lockstep).
2. Distributed quarters solve == single-device oracle across mesh shapes.
3. CA-depth independence: the trajectory does not depend on n (exact
   redundant-recompute semantics, ≙ tests/test_ca_sor.py for the grid path).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pampi_tpu.models.poisson import PoissonSolver
from pampi_tpu.models.poisson_dist import DistPoissonSolver
from pampi_tpu.parallel.comm import CartComm
from pampi_tpu.parallel import quarters_dist as qd
from pampi_tpu.utils import dispatch
from pampi_tpu.utils.params import Parameter


def _param(**kw):
    base = dict(
        imax=64, jmax=64, itermax=200, eps=1e-12, omg=1.9,
        tpu_dtype="float64", tpu_sor_layout="quarters",
    )
    base.update(kw)
    return Parameter(**base)


def test_twin_bitwise_matches_interpret_kernel():
    """The jnp twin and the scalar-prefetch Pallas kernel (interpret mode)
    are the same program: bitwise-equal planes and residuals, including at
    nonzero global offsets (an off-origin shard's mask geometry)."""
    from pampi_tpu.ops.sor_qdist import make_rb_iters_qdist

    rng = np.random.default_rng(7)
    jmax = imax = 32
    jl, il = 16, 8
    n = 2
    g = qd.make_qgeom(jmax, imax, jl, il, n, jnp.float64)
    ext = jnp.asarray(rng.standard_normal((jl + 2, il + 2)))
    rhse = jnp.asarray(rng.standard_normal((jl + 2, il + 2)))
    xq = qd.pack_ext_to_q(ext, g)
    rq = qd.pack_ext_to_q(rhse, g)
    dx = dy = 1.0 / imax
    factor = 1.9 * 0.5 * (dx * dx * dy * dy) / (dx * dx + dy * dy)

    for qoff_j, qoff_i in ((0, 0), (8, 4), (0, 12)):
        m = qd.q_masks(g, qoff_j, qoff_i)
        t_x, t_r = jax.jit(qd.rb_iters_q_jnp, static_argnums=2)(
            xq, rq, g, m, factor, 1.0 / (dx * dx), 1.0 / (dy * dy)
        )
        rb = make_rb_iters_qdist(g, dx, dy, 1.9, jnp.float64, interpret=True)
        k_x, k_r = rb(jnp.asarray([qoff_j, qoff_i], jnp.int32), xq, rq)
        # the kernel stores only the band rows [h, h+nblocks*brq) — its
        # window-halo padding rows stay uninitialized (never read back)
        band = slice(g.h, g.h + g.nblocks * g.brq)
        np.testing.assert_array_equal(
            np.asarray(t_x[:, band]), np.asarray(k_x[:, band])
        )
        # residual summation order differs (per-lane/per-block accumulator
        # vs whole-array sum): ulp-level only
        np.testing.assert_allclose(float(t_r), float(k_r), rtol=1e-12)


@pytest.mark.parametrize("dtype", [jnp.float64, jnp.float32])
@pytest.mark.parametrize("jl,il", [(16, 8), (8, 20)])
def test_pack_unpack_roundtrip(jl, il, dtype):
    g = qd.make_qgeom(32, 40, jl, il, 2, dtype)
    ext = jnp.asarray(
        np.random.default_rng(0).standard_normal((jl + 2, il + 2)), dtype
    )
    out = qd.unpack_q_to_ext(qd.pack_ext_to_q(ext, g), g)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ext))


@pytest.mark.parametrize("dtype", [jnp.float64, jnp.float32])
@pytest.mark.parametrize("jl,il", [(16, 8), (12, 36)])
def test_pack_matches_numpy_construction(jl, il, dtype):
    """The stacked plane, bit for bit: zeros, with each slot's parity
    quarter ext[pr::2, pc::2] at [row_base, col_base]."""
    g = qd.make_qgeom(48, 72, jl, il, 2, dtype)
    ext = np.random.default_rng(5).standard_normal((jl + 2, il + 2))
    ext = ext.astype(np.dtype(dtype))
    want = np.zeros((4, g.rp, g.w2p), ext.dtype)
    for slot, (pr, pc) in enumerate(qd.SLOT_PARITY):
        q = ext[pr::2, pc::2]
        want[slot, g.row_base : g.row_base + q.shape[0],
             g.col_base : g.col_base + q.shape[1]] = q
    got = np.asarray(qd.pack_ext_to_q(jnp.asarray(ext), g))
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(got, want)


def test_pack_lowers_without_elementwise_gather():
    """A 2-D stride-2 index lowers to a gather that collapses both axes
    (1×1 slices through an index vector of every cell) — ~90% of a 4096²
    step on a v5e 2x2 mesh. The pack must not contain one."""
    import re

    g = qd.make_qgeom(64, 64, 32, 16, 2, jnp.float32)
    ext = jax.ShapeDtypeStruct((34, 18), jnp.float32)
    txt = jax.jit(qd.pack_ext_to_q, static_argnums=1).lower(ext, g).as_text()
    collapsed = [
        {int(d) for d in m.group(1).split(",") if d.strip()}
        for m in re.finditer(
            r"stablehlo\.gather.*?collapsed_slice_dims = \[([^\]]*)\]",
            txt, re.S,
        )
    ]
    assert not [c for c in collapsed if {0, 1} <= c], collapsed


@pytest.mark.parametrize("dims", [(2, 4), (1, 8), (8, 1), (2, 2)])
def test_qdist_matches_single_device_oracle(dims):
    """Forced-quarters distributed solve (interpret kernel on CPU) equals
    the single-device jnp red-black solver on every mesh shape — full
    reference-layout field to 1e-12 (observed bitwise)."""
    # 96 is divisible by every clamped CA depth these meshes produce
    # (n=3 on the thin shards, n=4 elsewhere), so no overshoot
    param = _param(itermax=96)
    ds = DistPoissonSolver(param, comm=CartComm(ndims=2, dims=dims))
    it_d, _ = ds.solve()
    assert "quarters" in dispatch.last("poisson_dist")

    ss = PoissonSolver(_param(tpu_sor_layout="checkerboard", itermax=96))
    it_s, _ = ss.solve()
    assert it_d == it_s == param.itermax
    np.testing.assert_allclose(
        ds.full_field(), np.asarray(jax.device_get(ss.p)), atol=1e-12, rtol=0
    )


def test_qdist_trajectory_independent_of_ca_depth():
    """n=1,2,3 runs produce identical fields after the same iteration count
    (exact CA semantics: deeper exchange + redundant recompute changes the
    message schedule, not the arithmetic)."""
    fields = []
    for n in (1, 2, 3):
        param = _param(itermax=24, tpu_ca_inner=n, tpu_sor_inner=n)
        ds = DistPoissonSolver(param, comm=CartComm(ndims=2, dims=(2, 4)))
        it, _ = ds.solve()
        assert it == 24
        fields.append(ds.full_field())
    np.testing.assert_array_equal(fields[0], fields[1])
    np.testing.assert_array_equal(fields[0], fields[2])


def test_qdist_f32_close_to_oracle():
    param = _param(tpu_dtype="float32", itermax=120)
    ds = DistPoissonSolver(param, comm=CartComm(ndims=2, dims=(2, 4)))
    ds.solve()
    ss = PoissonSolver(_param(tpu_dtype="float32",
                              tpu_sor_layout="checkerboard", itermax=120))
    ss.solve()
    np.testing.assert_allclose(
        ds.full_field(), np.asarray(jax.device_get(ss.p)),
        atol=5e-5, rtol=0,
    )


def test_ns2d_dist_quarters_vs_single(reference_dir):
    """Forced-quarters distributed NS-2D equals the single-device solver to
    ulp-level over several dcavity steps (the quarters association differs
    from the checkerboard jnp path — ops/sor_quarters.py policy — so this is
    allclose, not the grid path's array_equal)."""
    from pampi_tpu.models.ns2d import NS2DSolver
    from pampi_tpu.models.ns2d_dist import NS2DDistSolver
    from pampi_tpu.utils.params import read_parameter

    param = read_parameter(
        str(reference_dir / "assignment-5/sequential/dcavity.par")
    ).replace(te=0.003, imax=64, jmax=64, tpu_sor_layout="quarters")
    dist = NS2DDistSolver(param, CartComm(ndims=2, dims=(2, 4)))
    dist.run(progress=False)
    assert "quarters" in dispatch.last("ns2d_dist")

    single = NS2DSolver(param.replace(tpu_sor_layout="checkerboard"))
    single.run(progress=False)
    assert dist.nt == single.nt
    ud, vd, pd = dist.fields()
    # residual summation order can flip a convergence-gated iteration at the
    # eps threshold, so parity is trajectory-level (1e-8), not bitwise
    np.testing.assert_allclose(np.asarray(single.u), ud, atol=1e-8, rtol=0)
    np.testing.assert_allclose(np.asarray(single.v), vd, atol=1e-8, rtol=0)
    # p is the directly-iterated quantity: a flipped convergence-gated
    # iteration moves it at the per-update level near eps
    np.testing.assert_allclose(np.asarray(single.p), pd, atol=1e-6, rtol=0)


def test_qdist_clamp_and_eligibility():
    assert qd.qdist_clamp(8, 8, 8) == 3
    assert qd.qdist_clamp(0, 64, 64) == 1
    assert qd.qdist_supported(64, 64, 16, 8)
    assert not qd.qdist_supported(63, 64, 16, 8)   # odd global
    assert not qd.qdist_supported(64, 64, 16, 2)   # shard too thin
    with pytest.raises(ValueError):
        # 72/8 = 9: odd per-shard extent — forced quarters must refuse
        DistPoissonSolver(
            _param(imax=72, jmax=72),
            comm=CartComm(ndims=2, dims=(8, 1)),
        )


def test_obstacle_dist_pallas_bitwise_matches_jnp():
    """The per-shard flag-masked Pallas kernel (ops/sor_obsdist, interpret
    on CPU) is the same program as the jnp CA obstacle path — bitwise, on
    the 8-device mesh, at matched CA depth (f64: the kernel computes
    omega/denom exactly as make_masks does)."""
    from jax.sharding import PartitionSpec as P

    from pampi_tpu.ops import obstacle as obst
    from pampi_tpu.parallel.comm import halo_exchange

    imax, jmax = 64, 32
    dx, dy = 16.0 / imax, 4.0 / jmax
    fluid = obst.build_fluid(imax, jmax, dx, dy, "6.0,1.5,10.0,2.5")
    m = obst.make_masks(fluid, dx, dy, 1.7, jnp.float64)
    comm = CartComm(ndims=2, dims=(2, 4))
    jl, il = jmax // 2, imax // 4
    rng = np.random.default_rng(1)
    p0 = jnp.asarray(rng.standard_normal((jmax + 2, imax + 2)))
    rhs = jnp.asarray(rng.standard_normal((jmax + 2, imax + 2)))

    outs = {}
    for backend in ("auto", "pallas"):  # auto on CPU = jnp CA
        solve, used_pallas = obst.make_dist_obstacle_solver(
            comm, imax, jmax, jl, il, dx, dy, 1e-12, 60, m, jnp.float64,
            ca_n=2, sor_inner=2, backend=backend,
        )
        expect = "jnp_ca ca2" if backend == "auto" else "pallas ca2"
        assert dispatch.last("obstacle_dist") == expect
        assert used_pallas == (backend == "pallas")

        def kern(p_int, rhs_int, _solve=solve):
            pe = halo_exchange(jnp.pad(p_int, 1), comm)
            re = halo_exchange(jnp.pad(rhs_int, 1), comm)
            p, res, it = _solve(pe, re)
            return p[1:-1, 1:-1], res, it

        spec = P("j", "i")
        f = jax.jit(comm.shard_map(
            kern, in_specs=(spec, spec), out_specs=(spec, P(), P()),
            check_vma=False,
        ))
        p_out, res, it = f(p0[1:-1, 1:-1], rhs[1:-1, 1:-1])
        outs[backend] = (np.asarray(p_out), int(it))

    assert outs["auto"][1] == outs["pallas"][1] == 60
    np.testing.assert_array_equal(outs["auto"][0], outs["pallas"][0])


def test_obsdist_kernel_multiblock_matches_jnp_twin():
    """The multi-block DMA pipeline (nblocks >= 3: double-buffer slot
    rotation, b>=2 store drains, cross-block owned-residual accumulation)
    against ca_rb_iters_obstacle directly — plane bitwise AND residual
    parity (the mesh-level test's convergence counts are cap-bound, so it
    never checks res)."""
    from pampi_tpu.ops import obstacle as obst
    from pampi_tpu.ops import sor_pallas as sp
    from pampi_tpu.ops.sor_obsdist import make_rb_iters_obsdist
    from pampi_tpu.parallel.stencil2d import ca_masks

    imax, jmax = 64, 32
    dx, dy = 16.0 / imax, 4.0 / jmax
    fluid = obst.build_fluid(imax, jmax, dx, dy, "6.0,1.5,10.0,2.5")
    m = obst.make_masks(fluid, dx, dy, 1.7, jnp.float64)
    jl, il = jmax, imax  # single shard: offsets 0, full domain
    n = 2
    H = 2 * n
    rb, br, h = make_rb_iters_obsdist(
        jmax, imax, jl, il, n, dx, dy, 1.7, jnp.float64,
        interpret=True, block_rows=8,  # ext_j=40 -> nblocks=5
    )
    assert -(-(jl + 2 * H) // br) >= 3

    rng = np.random.default_rng(3)
    pd = jnp.asarray(rng.standard_normal((jl + 2 * H, il + 2 * H)))
    rd = jnp.asarray(rng.standard_normal((jl + 2 * H, il + 2 * H)))
    offs = jnp.asarray([0, 0], jnp.int32)
    k_p, k_r = rb(offs, sp.pad_array(pd, br, h), sp.pad_array(rd, br, h),
                  sp.pad_array(
                      jnp.pad(m.fluid, [(H - 1, H - 1)] * 2).astype(
                          jnp.float64
                      ), br, h))
    k_p = sp.unpad_array(k_p, jl + 2 * H - 2, il + 2 * H - 2, h)

    # the jnp twin's deep masks use get_offsets (axis_index), so it must
    # run under a (1,1)-mesh shard_map (compat_shard_map: the one
    # toolchain shim — this container's jax has no jax.shard_map)
    import jax as _j
    from jax.sharding import Mesh, PartitionSpec as P

    from pampi_tpu.parallel.comm import compat_shard_map

    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("j", "i"))

    def kern(pd, rd):
        cm = ca_masks(jl, il, H, jmax, imax, jnp.float64)
        om = obst.deep_obstacle_masks(m, jl, il, H)
        return obst.ca_rb_iters_obstacle(
            pd, rd, n, cm, om, 1.0 / (dx * dx), 1.0 / (dy * dy)
        )

    t_p, t_r = _j.jit(compat_shard_map(
        kern, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
        check_vma=False,
    ))(pd, rd)
    np.testing.assert_array_equal(np.asarray(k_p), np.asarray(t_p))
    np.testing.assert_allclose(float(k_r), float(t_r), rtol=1e-12)


def test_obsdist_depth_backoff_keeps_pallas(monkeypatch):
    """VMEM infeasibility at deep n must back the depth off (halving) and
    keep the pallas kernel, not fall to jnp: a shallower kernel beats the
    jnp CA path at any depth (round-4 anchor: n=16 OOMs Mosaic's unrolled-
    sweep stack at a 512x2048 shard while n=8 runs at 22.9G)."""
    from pampi_tpu.ops import obstacle as obst
    from pampi_tpu.ops import sor_obsdist as so
    from pampi_tpu.parallel.comm import CartComm
    from pampi_tpu.utils import dispatch

    real = so.make_rb_iters_obsdist

    def shallow_only(jmax, imax, jl, il, n, *a, **k):
        if n > 2:
            raise ValueError("forced infeasible at deep n")
        return real(jmax, imax, jl, il, n, *a, **k)

    monkeypatch.setattr(so, "make_rb_iters_obsdist", shallow_only)

    imax = jmax = 32
    dx = dy = 1.0 / 32
    fluid = obst.build_fluid(imax, jmax, dx, dy, "0.3,0.3,0.6,0.6")
    m = obst.make_masks(fluid, dx, dy, 1.7, jnp.float64)
    comm = CartComm(ndims=2, dims=(1, 1))
    solve, used = obst.make_dist_obstacle_solver(
        comm, imax, jmax, jmax, imax, dx, dy, 1e-12, 8, m, jnp.float64,
        ca_n=8, sor_inner=8, backend="pallas",
    )
    assert used
    assert dispatch.last("obstacle_dist") == "pallas ca2"


def test_obsdist_windowed_sweeps_bitwise():
    """rb_inner_sweeps(loop=True) — the scf.for sweep windowing — is
    bitwise-equal to the unrolled form (same per-sweep op sequence).
    Round-5 outcome (VERDICT r4 item 7): the looped kernel is an EXPLICIT
    opt-in only — it crashes the production Mosaic compiler at any depth
    on the current toolchain (documented in make_rb_iters_obsdist), so
    auto mode keeps the unrolled form + depth backoff; this test pins the
    windowed variant's correctness for when the toolchain allows it."""
    from pampi_tpu.ops import obstacle as obst
    from pampi_tpu.ops import sor_obsdist as so
    from pampi_tpu.ops import sor_pallas as sp

    imax, jmax = 64, 32
    dx, dy = 16.0 / imax, 4.0 / jmax
    fluid = obst.build_fluid(imax, jmax, dx, dy, "6.0,1.5,10.0,2.5")
    m = obst.make_masks(fluid, dx, dy, 1.7, jnp.float64)
    jl, il = jmax, imax
    n = 4
    H = 2 * n

    def build(loop):
        return so.make_rb_iters_obsdist(
            jmax, imax, jl, il, n, dx, dy, 1.7, jnp.float64,
            interpret=True, loop_sweeps=loop,
        )

    rb_u, br, h = build(False)
    rb_l, br2, h2 = build(True)
    assert (br, h) == (br2, h2)

    rng = np.random.default_rng(9)
    pd = jnp.asarray(rng.standard_normal((jl + 2 * H, il + 2 * H)))
    rd = jnp.asarray(rng.standard_normal((jl + 2 * H, il + 2 * H)))
    flg = sp.pad_array(
        jnp.pad(m.fluid, [(H - 1, H - 1)] * 2).astype(jnp.float64), br, h)
    offs = jnp.asarray([0, 0], jnp.int32)
    pu, ru = rb_u(offs, sp.pad_array(pd, br, h), sp.pad_array(rd, br, h), flg)
    plp, rl = rb_l(offs, sp.pad_array(pd, br, h), sp.pad_array(rd, br, h), flg)
    np.testing.assert_array_equal(np.asarray(pu), np.asarray(plp))
    np.testing.assert_array_equal(float(ru), float(rl))
