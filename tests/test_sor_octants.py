"""Octant-decomposition 3-D SOR layout (ops/sor_octants.py + the Pallas
kernel in ops/sor3d_pallas.py): bijection, oracle vs the masked 3-D
reference path, kernel vs oracle (interpret, incl. multi-block), and the
make_pressure_solve_3d layout dispatch. Tolerances: see
tests/test_sor_quarters.py — ulp-level equality across layouts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pampi_tpu.models.ns3d import (
    checkerboard_mask_3d,
    make_pressure_solve_3d,
    neumann_faces_3d,
    sor_coefficients_3d,
    sor_pass_3d,
)
from pampi_tpu.ops import sor3d_pallas as sp3
from pampi_tpu.ops.sor_octants import (
    pack_octants,
    rb_iter_octants,
    unpack_octants,
)


def _rand(shape, dtype, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=shape), dtype)


def test_pack_unpack_roundtrip():
    p = _rand((10, 14, 18), jnp.float64)
    np.testing.assert_array_equal(
        np.asarray(unpack_octants(pack_octants(p))), np.asarray(p)
    )


@pytest.mark.parametrize("km,jm,im", [(8, 8, 8), (16, 8, 12), (12, 16, 8)])
def test_oracle_matches_masked_path_f64(km, jm, im):
    """f64 octant oracle vs the masked 3-D reference composition
    (sor_pass_3d odd→even + neumann_faces_3d) over 4 iterations."""
    shape = (km + 2, jm + 2, im + 2)
    p, rhs = _rand(shape, jnp.float64, 1), _rand(shape, jnp.float64, 2)
    dx, dy, dz = 1.0 / im, 1.0 / jm, 1.0 / km
    factor, idx2, idy2, idz2 = sor_coefficients_3d(dx, dy, dz, 1.7)
    odd = checkerboard_mask_3d(km, jm, im, 1, jnp.float64)
    even = checkerboard_mask_3d(km, jm, im, 0, jnp.float64)
    pj = p
    for _ in range(4):
        pj, r0 = sor_pass_3d(pj, rhs, odd, factor, idx2, idy2, idz2)
        pj, r1 = sor_pass_3d(pj, rhs, even, factor, idx2, idy2, idz2)
        pj = neumann_faces_3d(pj)
    q, qr = pack_octants(p), pack_octants(rhs)
    for _ in range(4):
        q, rsq = rb_iter_octants(q, qr, factor, idx2, idy2, idz2)
    np.testing.assert_allclose(
        np.asarray(unpack_octants(q)), np.asarray(pj), rtol=0, atol=1e-13
    )
    assert float(rsq) == pytest.approx(float(r0 + r1), rel=1e-10)


@pytest.mark.parametrize("km,jm,im,k,bko", [
    (8, 8, 8, 1, None), (8, 8, 8, 3, None),
    (12, 10, 8, 4, 2), (22, 14, 14, 2, 4),  # multi-block (tail: 22%4=2)
])
def test_kernel_matches_oracle(km, jm, im, k, bko):
    shape = (km + 2, jm + 2, im + 2)
    p, rhs = _rand(shape, jnp.float32, 3), _rand(shape, jnp.float32, 4)
    dx, dy, dz = 1.0 / im, 1.0 / jm, 1.0 / km
    factor, idx2, idy2, idz2 = sor_coefficients_3d(dx, dy, dz, 1.7)
    rb, bk, h = sp3.make_rb_iter_tblock_3d_octants(
        im, jm, km, dx, dy, dz, 1.7, jnp.float32, n_inner=k, block_k=bko,
        interpret=True,
    )
    po, ro = sp3.pad_octants(p, bk, h), sp3.pad_octants(rhs, bk, h)
    po, rsq = rb(po, ro)
    out = sp3.unpad_octants(po, km, jm, im, h)
    q, qr = pack_octants(p), pack_octants(rhs)
    for _ in range(k):
        q, osq = rb_iter_octants(q, qr, factor, idx2, idy2, idz2)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(unpack_octants(q)), rtol=0, atol=2e-5
    )
    assert float(rsq) == pytest.approx(float(osq), rel=1e-4)


def test_pressure_solve_octants_matches_jnp():
    """layout='octants' forced through make_pressure_solve_3d (interpret on
    CPU, backend='pallas') vs the jnp masked solve: same iteration count,
    converged fields at ulp-sum tolerance."""
    km = jm = im = 12
    dx = 1.0 / im
    p = jnp.zeros((km + 2, jm + 2, im + 2), jnp.float32)
    rhs = _rand(p.shape, jnp.float32, 5)
    solve_o = jax.jit(make_pressure_solve_3d(
        im, jm, km, dx, dx, dx, 1.7, 0.0, 20, jnp.float32,
        backend="pallas", n_inner=2, layout="octants",
    ))
    solve_j = jax.jit(make_pressure_solve_3d(
        im, jm, km, dx, dx, dx, 1.7, 0.0, 20, jnp.float32,
        backend="jnp", n_inner=1, layout="checkerboard",
    ))
    po, res_o, it_o = solve_o(p, rhs)
    pj, res_j, it_j = solve_j(p, rhs)
    assert int(it_o) == int(it_j) == 20
    np.testing.assert_allclose(np.asarray(po), np.asarray(pj), rtol=0,
                               atol=1e-4)
    assert float(res_o) == pytest.approx(float(res_j), rel=1e-3)


def test_octants_rejects_odd_dims():
    with pytest.raises(ValueError, match="even"):
        make_pressure_solve_3d(
            15, 16, 16, 1 / 15, 1 / 16, 1 / 16, 1.7, 1e-3, 10, jnp.float32,
            backend="pallas", layout="octants",
        )


@pytest.mark.parametrize("km,jm,im,k,bko", [
    (22, 14, 14, 2, 2),  # six blocks as thin as the halo
    (22, 14, 14, 3, 4),  # three blocks
])
def test_kernel_in_place_loop_matches_oracle(km, jm, im, k, bko):
    """The stack aliased through the kernel and carried by a jitted
    fori_loop of 3 calls, each block's lower halo the band the block below
    stores, vs 3·k oracle iterations. Interpret mode performs the DMAs in
    issue order, so it cannot show a load racing a store; on the chip
    the benchmark's `correct` check is the guard."""
    shape = (km + 2, jm + 2, im + 2)
    p, rhs = _rand(shape, jnp.float32, 6), _rand(shape, jnp.float32, 7)
    dx, dy, dz = 1.0 / im, 1.0 / jm, 1.0 / km
    factor, idx2, idy2, idz2 = sor_coefficients_3d(dx, dy, dz, 1.7)
    rb, bk, h = sp3.make_rb_iter_tblock_3d_octants(
        im, jm, km, dx, dy, dz, 1.7, jnp.float32, n_inner=k, block_k=bko,
        interpret=True,
    )
    assert -(-((km + 2) // 2) // bk) >= 3

    @jax.jit
    def loop(po, ro):
        return jax.lax.fori_loop(
            0, 3, lambda _t, c: rb(c[0], ro), (po, jnp.float32(0.0)))

    po, rsq = loop(sp3.pad_octants(p, bk, h), sp3.pad_octants(rhs, bk, h))
    out = sp3.unpad_octants(po, km, jm, im, h)
    q, qr = pack_octants(p), pack_octants(rhs)
    for _ in range(3 * k):
        q, osq = rb_iter_octants(q, qr, factor, idx2, idy2, idz2)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(unpack_octants(q)), rtol=0, atol=2e-5
    )
    assert float(rsq) == pytest.approx(float(osq), rel=1e-4)
