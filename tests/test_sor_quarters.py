"""Quarter-decomposition SOR layout (ops/sor_quarters.py + the pallas
kernel in ops/sor_pallas.py): layout bijection, neighbour identities via
trajectory equality with the masked reference path, the kernel vs the jnp
oracle (interpret mode), and the make_rb_loop layout dispatch.

Tolerance note: the quarter layout keeps the reference's per-cell
association term-for-term, but XLA contracts multiply-adds differently for
differently-structured programs, so equality with the masked path is
ulp-level (f32: ~4e-7 on O(1) fields; f64: ~1e-15), not bitwise. The
checkerboard layout remains the bitwise-oracle mode (`tpu_sor_layout
checkerboard`)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pampi_tpu.models.poisson import init_fields, make_rb_step, make_rb_loop
from pampi_tpu.ops import sor_pallas as sp
from pampi_tpu.ops.sor_quarters import (
    pack_quarters,
    rb_iter_quarters,
    unpack_quarters,
)
from pampi_tpu.utils.params import Parameter


def _factor(im, jm, omega=1.9):
    dx, dy = 1.0 / im, 1.0 / jm
    dx2, dy2 = dx * dx, dy * dy
    return dx, dy, omega * 0.5 * (dx2 * dy2) / (dx2 + dy2), 1.0 / dx2, 1.0 / dy2


def test_pack_unpack_roundtrip():
    rng = np.random.default_rng(0)
    p = jnp.asarray(rng.normal(size=(18, 34)))
    q = pack_quarters(p)
    np.testing.assert_array_equal(np.asarray(unpack_quarters(*q)), np.asarray(p))


@pytest.mark.parametrize("jm,im", [(16, 16), (32, 16), (126, 126)])
def test_oracle_matches_masked_path_f64(jm, im):
    """f64 quarters oracle vs the masked jnp reference step over 5 full
    iterations: ulp-level (see module docstring)."""
    param = Parameter(imax=im, jmax=jm)
    p, rhs = init_fields(param, problem=2, dtype=jnp.float64)
    dx, dy, factor, idx2, idy2 = _factor(im, jm)
    step = make_rb_step(im, jm, dx, dy, 1.9, jnp.float64, backend="jnp")
    q, qr = pack_quarters(p), pack_quarters(rhs)
    it = jax.jit(lambda q, qr: rb_iter_quarters(q, qr, factor, idx2, idy2))
    pj = p
    for _ in range(5):
        pj, resj = step(pj, rhs)
        q, rsq = it(q, qr)
    np.testing.assert_allclose(
        np.asarray(unpack_quarters(*q)), np.asarray(pj), rtol=0, atol=1e-13
    )
    assert float(rsq) / (im * jm) == pytest.approx(float(resj), rel=1e-10)


@pytest.mark.parametrize("jm,im,k,brq", [
    (30, 30, 1, None), (30, 30, 3, None),
    (126, 62, 4, None), (62, 126, 2, None),
    (126, 126, 3, 16), (126, 126, 4, 8),  # multi-block
])
def test_kernel_matches_oracle(jm, im, k, brq):
    """The pallas quarters kernel (interpret mode) vs k applications of the
    jnp oracle."""
    param = Parameter(imax=im, jmax=jm)
    p, rhs = init_fields(param, problem=2, dtype=jnp.float32)
    dx, dy, factor, idx2, idy2 = _factor(im, jm)
    rb, brr, h = sp.make_rb_iter_tblock_quarters(
        im, jm, dx, dy, 1.9, jnp.float32, n_inner=k, block_rows_q=brq,
        interpret=True,
    )
    pq, rq = sp.pad_quarters(p, brr, h), sp.pad_quarters(rhs, brr, h)
    pq, rsq = rb(pq, rq)
    out = sp.unpad_quarters(pq, jm, im, h)

    q, qr = pack_quarters(p), pack_quarters(rhs)
    for _ in range(k):
        q, osq = rb_iter_quarters(q, qr, factor, idx2, idy2)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(unpack_quarters(*q)), rtol=0, atol=2e-6
    )
    assert float(rsq) == pytest.approx(float(osq), rel=1e-5)


def test_make_rb_loop_dispatches_quarters():
    """layout='quarters' + backend='pallas' (interpret on CPU): the solve
    loop carries the stacked layout and converges like the jnp path."""
    im = jm = 64
    dx, dy, factor, idx2, idy2 = _factor(im, jm)
    param = Parameter(imax=im, jmax=jm)
    p, rhs = init_fields(param, problem=2, dtype=jnp.float32)

    step_q, prep, post, eff = make_rb_loop(
        im, jm, dx, dy, 1.9, jnp.float32, backend="pallas", n_inner=2,
        layout="quarters",
    )
    assert eff == 2
    pq, rq = prep(p), prep(rhs)
    for _ in range(10):
        pq, res_q = step_q(pq, rq)
    out = post(pq)

    step_j = make_rb_step(im, jm, dx, dy, 1.9, jnp.float32, backend="jnp")
    pj = p
    for _ in range(20):
        pj, res_j = step_j(pj, rhs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(pj), rtol=0,
                               atol=1e-5)
    assert float(res_q) == pytest.approx(float(res_j), rel=1e-4)


def test_quarters_rejects_odd_dims():
    with pytest.raises(ValueError, match="even"):
        make_rb_loop(65, 64, 1 / 65, 1 / 64, 1.9, jnp.float32,
                     backend="pallas", layout="quarters")


def test_auto_layout_falls_back_on_odd_dims():
    """layout='auto' with odd dims must silently use the checkerboard
    kernel, not error."""
    step, prep, post, eff = make_rb_loop(
        66, 63, 1 / 66, 1 / 63, 1.9, jnp.float32, backend="pallas",
        n_inner=2, layout="auto",
    )
    param = Parameter(imax=66, jmax=63)
    p, rhs = init_fields(param, problem=2, dtype=jnp.float32)
    pp, res = step(prep(p), prep(rhs))
    assert post(pp).shape == p.shape and float(res) >= 0.0


@pytest.mark.parametrize("jm,im,k,brq", [
    (126, 126, 4, 8),   # eight blocks as thin as the halo
    (126, 126, 3, 16),  # four blocks
])
def test_kernel_in_place_loop_matches_oracle(jm, im, k, brq):
    """The stack aliased through the kernel and carried by a jitted
    fori_loop of 3 calls, each block's lower halo the band the block below
    stores, vs 3·k oracle iterations. Interpret mode performs the DMAs in
    issue order, so it cannot show a load racing a store; on the chip
    the benchmark's `correct` check is the guard."""
    param = Parameter(imax=im, jmax=jm)
    p, rhs = init_fields(param, problem=2, dtype=jnp.float32)
    dx, dy, factor, idx2, idy2 = _factor(im, jm)
    rb, brr, h = sp.make_rb_iter_tblock_quarters(
        im, jm, dx, dy, 1.9, jnp.float32, n_inner=k, block_rows_q=brq,
        interpret=True,
    )
    assert -(-((jm + 2) // 2) // brr) >= 3

    @jax.jit
    def loop(pq, rq):
        return jax.lax.fori_loop(
            0, 3, lambda _t, c: rb(c[0], rq), (pq, jnp.float32(0.0)))

    pq, rsq = loop(sp.pad_quarters(p, brr, h), sp.pad_quarters(rhs, brr, h))
    out = sp.unpad_quarters(pq, jm, im, h)

    q, qr = pack_quarters(p), pack_quarters(rhs)
    for _ in range(3 * k):
        q, osq = rb_iter_quarters(q, qr, factor, idx2, idy2)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(unpack_quarters(*q)), rtol=0, atol=2e-6
    )
    assert float(rsq) == pytest.approx(float(osq), rel=1e-5)
