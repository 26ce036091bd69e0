"""Seeded initial fields: the published zero initial state plus a small,
smooth, exactly divergence-free perturbation of the velocity.

The perturbation is the discrete curl of a stream function
psi(x, y[, z]) = A * sum_{m,n} a_mn sin(m pi x) sin(n pi y) [sin(l pi z)]
sampled on the cell corners (z at cell centres), so that the staggered
divergence of (u, v) is zero cell by cell and the normal velocity is zero
on every wall. The coefficients a_mn come from the seed; A scales the
largest perturbed velocity to `amplitude` (a share of the lid speed). w
and p start at zero. Every seed gives the same grid, the same mode set
and the same amplitude, so the work a replay does depends on the seed only
through the flow it produces.
"""

from __future__ import annotations

import numpy as np


def coefficients(seed: int, modes: int) -> np.ndarray:
    """The (modes, modes) mode weights of a seed, drawn on the host (a
    handful of numbers; any non-negative integer seed is valid)."""
    rng = np.random.default_rng(int(seed))
    a = rng.standard_normal((modes, modes))
    m = np.arange(1, modes + 1)
    return (a / (m[:, None] ** 2 + m[None, :] ** 2)).astype(np.float32)


def make_fields(par: dict, coef, amplitude: float):
    """(u, v[, w], p) as float32 device arrays in the reference layout,
    built in one jitted call from the mode weights."""
    import jax
    import jax.numpy as jnp

    three_d = par["name"].endswith("3d")
    imax, jmax = par["imax"], par["jmax"]
    kmax = par.get("kmax", 1) if three_d else None
    modes = coef.shape[0]

    def build(coef):
        k = jnp.arange(1, modes + 1, dtype=jnp.float32) * jnp.pi
        x = jnp.arange(imax + 1, dtype=jnp.float32) / imax   # corners
        y = jnp.arange(jmax + 1, dtype=jnp.float32) / jmax
        sx = jnp.sin(k[:, None] * x[None, :])                # (m, i)
        sy = jnp.sin(k[:, None] * y[None, :])                # (n, j)
        # psi[j, i] = sum_mn coef[m, n] sx[m, i] sy[n, j]
        psi = jnp.einsum("mn,mi,nj->ji", coef, sx, sy,
                         precision=jax.lax.Precision.HIGHEST)
        dx, dy = par["xlength"] / imax, par["ylength"] / jmax
        if three_d:
            zc = (jnp.arange(1, kmax + 1, dtype=jnp.float32) - 0.5) / kmax
            zprof = jnp.sin(jnp.pi * zc)                    # (k,)
            psi = zprof[:, None, None] * psi[None]          # (k, j, i)
            shape = (kmax + 2, jmax + 2, imax + 2)
            u = jnp.zeros(shape, jnp.float32)
            v = jnp.zeros(shape, jnp.float32)
            # u[k, j, i] = (psi[j, i] - psi[j-1, i]) / dy, j = 1..jmax
            u = u.at[1:-1, 1:-1, :-1].set((psi[:, 1:, :] - psi[:, :-1, :]) / dy)
            v = v.at[1:-1, :-1, 1:-1].set(-(psi[:, :, 1:] - psi[:, :, :-1]) / dx)
            scale = amplitude / jnp.maximum(
                jnp.max(jnp.abs(u)), jnp.max(jnp.abs(v)))
            w = jnp.zeros(shape, jnp.float32)
            return u * scale, v * scale, w, jnp.zeros(shape, jnp.float32)
        shape = (jmax + 2, imax + 2)
        u = jnp.zeros(shape, jnp.float32)
        v = jnp.zeros(shape, jnp.float32)
        u = u.at[1:-1, :-1].set((psi[1:, :] - psi[:-1, :]) / dy)
        v = v.at[:-1, 1:-1].set(-(psi[:, 1:] - psi[:, :-1]) / dx)
        scale = amplitude / jnp.maximum(jnp.max(jnp.abs(u)),
                                        jnp.max(jnp.abs(v)))
        return u * scale, v * scale, jnp.zeros(shape, jnp.float32)

    return jax.jit(build)(jnp.asarray(coef))
