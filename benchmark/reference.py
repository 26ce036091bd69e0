"""Plain jax.numpy reference of the lid-driven cavity, 2-D and 3-D.

The yardstick that decides `correct`. It follows the reference C code of
PAMPI assignments 5 and 6 (solver.c: computeTimestep, setBoundaryConditions,
setSpecialBoundaryCondition, computeFG, computeRHS, normalizePressure,
solve, adaptUV) and imports nothing of the program under test: every
formula below is written out here, so that a change to the program cannot
move it.

Arrays are (jmax+2, imax+2) in 2-D and (kmax+2, jmax+2, imax+2) in 3-D,
ghost ring included; u lives on east faces, v on north faces, w on back
faces, p at cell centres.

The pressure solve is red-black SOR run as the configuration states it:
`inner` red-black iterations per convergence check, the check reading the
residual of the last of them. `flat` runs exactly ceil(itermax/inner)
checks with no early exit. Fields are stored in `dtype`; the residual sum
and the loop time are kept in float32 whatever the storage.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

NOSLIP, SLIP, OUTFLOW = 1, 2, 3


# ---------------------------------------------------------------- 2-D ---

def _bc_2d(u, v, left, right, bottom, top):
    """Wall boundary conditions (setBoundaryConditions); periodic is a
    no-op, as in the reference."""
    if left == NOSLIP:
        u = u.at[1:-1, 0].set(0.0)
        v = v.at[1:-1, 0].set(-v[1:-1, 1])
    elif left == SLIP:
        u = u.at[1:-1, 0].set(0.0)
        v = v.at[1:-1, 0].set(v[1:-1, 1])
    elif left == OUTFLOW:
        u = u.at[1:-1, 0].set(u[1:-1, 1])
        v = v.at[1:-1, 0].set(v[1:-1, 1])
    if right == NOSLIP:
        u = u.at[1:-1, -2].set(0.0)
        v = v.at[1:-1, -1].set(-v[1:-1, -2])
    elif right == SLIP:
        u = u.at[1:-1, -2].set(0.0)
        v = v.at[1:-1, -1].set(v[1:-1, -2])
    elif right == OUTFLOW:
        u = u.at[1:-1, -2].set(u[1:-1, -3])
        v = v.at[1:-1, -1].set(v[1:-1, -2])
    if bottom == NOSLIP:
        v = v.at[0, 1:-1].set(0.0)
        u = u.at[0, 1:-1].set(-u[1, 1:-1])
    elif bottom == SLIP:
        v = v.at[0, 1:-1].set(0.0)
        u = u.at[0, 1:-1].set(u[1, 1:-1])
    elif bottom == OUTFLOW:
        u = u.at[0, 1:-1].set(u[1, 1:-1])
        v = v.at[0, 1:-1].set(v[1, 1:-1])
    if top == NOSLIP:
        v = v.at[-2, 1:-1].set(0.0)
        u = u.at[-1, 1:-1].set(-u[-2, 1:-1])
    elif top == SLIP:
        v = v.at[-2, 1:-1].set(0.0)
        u = u.at[-1, 1:-1].set(u[-2, 1:-1])
    elif top == OUTFLOW:
        u = u.at[-1, 1:-1].set(u[-2, 1:-1])
        v = v.at[-2, 1:-1].set(v[-3, 1:-1])
    return u, v


def _fg_2d(u, v, dt, re, gx, gy, gamma, dx, dy):
    """Momentum predictor F, G (computeFG): central plus gamma-blended
    donor-cell convection, viscous Laplacian, body force; F = U and G = V
    on the walls."""
    idx, idy = 1.0 / dx, 1.0 / dy
    c = u[1:-1, 1:-1]
    e, w_ = u[1:-1, 2:], u[1:-1, :-2]
    n, s = u[2:, 1:-1], u[:-2, 1:-1]
    vc, ve = v[1:-1, 1:-1], v[1:-1, 2:]
    vs, vse = v[:-2, 1:-1], v[:-2, 2:]
    du2dx = idx * 0.25 * ((c + e) * (c + e) - (c + w_) * (c + w_)) \
        + gamma * idx * 0.25 * (jnp.abs(c + e) * (c - e)
                                + jnp.abs(c + w_) * (c - w_))
    duvdy = idy * 0.25 * ((vc + ve) * (c + n) - (vs + vse) * (c + s)) \
        + gamma * idy * 0.25 * (jnp.abs(vc + ve) * (c - n)
                                + jnp.abs(vs + vse) * (c - s))
    lap_u = idx * idx * (e - 2.0 * c + w_) + idy * idy * (n - 2.0 * c + s)
    f_in = c + dt * ((1.0 / re) * lap_u - du2dx - duvdy + gx)

    vn, vw = v[2:, 1:-1], v[1:-1, :-2]
    un, uw, unw = u[2:, 1:-1], u[1:-1, :-2], u[2:, :-2]
    duvdx = idx * 0.25 * ((c + un) * (vc + ve) - (uw + unw) * (vc + vw)) \
        + gamma * idx * 0.25 * (jnp.abs(c + un) * (vc - ve)
                                + jnp.abs(uw + unw) * (vc - vw))
    dv2dy = idy * 0.25 * ((vc + vn) * (vc + vn) - (vc + vs) * (vc + vs)) \
        + gamma * idy * 0.25 * (jnp.abs(vc + vn) * (vc - vn)
                                + jnp.abs(vc + vs) * (vc - vs))
    lap_v = idx * idx * (ve - 2.0 * vc + vw) + idy * idy * (vn - 2.0 * vc + vs)
    g_in = vc + dt * ((1.0 / re) * lap_v - duvdx - dv2dy + gy)

    f = jnp.zeros_like(u).at[1:-1, 1:-1].set(f_in)
    g = jnp.zeros_like(v).at[1:-1, 1:-1].set(g_in)
    f = f.at[1:-1, 0].set(u[1:-1, 0]).at[1:-1, -2].set(u[1:-1, -2])
    g = g.at[0, 1:-1].set(v[0, 1:-1]).at[-2, 1:-1].set(v[-2, 1:-1])
    return f, g


def _rhs_2d(f, g, dt, dx, dy):
    """Poisson right-hand side div(F, G)/dt (computeRHS)."""
    r = ((f[1:-1, 1:-1] - f[1:-1, :-2]) / dx
         + (g[1:-1, 1:-1] - g[:-2, 1:-1]) / dy) * (1.0 / dt)
    return jnp.zeros_like(f).at[1:-1, 1:-1].set(r)


def _adapt_2d(u, v, f, g, p, dt, dx, dy):
    """Projection (adaptUV)."""
    pc = p[1:-1, 1:-1]
    u = u.at[1:-1, 1:-1].set(f[1:-1, 1:-1] - (p[1:-1, 2:] - pc) * (dt / dx))
    v = v.at[1:-1, 1:-1].set(g[1:-1, 1:-1] - (p[2:, 1:-1] - pc) * (dt / dy))
    return u, v


def _parity_2d(jmax, imax, parity):
    jj = jnp.arange(1, jmax + 1)[:, None]
    ii = jnp.arange(1, imax + 1)[None, :]
    return ((ii + jj) % 2) == parity


def _sor_iteration_2d(p, rhs, red, black, factor, idx2, idy2, norm):
    """One red-black iteration (red = (i+j) even first, black seeing red's
    update), the Neumann ghost copy, and the normalised squared residual
    summed in float32."""
    rsq = jnp.float32(0.0)
    for mask in (red, black):
        c = p[1:-1, 1:-1]
        lap = (p[1:-1, 2:] - 2.0 * c + p[1:-1, :-2]) * idx2 \
            + (p[2:, 1:-1] - 2.0 * c + p[:-2, 1:-1]) * idy2
        r = jnp.where(mask, rhs[1:-1, 1:-1] - lap, 0.0).astype(p.dtype)
        p = p.at[1:-1, 1:-1].add(-factor * r)
        r32 = r.astype(jnp.float32)
        rsq = rsq + jnp.sum(r32 * r32)
    p = p.at[0, 1:-1].set(p[1, 1:-1]).at[-1, 1:-1].set(p[-2, 1:-1])
    p = p.at[1:-1, 0].set(p[1:-1, 1]).at[1:-1, -1].set(p[1:-1, -2])
    return p, rsq / norm


# ---------------------------------------------------------------- 3-D ---

def _sh(a, dk=0, dj=0, di=0):
    K, J, I = a.shape
    return a[1 + dk:K - 1 + dk, 1 + dj:J - 1 + dj, 1 + di:I - 1 + di]


_FACES = (("top", 1, "hi"), ("bottom", 1, "lo"), ("left", 2, "lo"),
          ("right", 2, "hi"), ("front", 0, "lo"), ("back", 0, "hi"))


def _plane(axis, pos):
    idx = [slice(1, -1)] * 3
    idx[axis] = pos
    return tuple(idx)


def _bc_3d(u, v, w, kinds):
    """Six-face boundary conditions in the reference's order (top, bottom,
    left, right, front, back); `kinds` maps face -> kind."""
    fields = {0: w, 1: v, 2: u}
    for face, axis, side in _FACES:
        kind = kinds[face]
        ghost_pos, wall_pos, step = (0, 0, 1) if side == "lo" else (-1, -2, -1)
        ghost, ghost_in = _plane(axis, ghost_pos), _plane(axis, ghost_pos + step)
        wall, wall_in = _plane(axis, wall_pos), _plane(axis, wall_pos + step)
        normal = fields[axis]
        tang = [a for a in (0, 1, 2) if a != axis]
        if kind in (NOSLIP, SLIP):
            fields[axis] = normal.at[wall].set(0.0)
            sign = -1.0 if kind == NOSLIP else 1.0
            for a in tang:
                fields[a] = fields[a].at[ghost].set(sign * fields[a][ghost_in])
        elif kind == OUTFLOW:
            fields[axis] = normal.at[wall].set(normal[wall_in])
            for a in tang:
                fields[a] = fields[a].at[ghost].set(fields[a][ghost_in])
    return fields[2], fields[1], fields[0]


def _fgh_3d(u, v, w, dt, re, gx, gy, gz, gamma, dx, dy, dz):
    """3-D momentum predictor (computeFG, assignment 6), with the
    reference's own dvwdz, which uses V(i,j,k+1) in both halves."""
    idx, idy, idz = 1.0 / dx, 1.0 / dy, 1.0 / dz
    ab = jnp.abs
    uc, vc, wc = _sh(u), _sh(v), _sh(w)
    u_ip, u_im = _sh(u, di=1), _sh(u, di=-1)
    u_jp, u_jm = _sh(u, dj=1), _sh(u, dj=-1)
    u_kp, u_km = _sh(u, dk=1), _sh(u, dk=-1)
    v_ip, v_im = _sh(v, di=1), _sh(v, di=-1)
    v_jp, v_jm = _sh(v, dj=1), _sh(v, dj=-1)
    v_kp = _sh(v, dk=1)
    v_km = _sh(v, dk=-1)
    w_ip, w_im = _sh(w, di=1), _sh(w, di=-1)
    w_jp, w_jm = _sh(w, dj=1), _sh(w, dj=-1)
    w_kp, w_km = _sh(w, dk=1), _sh(w, dk=-1)
    u_im_jp, u_im_kp = _sh(u, dj=1, di=-1), _sh(u, dk=1, di=-1)
    v_jm_ip, v_jm_kp = _sh(v, dj=-1, di=1), _sh(v, dk=1, dj=-1)
    w_km_ip, w_km_jp = _sh(w, dk=-1, di=1), _sh(w, dk=-1, dj=1)

    def flux(h, a, b, c, d, e, f):
        # h/4 * ((a)(b) - (c)(d)) + gamma h/4 (|a|(e) + |c|(f))
        return h * 0.25 * (a * b - c * d) + gamma * h * 0.25 * (
            ab(a) * e + ab(c) * f)

    du2dx = flux(idx, uc + u_ip, uc + u_ip, uc + u_im, uc + u_im,
                 uc - u_ip, uc - u_im)
    duvdy = flux(idy, vc + v_ip, uc + u_jp, v_jm + v_jm_ip, uc + u_jm,
                 uc - u_jp, uc - u_jm)
    duwdz = flux(idz, wc + w_ip, uc + u_kp, w_km + w_km_ip, uc + u_km,
                 uc - u_kp, uc - u_km)
    lap_u = (idx * idx * (u_ip - 2.0 * uc + u_im)
             + idy * idy * (u_jp - 2.0 * uc + u_jm)
             + idz * idz * (u_kp - 2.0 * uc + u_km))
    f_in = uc + dt * ((1.0 / re) * lap_u - du2dx - duvdy - duwdz + gx)

    duvdx = flux(idx, uc + u_jp, vc + v_ip, u_im + u_im_jp, vc + v_im,
                 vc - v_ip, vc - v_im)
    dv2dy = flux(idy, vc + v_jp, vc + v_jp, vc + v_jm, vc + v_jm,
                 vc - v_jp, vc - v_jm)
    dvwdz = flux(idz, wc + w_jp, vc + v_kp, w_km + w_km_jp, vc + v_kp,
                 vc - v_kp, vc - v_kp)
    lap_v = (idx * idx * (v_ip - 2.0 * vc + v_im)
             + idy * idy * (v_jp - 2.0 * vc + v_jm)
             + idz * idz * (v_kp - 2.0 * vc + v_km))
    g_in = vc + dt * ((1.0 / re) * lap_v - duvdx - dv2dy - dvwdz + gy)

    duwdx = flux(idx, uc + u_kp, wc + w_ip, u_im + u_im_kp, wc + w_im,
                 wc - w_ip, wc - w_im)
    dvwdy = flux(idy, vc + v_kp, wc + w_jp, v_jm_kp + v_jm, wc + w_jm,
                 wc - w_jp, wc - w_jm)
    dw2dz = flux(idz, wc + w_kp, wc + w_kp, wc + w_km, wc + w_km,
                 wc - w_kp, wc - w_km)
    lap_w = (idx * idx * (w_ip - 2.0 * wc + w_im)
             + idy * idy * (w_jp - 2.0 * wc + w_jm)
             + idz * idz * (w_kp - 2.0 * wc + w_km))
    h_in = wc + dt * ((1.0 / re) * lap_w - duwdx - dvwdy - dw2dz + gz)

    inner = (slice(1, -1),) * 3
    f = jnp.zeros_like(u).at[inner].set(f_in)
    g = jnp.zeros_like(v).at[inner].set(g_in)
    h = jnp.zeros_like(w).at[inner].set(h_in)
    f = f.at[1:-1, 1:-1, 0].set(u[1:-1, 1:-1, 0])
    f = f.at[1:-1, 1:-1, -2].set(u[1:-1, 1:-1, -2])
    g = g.at[1:-1, 0, 1:-1].set(v[1:-1, 0, 1:-1])
    g = g.at[1:-1, -2, 1:-1].set(v[1:-1, -2, 1:-1])
    h = h.at[0, 1:-1, 1:-1].set(w[0, 1:-1, 1:-1])
    h = h.at[-2, 1:-1, 1:-1].set(w[-2, 1:-1, 1:-1])
    return f, g, h


def _rhs_3d(f, g, h, dt, dx, dy, dz):
    r = ((_sh(f) - _sh(f, di=-1)) / dx + (_sh(g) - _sh(g, dj=-1)) / dy
         + (_sh(h) - _sh(h, dk=-1)) / dz) * (1.0 / dt)
    return jnp.zeros_like(f).at[1:-1, 1:-1, 1:-1].set(r)


def _adapt_3d(u, v, w, f, g, h, p, dt, dx, dy, dz):
    pc = _sh(p)
    inner = (slice(1, -1),) * 3
    u = u.at[inner].set(_sh(f) - (_sh(p, di=1) - pc) * (dt / dx))
    v = v.at[inner].set(_sh(g) - (_sh(p, dj=1) - pc) * (dt / dy))
    w = w.at[inner].set(_sh(h) - (_sh(p, dk=1) - pc) * (dt / dz))
    return u, v, w


def _parity_3d(kmax, jmax, imax, parity):
    kk = jnp.arange(1, kmax + 1)[:, None, None]
    jj = jnp.arange(1, jmax + 1)[None, :, None]
    ii = jnp.arange(1, imax + 1)[None, None, :]
    return ((ii + jj + kk) % 2) == parity


def _sor_iteration_3d(p, rhs, first, second, factor, idx2, idy2, idz2, norm):
    """One red-black iteration of the 7-point stencil: (i+j+k) odd first,
    then even (the reference's ksw/jsw/isw order), then the six Neumann
    face copies."""
    rsq = jnp.float32(0.0)
    for mask in (first, second):
        c = _sh(p)
        lap = ((_sh(p, di=1) - 2.0 * c + _sh(p, di=-1)) * idx2
               + (_sh(p, dj=1) - 2.0 * c + _sh(p, dj=-1)) * idy2
               + (_sh(p, dk=1) - 2.0 * c + _sh(p, dk=-1)) * idz2)
        r = jnp.where(mask, _sh(rhs) - lap, 0.0).astype(p.dtype)
        p = p.at[1:-1, 1:-1, 1:-1].add(-factor * r)
        r32 = r.astype(jnp.float32)
        rsq = rsq + jnp.sum(r32 * r32)
    p = p.at[0, 1:-1, 1:-1].set(p[1, 1:-1, 1:-1])
    p = p.at[-1, 1:-1, 1:-1].set(p[-2, 1:-1, 1:-1])
    p = p.at[1:-1, 0, 1:-1].set(p[1:-1, 1, 1:-1])
    p = p.at[1:-1, -1, 1:-1].set(p[1:-1, -2, 1:-1])
    p = p.at[1:-1, 1:-1, 0].set(p[1:-1, 1:-1, 1])
    p = p.at[1:-1, 1:-1, -1].set(p[1:-1, 1:-1, -2])
    return p, rsq / norm


# -------------------------------------------------------------- drive ---

def _solve_loop(iteration, p, rhs, eps, itermax, inner, flat):
    """Convergence loop: `inner` iterations per check, the check reading
    the last iteration's residual."""
    def trip(p):
        res = jnp.float32(0.0)
        for _ in range(inner):
            p, res = iteration(p, rhs)
        return p, res

    if flat:
        def body(_k, c):
            p, _res = c
            return trip(p)

        p, _ = lax.fori_loop(0, -(-itermax // inner), body,
                             (p, jnp.float32(1.0)))
        return p

    epssq = jnp.float32(eps * eps)

    def cond(c):
        _p, res, it = c
        return jnp.logical_and(res >= epssq, it < itermax)

    def body(c):
        p, _res, it = c
        p, res = trip(p)
        return p, res, it + inner

    p, _, _ = lax.while_loop(cond, body, (p, jnp.float32(1.0), jnp.int32(0)))
    return p


def make_segment(par: dict, dtype):
    """The whole replay segment as one function of the initial fields:
    (fields tuple) -> (fields tuple, steps). `par` holds the .par keys of
    the configuration as run."""
    three_d = par["name"].endswith("3d")
    re_, tau, gamma = par["re"], par["tau"], par["gamma"]
    te = par["te"]
    eps, itermax, omg = par["eps"], par["itermax"], par["omg"]
    inner = max(1, int(par.get("tpu_sor_inner", 4)))
    flat = bool(par.get("tpu_flat_solve", 0))
    imax, jmax = par["imax"], par["jmax"]
    dx, dy = par["xlength"] / imax, par["ylength"] / jmax
    if not three_d:
        dx2, dy2 = dx * dx, dy * dy
        factor = omg * 0.5 * (dx2 * dy2) / (dx2 + dy2)
        dt_bound = 0.5 * re_ / (1.0 / dx2 + 1.0 / dy2)
        red, black = _parity_2d(jmax, imax, 0), _parity_2d(jmax, imax, 1)
        norm = float(imax * jmax)

        def iteration(p, rhs):
            return _sor_iteration_2d(p, rhs, red, black, factor,
                                     1.0 / dx2, 1.0 / dy2, norm)

        def step(state):
            u, v, p, t, nt = state
            umax, vmax = jnp.max(jnp.abs(u)), jnp.max(jnp.abs(v))
            dt = jnp.minimum(dt_bound, jnp.minimum(
                jnp.where(umax > 0, dx / umax, jnp.inf).astype(dtype),
                jnp.where(vmax > 0, dy / vmax, jnp.inf).astype(dtype)))
            dt = (dt * tau).astype(dtype)
            u, v = _bc_2d(u, v, par["bcLeft"], par["bcRight"],
                          par["bcBottom"], par["bcTop"])
            u = u.at[-1, 1:-2].set(2.0 - u[-2, 1:-2])  # the lid
            f, g = _fg_2d(u, v, dt, re_, par["gx"], par["gy"], gamma, dx, dy)
            rhs = _rhs_2d(f, g, dt, dx, dy)
            p = lax.cond(nt % 100 == 0, lambda q: q - jnp.mean(q),
                         lambda q: q, p)
            p = _solve_loop(iteration, p, rhs, eps, itermax, inner, flat)
            u, v = _adapt_2d(u, v, f, g, p, dt, dx, dy)
            return u, v, p, t + dt.astype(jnp.float32), nt + 1
    else:
        kmax = par["kmax"]
        dz = par["zlength"] / kmax
        dx2, dy2, dz2 = dx * dx, dy * dy, dz * dz
        factor = omg * 0.5 * (dx2 * dy2 * dz2) / (
            dy2 * dz2 + dx2 * dz2 + dx2 * dy2)
        dt_bound = 0.5 * re_ / (1.0 / dx2 + 1.0 / dy2 + 1.0 / dz2)
        odd = _parity_3d(kmax, jmax, imax, 1)
        even = _parity_3d(kmax, jmax, imax, 0)
        norm = float(imax * jmax * kmax)
        kinds = {"top": par["bcTop"], "bottom": par["bcBottom"],
                 "left": par["bcLeft"], "right": par["bcRight"],
                 "front": par["bcFront"], "back": par["bcBack"]}

        def iteration(p, rhs):
            return _sor_iteration_3d(p, rhs, odd, even, factor, 1.0 / dx2,
                                     1.0 / dy2, 1.0 / dz2, norm)

        def step(state):
            u, v, w, p, t, nt = state
            m = [jnp.max(jnp.abs(a)) for a in (u, v, w)]
            lim = [jnp.where(mx > 0, h / mx, jnp.inf).astype(dtype)
                   for mx, h in zip(m, (dx, dy, dz))]
            dt = jnp.minimum(dt_bound, jnp.minimum(
                lim[0], jnp.minimum(lim[1], lim[2])))
            dt = (dt * tau).astype(dtype)
            u, v, w = _bc_3d(u, v, w, kinds)
            u = u.at[1:-2, -1, 1:-2].set(2.0 - u[1:-2, -2, 1:-2])  # the lid
            f, g, h = _fgh_3d(u, v, w, dt, re_, par["gx"], par["gy"],
                              par["gz"], gamma, dx, dy, dz)
            rhs = _rhs_3d(f, g, h, dt, dx, dy, dz)
            p = _solve_loop(iteration, p, rhs, eps, itermax, inner, flat)
            u, v, w = _adapt_3d(u, v, w, f, g, h, p, dt, dx, dy, dz)
            return u, v, w, p, t + dt.astype(jnp.float32), nt + 1

    def segment(fields):
        state = tuple(a.astype(dtype) for a in fields) + (
            jnp.float32(0.0), jnp.int32(0))
        t_index = len(fields)

        def cond(s):
            return s[t_index] <= te

        state = lax.while_loop(cond, step, state)
        return state[:t_index], state[t_index + 1]

    return segment


def run_segment(par: dict, fields: tuple, dtype=jnp.float32):
    """Run the reference segment on the default device; returns the final
    fields as float32 device arrays and the number of steps."""
    out, steps = jax.jit(make_segment(par, dtype))(tuple(fields))
    return tuple(a.astype(jnp.float32) for a in out), int(steps)


def check_covered(par: dict, name: str) -> None:
    """Refuse settings the reference does not cover: another case than
    `name`, another solver than red-black SOR, a fixed time step."""
    if par["name"] != name:
        raise ValueError(f"this reference covers the lid-driven cavity "
                         f"{name!r} only, not {par['name']!r}")
    if par.get("tpu_solver", "sor") != "sor":
        raise ValueError("the reference solves with red-black SOR only")
    if par.get("tau", 0.5) <= 0 or not math.isfinite(par["te"]):
        raise ValueError("the reference runs the adaptive time step only")
