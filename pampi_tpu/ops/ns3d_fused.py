"""Fused NS-3D step-phase Pallas kernels — the 3-D twin of ops/ns2d_fused.py.

Same motivation and equivalence policy as the 2-D module (launch-latency
amortization of the non-solve phase chain; copies/selects/maxes bitwise,
compound F/G/H / RHS / projection arithmetic ulp-equivalent via the SHARED
formula functions ops/ns3d.fgh_predictor_terms / rhs_terms_3d /
adapt_terms_3d with a roll-based window shift):

  PRE  (u, v, w, dt)  -> (u', v', w', F, G, H, rhs)
       6-face wall BCs -> special BC -> F/G/H predictor + wall fixups ->
       Poisson RHS
  POST (u', v', w', F, G, H, p, dt)
       -> (u'', v'', w'', max|u''|, max|v''|, max|w''|)
       projection adaptUV + the 3-D CFL max reduction

Layout: blocks along k (the untiled outermost axis — halo planes need no
alignment rounding), full padded (jp, ip) planes per k-slice
(sor3d_pallas.padded_ji tiling). `pad3`/`unpad3` convert at the chunk/step
boundary. All writes are gated by GLOBAL coordinates (offsets via scalar
prefetch), so the same kernels serve the single-device solver (offsets 0)
and the distributed twin (per-shard deep-halo blocks, depth FUSE_DEEP_HALO
exchange per step).

Obstacle flag fields compose branch-free exactly like the 2-D module: the
padded 0/1 fluid flag rides as a fourth input window and
u_face/v_face/w_face are derived in-kernel (integer-exact parity with
ops/obstacle3d.make_masks_3d including the ghost-plane wrap fixes), so the
3-D obstacle velocity BC (priority-ordered tangential mirrors), the F/G/H
face masks and the projection face masks are the same flag-multiply forms
the jnp path uses. Single-device callers bake the flag as a padded
constant (`fluid=<array>`); distributed callers pass `fluid=True` and
feed the per-shard global-constant slice at call time. Ragged shards are
the same kernels at uneven block bounds (global gating), with
POST(ragged=True) appending the live-mask multiply of the jnp ragged
chain (parallel/ragged3d.live_masks_3d).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import ns3d as ops3
from .ns2d_fused import (  # shared validity chain + overlap rim
    FUSE_CHAIN,
    FUSE_DEEP_HALO,
    FUSE_FOOTPRINT,
    OVERLAP_RIM,
)
from .sor_pallas import (
    LANE,
    VMEM_LIMIT_BYTES,
    CompilerParams,
    _align,
    _check_dtype,
    pltpu,
)

NOSLIP, SLIP, OUTFLOW, PERIODIC = 1, 2, 3, 4

__all__ = [
    "FUSE_CHAIN", "FUSE_DEEP_HALO", "FUSE_FOOTPRINT", "OVERLAP_RIM",
    "make_fused_pre_3d", "make_fused_post_3d", "make_fused_step_3d",
    "probe_fused_3d",
]


def _win_shift(a, dk=0, dj=0, di=0):
    """fgh_predictor_terms' `sh` contract on the VMEM window: roll so that
    out[x] = a[x + (dk, dj, di)] (identical neighbour values at every cell
    whose neighbours are real)."""
    out = a
    if dk:
        out = jnp.roll(out, -dk, axis=0)
    if dj:
        out = jnp.roll(out, -dj, axis=1)
    if di:
        out = jnp.roll(out, -di, axis=2)
    return out


def apply_wall_bcs_3d(u, v, w, gk, gj, gi, bcs, gkmax, gjmax, gimax):
    """set_boundary_conditions_3d as sequential global-coordinate-gated
    where-updates: same face order (the bcs dict's insertion order = the
    reference's application order), same written values. axes: 0=k, 1=j,
    2=i; normal component per axis {0: w, 1: v, 2: u}."""
    fields = {0: w, 1: v, 2: u}
    coords = {0: gk, 1: gj, 2: gi}
    gmaxes = {0: gkmax, 1: gjmax, 2: gimax}
    tans = {
        a: (coords[a] >= 1) & (coords[a] <= gmaxes[a]) for a in (0, 1, 2)
    }
    from .ns3d import FACES

    for face, kind in bcs.items():
        axis, side = FACES[face]
        g = coords[axis]
        t_axes = [a for a in (0, 1, 2) if a != axis]
        tan = tans[t_axes[0]] & tans[t_axes[1]]
        if side == "lo":
            ghost = (g == 0) & tan
            wall = (g == 0) & tan
            wall_in = -1  # read one plane inward: roll(x, -1, axis)
        else:
            ghost = (g == gmaxes[axis] + 1) & tan
            wall = (g == gmaxes[axis]) & tan
            wall_in = 1
        normal = fields[axis]
        zero = jnp.zeros((), normal.dtype)

        def inward(x, s=wall_in, a=axis):
            return jnp.roll(x, s, axis=a)

        if kind == NOSLIP:
            fields[axis] = jnp.where(wall, zero, normal)
            for a in t_axes:
                fields[a] = jnp.where(ghost, -inward(fields[a]), fields[a])
        elif kind == SLIP:
            fields[axis] = jnp.where(wall, zero, normal)
            for a in t_axes:
                fields[a] = jnp.where(ghost, inward(fields[a]), fields[a])
        elif kind == OUTFLOW:
            fields[axis] = jnp.where(wall, inward(normal), normal)
            for a in t_axes:
                fields[a] = jnp.where(ghost, inward(fields[a]), fields[a])
        elif kind == PERIODIC:
            pass
    return fields[2], fields[1], fields[0]


def apply_special_bc_3d(u, gk, gj, gi, problem, gkmax, gjmax, gimax):
    """set_special_bc_dcavity_3d / set_special_bc_canal_3d in gated-where
    form (incl. the reference's skip-last-interior-i-AND-k lid quirk)."""
    if problem == "dcavity":
        m = (
            (gj == gjmax + 1)
            & (gk >= 1) & (gk <= gkmax - 1)
            & (gi >= 1) & (gi <= gimax - 1)
        )
        u = jnp.where(m, 2.0 - jnp.roll(u, 1, axis=1), u)
    elif problem == "canal":
        m = (
            (gi == 0)
            & (gk >= 1) & (gk <= gkmax)
            & (gj >= 1) & (gj <= gjmax)
        )
        u = jnp.where(m, jnp.full((), 2.0, u.dtype), u)
    return u


def _obstacle_faces_3d(fl, gk, gj, gi, gkmax, gjmax, gimax, sh=_win_shift):
    """u/v/w_face derived from the 0/1 fluid flag window — integer-exact
    parity with ops/obstacle3d.make_masks_3d (incl. its ghost-plane
    wrap-fixes: the last global ghost column/row/plane is forced to a
    face). `sh` is the window's neighbour-shift contract."""
    one = jnp.ones((), fl.dtype)
    u_face = jnp.where(gi == gimax + 1, one, fl * sh(fl, 0, 0, 1))
    v_face = jnp.where(gj == gjmax + 1, one, fl * sh(fl, 0, 1, 0))
    w_face = jnp.where(gk == gkmax + 1, one, fl * sh(fl, 1, 0, 0))
    return u_face, v_face, w_face


def apply_obstacle_velocity_bc_3d_window(u, v, w, fl, u_face, v_face,
                                         w_face, sh=_win_shift):
    """ops/obstacle3d.apply_obstacle_velocity_bc_3d transcribed on the
    window: zero normal components on faces touching an obstacle, then the
    priority-ordered first-hit tangential mirror (`_mirror`) with `sh` as
    the neighbour read. Every wrapped read the full-array form relies on is
    multiplied by zero at the cells that could see window wrap (the ghost
    shell is always fluid), as in the 2-D transcription."""
    one = jnp.ones((), u.dtype)
    u = u * u_face
    v = v * v_face
    w = w * w_face

    def mirror(comp, both_obs, faces_and_vals):
        acc = jnp.zeros_like(comp)
        remaining = jnp.ones_like(comp)
        for fm, val in faces_and_vals:
            acc = acc + remaining * fm * (-val)
            remaining = remaining * (one - fm)
        return comp + both_obs * acc

    both_u = (one - fl) * (one - sh(fl, 0, 0, 1))
    u = mirror(u, both_u, [
        (sh(u_face, 0, 1, 0), sh(u, 0, 1, 0)),     # north (j+1)
        (sh(u_face, 0, -1, 0), sh(u, 0, -1, 0)),   # south (j-1)
        (sh(u_face, 1, 0, 0), sh(u, 1, 0, 0)),     # back  (k+1)
        (sh(u_face, -1, 0, 0), sh(u, -1, 0, 0)),   # front (k-1)
    ])
    both_v = (one - fl) * (one - sh(fl, 0, 1, 0))
    v = mirror(v, both_v, [
        (sh(v_face, 0, 0, 1), sh(v, 0, 0, 1)),     # east  (i+1)
        (sh(v_face, 0, 0, -1), sh(v, 0, 0, -1)),   # west  (i-1)
        (sh(v_face, 1, 0, 0), sh(v, 1, 0, 0)),     # back
        (sh(v_face, -1, 0, 0), sh(v, -1, 0, 0)),   # front
    ])
    both_w = (one - fl) * (one - sh(fl, 1, 0, 0))
    w = mirror(w, both_w, [
        (sh(w_face, 0, 0, 1), sh(w, 0, 0, 1)),     # east
        (sh(w_face, 0, 0, -1), sh(w, 0, 0, -1)),   # west
        (sh(w_face, 0, 1, 0), sh(w, 0, 1, 0)),     # north
        (sh(w_face, 0, -1, 0), sh(w, 0, -1, 0)),   # south
    ])
    return u, v, w


def _pre3_kernel(
    sref,    # SMEM scalar prefetch: int32[3] = (koff, joff, ioff)
    dt_ref,  # SMEM (1, 1)
    *refs,   # [u, v, w(, flg)] + [u', v', w', f, g, h, rhs] + scratch
    block_k: int,
    nblocks: int,
    gkmax: int,
    gjmax: int,
    gimax: int,
    lkmax: int,
    ljmax: int,
    limax: int,
    ext_pad: int,
    halo: int,
    bcs: tuple,      # tuple of (face, kind) — dict order preserved
    problem: str | None,
    re: float,
    gx: float,
    gy: float,
    gz: float,
    gamma: float,
    dx: float,
    dy: float,
    dz: float,
    masked: bool,
    bands: tuple | None = None,
    dynamic: bool = False,
):
    if dynamic:
        # shape-class mode (the 2-D _pre_kernel contract): live extents
        # and per-lane cell sizes as SMEM scalars after dt
        ext_ref, geo_ref, *refs = refs
    if masked:
        (u_in, v_in, w_in, flg, u_out, v_out, w_out, f_out, g_out, h_out,
         r_out, uw2, vw2, ww2, fw2, ob2, ld_sem, st_sem) = refs
    else:
        (u_in, v_in, w_in, u_out, v_out, w_out, f_out, g_out, h_out, r_out,
         uw2, vw2, ww2, ob2, ld_sem, st_sem) = refs
        flg = fw2 = None
    b = pl.program_id(0)
    bk = block_k
    h = halo
    slot = b % 2
    nslot = (b + 1) % 2
    koff = sref[0]
    joff = sref[1]
    ioff = sref[2]
    dt = dt_ref[0, 0]
    if dynamic:
        # single-device class lanes: local extents == global extents
        gkmax = ext_ref[0, 0]
        gjmax = ext_ref[0, 1]
        gimax = ext_ref[0, 2]
        lkmax, ljmax, limax = gkmax, gjmax, gimax
        dx = geo_ref[0, 0]
        dy = geo_ref[0, 1]
        dz = geo_ref[0, 2]

    # banded (grid-restricted) sweeps over the leading k axis — the 3-D
    # twin of the ns2d_fused band mapping (`tpu_overlap_restrict`); the
    # full-sweep default keeps the literal k*bk indexing (byte-identical
    # historical trace)
    if bands is None or (len(bands) == 1 and bands[0][0] == 0):
        def plane_of(k):
            return k * bk
    else:
        def plane_of(k):
            row, acc = None, 0
            for s, n in bands:
                r = s + (k - acc) * bk
                row = r if row is None else jnp.where(k >= acc, r, row)
                acc += n
            return row

    def load(k, s):
        r0 = plane_of(k)
        ins = [(u_in, uw2), (v_in, vw2), (w_in, ww2)]
        if masked:
            ins.append((flg, fw2))
        return [
            pltpu.make_async_copy(
                arr.at[pl.ds(r0, bk + 2 * h)], win.at[s],
                ld_sem.at[s, q])
            for q, (arr, win) in enumerate(ins)
        ]

    def store(k, s):
        r0 = plane_of(k)
        outs = (u_out, v_out, w_out, f_out, g_out, h_out, r_out)
        return [
            pltpu.make_async_copy(
                ob2.at[s, q], outs[q].at[pl.ds(h + r0, bk)],
                st_sem.at[s, q])
            for q in range(7)
        ]

    @pl.when(b == 0)
    def _():
        for c in load(0, 0):
            c.start()

    @pl.when(b + 1 < nblocks)
    def _():
        for c in load(b + 1, nslot):
            c.start()

    for c in load(b, slot):
        c.wait()

    u = uw2[slot]
    v = vw2[slot]
    w = ww2[slot]

    # window cell (wk, wj, wi): deep-block index a_k = plane_of(b)+wk-h,
    # global extended index gk = a_k - ext_pad + koff (and j/i likewise)
    a_k = plane_of(b) - h + jax.lax.broadcasted_iota(jnp.int32, u.shape, 0)
    a_j = jax.lax.broadcasted_iota(jnp.int32, u.shape, 1)
    a_i = jax.lax.broadcasted_iota(jnp.int32, u.shape, 2)
    gk = a_k - ext_pad + koff
    gj = a_j - ext_pad + joff
    gi = a_i - ext_pad + ioff

    # dead-cell-zero invariant on the loaded windows (ns2d_fused rationale:
    # the carried padded arrays' unstored halo/tail planes are undefined)
    ext_k = lkmax + 2 + 2 * ext_pad
    ext_j = ljmax + 2 + 2 * ext_pad
    ext_i = limax + 2 + 2 * ext_pad
    live_in = (
        (a_k >= 0) & (a_k < ext_k)
        & (a_j >= 0) & (a_j < ext_j)
        & (a_i >= 0) & (a_i < ext_i)
    )
    u = jnp.where(live_in, u, 0.0)
    v = jnp.where(live_in, v, 0.0)
    w = jnp.where(live_in, w, 0.0)

    u, v, w = apply_wall_bcs_3d(
        u, v, w, gk, gj, gi, dict(bcs), gkmax, gjmax, gimax
    )
    u = apply_special_bc_3d(u, gk, gj, gi, problem, gkmax, gjmax, gimax)
    if masked:
        fl = fw2[slot]
        u_face, v_face, w_face = _obstacle_faces_3d(
            fl, gk, gj, gi, gkmax, gjmax, gimax
        )
        u, v, w = apply_obstacle_velocity_bc_3d_window(
            u, v, w, fl, u_face, v_face, w_face
        )

    f_full, g_full, h_full = ops3.fgh_predictor_terms(
        u, v, w, dt, re, gx, gy, gz, gamma, dx, dy, dz, sh=_win_shift
    )
    interior = (
        (gk >= 1) & (gk <= gkmax)
        & (gj >= 1) & (gj <= gjmax)
        & (gi >= 1) & (gi <= gimax)
    )
    tan_k = (gk >= 1) & (gk <= gkmax)
    tan_j = (gj >= 1) & (gj <= gjmax)
    tan_i = (gi >= 1) & (gi <= gimax)
    f = jnp.where(interior, f_full, 0.0)
    g = jnp.where(interior, g_full, 0.0)
    hh = jnp.where(interior, h_full, 0.0)
    # wall fixups (apply_fgh_wall_fixups): F=U on left/right, G=V on
    # bottom/top, H=W on front/back walls
    f = jnp.where(((gi == 0) | (gi == gimax)) & tan_k & tan_j, u, f)
    g = jnp.where(((gj == 0) | (gj == gjmax)) & tan_k & tan_i, v, g)
    hh = jnp.where(((gk == 0) | (gk == gkmax)) & tan_j & tan_i, w, hh)
    if masked:
        # F/G/H carry U/V/W on non-fluid faces (obstacle3d.mask_fgh)
        one = jnp.ones((), u.dtype)
        f = u_face * f + (one - u_face) * u
        g = v_face * g + (one - v_face) * v
        hh = w_face * hh + (one - w_face) * w

    local_int = (
        (a_k >= ext_pad + 1) & (a_k <= ext_pad + lkmax)
        & (a_j >= ext_pad + 1) & (a_j <= ext_pad + ljmax)
        & (a_i >= ext_pad + 1) & (a_i <= ext_pad + limax)
    )
    rhs = jnp.where(
        interior & local_int,
        ops3.rhs_terms_3d(f, g, hh, dt, dx, dy, dz, sh=_win_shift),
        0.0,
    )

    @pl.when(b >= 2)
    def _():
        for c in store(b - 2, slot):
            c.wait()

    for q, arr in enumerate((u, v, w, f, g, hh, rhs)):
        ob2[slot, q] = arr[h: h + bk]
    for c in store(b, slot):
        c.start()

    @pl.when(b == nblocks - 1)
    def _():
        for c in store(b, slot):
            c.wait()
        if nblocks > 1:
            for c in store(b - 1, nslot):
                c.wait()


def _post3_kernel(
    sref,    # SMEM scalar prefetch: int32[3]
    dt_ref,  # SMEM (1, 1)
    *refs,   # [u, v, w, f, g, h, p(, flg)] + [u', v', w', umax, vmax, wmax] + scratch
    block_k: int,
    nblocks: int,
    gkmax: int,
    gjmax: int,
    gimax: int,
    ext_pad: int,
    halo: int,
    dx: float,
    dy: float,
    dz: float,
    masked: bool,
    ragged: bool,
    dynamic: bool = False,
):
    if dynamic:
        ext_ref, geo_ref, *refs = refs
    if masked:
        (ub, vb, wb, fb, gb, hb, p_in, flg,
         u_out, v_out, w_out, umax, vmax, wmax,
         bw2, pw2, fw2, ob2, macc, ld_sem, st_sem) = refs
    else:
        (ub, vb, wb, fb, gb, hb, p_in,
         u_out, v_out, w_out, umax, vmax, wmax,
         bw2, pw2, ob2, macc, ld_sem, st_sem) = refs
        flg = fw2 = None
    b = pl.program_id(0)
    bk = block_k
    h = halo
    slot = b % 2
    nslot = (b + 1) % 2
    koff = sref[0]
    joff = sref[1]
    ioff = sref[2]
    dt = dt_ref[0, 0]
    if dynamic:
        gkmax = ext_ref[0, 0]
        gjmax = ext_ref[0, 1]
        gimax = ext_ref[0, 2]
        dx = geo_ref[0, 0]
        dy = geo_ref[0, 1]
        dz = geo_ref[0, 2]

    def load(k, s):
        copies = [
            pltpu.make_async_copy(
                arr.at[pl.ds(h + k * bk, bk)], bw2.at[s, q],
                ld_sem.at[s, q])
            for q, arr in enumerate((ub, vb, wb, fb, gb, hb))
        ]
        copies.append(pltpu.make_async_copy(
            p_in.at[pl.ds(k * bk, bk + 2 * h)], pw2.at[s], ld_sem.at[s, 6]))
        if masked:
            copies.append(pltpu.make_async_copy(
                flg.at[pl.ds(k * bk, bk + 2 * h)], fw2.at[s],
                ld_sem.at[s, 7]))
        return copies

    def store(k, s):
        return [
            pltpu.make_async_copy(
                ob2.at[s, q], arr.at[pl.ds(h + k * bk, bk)],
                st_sem.at[s, q])
            for q, arr in enumerate((u_out, v_out, w_out))
        ]

    @pl.when(b == 0)
    def _():
        macc[...] = jnp.zeros_like(macc)
        for c in load(0, 0):
            c.start()

    @pl.when(b + 1 < nblocks)
    def _():
        for c in load(b + 1, nslot):
            c.start()

    for c in load(b, slot):
        c.wait()

    u = bw2[slot, 0]
    v = bw2[slot, 1]
    w = bw2[slot, 2]
    f = bw2[slot, 3]
    g = bw2[slot, 4]
    hh = bw2[slot, 5]
    pw = pw2[slot]
    pc = pw[h: h + bk]

    def sh_p(x, dk=0, dj=0, di=0):
        # adapt_terms_3d's shift contract on the p window: +1 in k comes
        # from the halo plane above the owned band, in-plane shifts roll
        if dk:
            return pw[h + dk: h + bk + dk]
        return _win_shift(x, 0, dj, di)

    a_k = b * bk + jax.lax.broadcasted_iota(jnp.int32, u.shape, 0)
    a_j = jax.lax.broadcasted_iota(jnp.int32, u.shape, 1)
    a_i = jax.lax.broadcasted_iota(jnp.int32, u.shape, 2)
    gk = a_k - ext_pad + koff
    gj = a_j - ext_pad + joff
    gi = a_i - ext_pad + ioff
    interior = (
        (gk >= 1) & (gk <= gkmax)
        & (gj >= 1) & (gj <= gjmax)
        & (gi >= 1) & (gi <= gimax)
    )

    ua, va, wa = ops3.adapt_terms_3d(f, g, hh, pc, dt, dx, dy, dz, sh=sh_p)
    if masked:
        # projection restricted to fluid-fluid faces (adapt_uvw_obstacle):
        # faces derived from the flag window, the +k shift served from the
        # halo plane above the owned band (the sh_p contract)
        flw = fw2[slot]
        flc = flw[h: h + bk]

        def sh_f(x, dk=0, dj=0, di=0):
            if dk:
                return flw[h + dk: h + bk + dk]
            return _win_shift(x, 0, dj, di)

        u_face, v_face, w_face = _obstacle_faces_3d(
            flc, gk, gj, gi, gkmax, gjmax, gimax, sh=sh_f
        )
        ua = ua * u_face
        va = va * v_face
        wa = wa * w_face
    u = jnp.where(interior, ua, u)
    v = jnp.where(interior, va, v)
    w = jnp.where(interior, wa, w)
    if ragged:
        # the jnp ragged chain's live-mask multiply (ragged3d.live_masks_3d)
        # op-for-op: dead pad cells go to zero after the projection so the
        # ghost-inclusive CFL scan never sees garbage
        live = ((gk <= gkmax + 1) & (gj <= gjmax + 1)
                & (gi <= gimax + 1)).astype(u.dtype)
        u = u * live
        v = v * live
        w = w * live

    @pl.when(b >= 2)
    def _():
        for c in store(b - 2, slot):
            c.wait()

    ob2[slot, 0] = u
    ob2[slot, 1] = v
    ob2[slot, 2] = w
    for c in store(b, slot):
        c.start()

    # ghost-inclusive 3-D maxElement (solver.c:299-310), dead cells and
    # stale deep halos excluded
    valid = (
        (gk >= 0) & (gk <= gkmax + 1)
        & (gj >= 0) & (gj <= gjmax + 1)
        & (gi >= 0) & (gi <= gimax + 1)
    )
    zero = jnp.zeros((), u.dtype)
    for q, arr in enumerate((u, v, w)):
        m = jnp.max(jnp.where(valid, jnp.abs(arr), zero), axis=(0, 1))
        macc[q: q + 1, :] = jnp.maximum(macc[q: q + 1, :], m[None, :])

    @pl.when(b == nblocks - 1)
    def _():
        umax[0, 0] = jnp.max(macc[0:1, :])
        vmax[0, 0] = jnp.max(macc[1:2, :])
        wmax[0, 0] = jnp.max(macc[2:3, :])
        for c in store(b, slot):
            c.wait()
        if nblocks > 1:
            for c in store(b - 1, nslot):
                c.wait()


def fused3_vmem_bytes(bk: int, h: int, jp: int, ip: int, itemsize: int,
                      masked: bool = False) -> int:
    """Scratch bytes of the larger kernel (pre: 3-4 windows + 7 out bands;
    post: 6 in bands + 1-2 windows + 3 out bands), double buffered, plus
    the per-lane max accumulator."""
    plane = jp * ip
    win = (bk + 2 * h) * plane
    band = bk * plane
    pre = 2 * ((4 if masked else 3) * win + 7 * band)
    post = 2 * (6 * band + (2 if masked else 1) * win + 3 * band) + 3 * ip
    return itemsize * max(pre, post)


# Share of the raised VMEM limit the scratch windows may take. The PRE
# kernel's in-register temporaries need about as much again: compiled for
# a v5e at 128³ (plane 136x256), block_k 17 (49.9 MiB of scratch) asked for
# 107.8 MiB of scoped VMEM and was refused, block_k 12 (36.7 MiB) compiled
# (tests/test_chip_compile.py keeps the 128³ compile).
SCRATCH_SHARE = 3


def pick_block_k_fused(kext: int, jp: int, ip: int, dtype,
                       masked: bool = False) -> int:
    """Block depth: budget the resident planes (20·bk + 12·h of the pre
    kernel, +2·bk+4·h for the flag window) against a third of the raised
    VMEM limit (SCRATCH_SHARE), capped by the whole grid."""
    plane = jp * ip * jnp.dtype(dtype).itemsize
    h = FUSE_CHAIN
    per_bk = 22 if masked else 20
    per_h = 16 if masked else 12
    feasible = ((VMEM_LIMIT_BYTES // SCRATCH_SHARE) // plane
                - per_h * h) // per_bk
    return max(1, min(feasible, kext, 32))


def fused_deep_layout_3d(kl: int, jl: int, il: int, dtype, ext_pad: int,
                         block_k: int | None = None,
                         masked: bool = False):
    """(block_k, halo, plane_width, nblocks) of the distributed 3-D
    deep-halo padded layout — the geometry `parallel/overlap.region_plan`
    bands over (the 3-D twin of ns2d_fused.fused_deep_layout_2d; the
    plan's `width` is the padded j*i plane)."""
    ext_k = kl + 2 + 2 * ext_pad
    ext_j = jl + 2 + 2 * ext_pad
    ext_i = il + 2 + 2 * ext_pad
    a = _align(dtype)
    jp = -(-ext_j // a) * a
    ip = -(-ext_i // LANE) * LANE
    if block_k is None:
        block_k = pick_block_k_fused(ext_k, jp, ip, dtype, masked)
    nblocks = -(-ext_k // block_k)
    return block_k, FUSE_CHAIN, jp * ip, nblocks


def _geom3(gkmax, gjmax, gimax, dtype, kl, jl, il, ext_pad, fluid, block_k,
           interpret):
    """Shared geometry/feasibility resolution (the 2-D _geom contract):
    `fluid` is None (no obstacles), a global (kmax+2, jmax+2, imax+2) 0/1
    array (single-device: baked in as a padded constant), or True
    (distributed: the per-shard flag block is an extra call-time arg)."""
    if pltpu is None:
        raise ValueError("pallas TPU backend unavailable")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _check_dtype(dtype, interpret)
    lkmax = gkmax if kl is None else kl
    ljmax = gjmax if jl is None else jl
    limax = gimax if il is None else il
    ext_k = lkmax + 2 + 2 * ext_pad
    ext_j = ljmax + 2 + 2 * ext_pad
    ext_i = limax + 2 + 2 * ext_pad
    a = _align(dtype)
    jp = -(-ext_j // a) * a
    ip = -(-ext_i // LANE) * LANE
    h = FUSE_CHAIN
    masked = fluid is not None
    if block_k is None:
        block_k = pick_block_k_fused(ext_k, jp, ip, dtype, masked)
    nblocks = -(-ext_k // block_k)
    kp = nblocks * block_k + 2 * h
    itemsize = jnp.dtype(dtype).itemsize
    if fused3_vmem_bytes(block_k, h, jp, ip, itemsize, masked) > VMEM_LIMIT_BYTES // 2:
        raise ValueError(
            f"fused 3-D step-phase scratch {fused3_vmem_bytes(block_k, h, jp, ip, itemsize, masked) >> 20} MiB "
            f"exceeds the VMEM budget (block_k={block_k}, plane {jp}x{ip}); "
            "the jnp phase chain is the fallback"
        )

    def pad3(x):
        out = jnp.zeros((kp, jp, ip), x.dtype)
        return out.at[h: h + x.shape[0], : x.shape[1], : x.shape[2]].set(x)

    def unpad3(xp):
        return xp[h: h + ext_k, :ext_j, :ext_i]

    flg_padded = None
    if masked and fluid is not True:
        import numpy as np

        flg_padded = pad3(jnp.asarray(np.asarray(fluid), dtype))
    return (interpret, lkmax, ljmax, limax, h, block_k, jp, ip, nblocks,
            kp, masked, pad3, unpad3, flg_padded)


def make_fused_pre_3d(
    param,
    gkmax: int,
    gjmax: int,
    gimax: int,
    dx: float,
    dy: float,
    dz: float,
    dtype,
    *,
    kl: int | None = None,
    jl: int | None = None,
    il: int | None = None,
    ext_pad: int = 0,
    fluid=None,
    block_k: int | None = None,
    interpret: bool | None = None,
    grid_bands: tuple | None = None,
    dynamic: bool = False,
):
    """Build the 3-D PRE kernel:
      pre(offs_i32[3], dt_11, u_pad, v_pad, w_pad)
          -> (u', v', w', f, g, h, rhs)                            [padded]
    plus (pad3, unpad3, halo). Geometry contract as make_fused_pre_2d;
    fluid=True (distributed obstacles) appends a call-time flag argument
    (the padded per-shard deep-halo slice of the global flag).
    `grid_bands` restricts the Pallas grid to k-plane bands of the same
    padded layout (see make_fused_pre_2d — the grid-restricted overlap
    halves). `dynamic=True` (the 3-D shape-class chunk): extents/cell
    sizes as call-time SMEM scalars — the call becomes
    pre(offs, ext_i32_13, geo_13, dt11, u, v, w) with ext =
    (kmax, jmax, imax) and geo = (dx, dy, dz); single-device only."""
    if dynamic and (fluid is not None or grid_bands is not None):
        raise ValueError(
            "dynamic extents are the single-device shape-class mode "
            "(no obstacle flags, no grid bands)")
    (interpret, lkmax, ljmax, limax, h, block_k, jp, ip, nblocks, kp,
     masked, pad3, unpad3, flg_padded) = _geom3(
        gkmax, gjmax, gimax, dtype, kl, jl, il, ext_pad, fluid, block_k,
        interpret)
    bcs = (
        ("top", param.bcTop), ("bottom", param.bcBottom),
        ("left", param.bcLeft), ("right", param.bcRight),
        ("front", param.bcFront), ("back", param.bcBack),
    )
    if grid_bands is not None:
        from ..parallel.overlap import check_bands

        check_bands(grid_bands, block_k, nblocks, label="block_k")
        nblocks = sum(n for _, n in grid_bands)
    kernel = functools.partial(
        _pre3_kernel,
        bands=grid_bands,
        block_k=block_k,
        nblocks=nblocks,
        gkmax=gkmax,
        gjmax=gjmax,
        gimax=gimax,
        lkmax=lkmax,
        ljmax=ljmax,
        limax=limax,
        ext_pad=ext_pad,
        halo=h,
        bcs=bcs,
        problem=param.name.replace("3d", ""),
        re=param.re,
        gx=param.gx,
        gy=param.gy,
        gz=param.gz,
        gamma=param.gamma,
        dx=dx,
        dy=dy,
        dz=dz,
        masked=masked,
        dynamic=dynamic,
    )
    n_in = 4 if masked else 3
    pre_scratch = [
        pltpu.VMEM((2, block_k + 2 * h, jp, ip), dtype),
        pltpu.VMEM((2, block_k + 2 * h, jp, ip), dtype),
        pltpu.VMEM((2, block_k + 2 * h, jp, ip), dtype),
    ]
    if masked:
        pre_scratch.append(pltpu.VMEM((2, block_k + 2 * h, jp, ip), dtype))
    pre_scratch += [
        pltpu.VMEM((2, 7, block_k, jp, ip), dtype),
        pltpu.SemaphoreType.DMA((2, n_in)),
        pltpu.SemaphoreType.DMA((2, 7)),
    ]
    call = pl.pallas_call(
        kernel,
        name="ns3d_pre",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nblocks,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
            * (3 if dynamic else 1)
            + [pl.BlockSpec(memory_space=pl.ANY)] * n_in,
            out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 7,
            scratch_shapes=pre_scratch,
        ),
        out_shape=[jax.ShapeDtypeStruct((kp, jp, ip), dtype)] * 7,
        compiler_params=CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )

    if dynamic:

        def pre(offs, ext, geo, dt11, u_pad, v_pad, w_pad):
            return call(offs, dt11, ext, geo, u_pad, v_pad, w_pad)
    elif masked and flg_padded is None:

        def pre(offs, dt11, u_pad, v_pad, w_pad, flg_pad):
            return call(offs, dt11, u_pad, v_pad, w_pad, flg_pad)
    elif masked:

        def pre(offs, dt11, u_pad, v_pad, w_pad):
            return call(offs, dt11, u_pad, v_pad, w_pad, flg_padded)
    else:

        def pre(offs, dt11, u_pad, v_pad, w_pad):
            return call(offs, dt11, u_pad, v_pad, w_pad)

    return pre, pad3, unpad3, h


def make_fused_post_3d(
    param,
    gkmax: int,
    gjmax: int,
    gimax: int,
    dx: float,
    dy: float,
    dz: float,
    dtype,
    *,
    kl: int | None = None,
    jl: int | None = None,
    il: int | None = None,
    ext_pad: int = 0,
    fluid=None,
    ragged: bool = False,
    block_k: int | None = None,
    interpret: bool | None = None,
    dynamic: bool = False,
):
    """Build the 3-D POST kernel:
      post(offs_i32[3], dt_11, u, v, w, f, g, h, p)  [all padded]
          -> (u'', v'', w'', umax, vmax, wmax).
    fluid=True appends a call-time flag argument (the padded per-shard
    EXTENDED-block slice of the global flag); ragged=True appends the
    dead-cell live-mask multiply after the projection. `dynamic=True`
    as in make_fused_pre_3d: post(offs, ext, geo, dt11, u, v, w, f, g,
    h, p) with extent-gated masks."""
    if dynamic and fluid is not None:
        raise ValueError(
            "dynamic extents are the single-device shape-class mode "
            "(no obstacle flags)")
    (interpret, lkmax, ljmax, limax, h, block_k, jp, ip, nblocks, kp,
     masked, pad3, unpad3, flg_padded) = _geom3(
        gkmax, gjmax, gimax, dtype, kl, jl, il, ext_pad, fluid, block_k,
        interpret)
    del lkmax, ljmax, limax
    kernel = functools.partial(
        _post3_kernel,
        block_k=block_k,
        nblocks=nblocks,
        gkmax=gkmax,
        gjmax=gjmax,
        gimax=gimax,
        ext_pad=ext_pad,
        halo=h,
        dx=dx,
        dy=dy,
        dz=dz,
        masked=masked,
        ragged=ragged,
        dynamic=dynamic,
    )
    n_in_post = 8 if masked else 7
    post_scratch = [
        pltpu.VMEM((2, 6, block_k, jp, ip), dtype),
        pltpu.VMEM((2, block_k + 2 * h, jp, ip), dtype),
    ]
    if masked:
        post_scratch.append(pltpu.VMEM((2, block_k + 2 * h, jp, ip), dtype))
    post_scratch += [
        pltpu.VMEM((2, 3, block_k, jp, ip), dtype),
        pltpu.VMEM((3, ip), dtype),
        pltpu.SemaphoreType.DMA((2, n_in_post)),
        pltpu.SemaphoreType.DMA((2, 3)),
    ]
    call = pl.pallas_call(
        kernel,
        name="ns3d_post",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nblocks,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
            * (3 if dynamic else 1)
            + [pl.BlockSpec(memory_space=pl.ANY)] * n_in_post,
            out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3
            + [pl.BlockSpec(memory_space=pltpu.SMEM)] * 3,
            scratch_shapes=post_scratch,
        ),
        out_shape=[jax.ShapeDtypeStruct((kp, jp, ip), dtype)] * 3
        + [jax.ShapeDtypeStruct((1, 1), dtype)] * 3,
        compiler_params=CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )

    if dynamic:

        def post(offs, ext, geo, dt11, u_pad, v_pad, w_pad, f_pad, g_pad,
                 h_pad, p_pad):
            u_pad, v_pad, w_pad, um, vm, wm = call(
                offs, dt11, ext, geo, u_pad, v_pad, w_pad, f_pad, g_pad,
                h_pad, p_pad
            )
            return u_pad, v_pad, w_pad, um[0, 0], vm[0, 0], wm[0, 0]
    elif masked and flg_padded is None:

        def post(offs, dt11, u_pad, v_pad, w_pad, f_pad, g_pad, h_pad,
                 p_pad, flg_pad):
            u_pad, v_pad, w_pad, um, vm, wm = call(
                offs, dt11, u_pad, v_pad, w_pad, f_pad, g_pad, h_pad,
                p_pad, flg_pad
            )
            return u_pad, v_pad, w_pad, um[0, 0], vm[0, 0], wm[0, 0]
    elif masked:

        def post(offs, dt11, u_pad, v_pad, w_pad, f_pad, g_pad, h_pad,
                 p_pad):
            u_pad, v_pad, w_pad, um, vm, wm = call(
                offs, dt11, u_pad, v_pad, w_pad, f_pad, g_pad, h_pad,
                p_pad, flg_padded
            )
            return u_pad, v_pad, w_pad, um[0, 0], vm[0, 0], wm[0, 0]
    else:

        def post(offs, dt11, u_pad, v_pad, w_pad, f_pad, g_pad, h_pad,
                 p_pad):
            u_pad, v_pad, w_pad, um, vm, wm = call(
                offs, dt11, u_pad, v_pad, w_pad, f_pad, g_pad, h_pad, p_pad
            )
            return u_pad, v_pad, w_pad, um[0, 0], vm[0, 0], wm[0, 0]

    return post, pad3, unpad3, h


def make_fused_step_3d(
    param,
    gkmax: int,
    gjmax: int,
    gimax: int,
    dx: float,
    dy: float,
    dz: float,
    dtype,
    *,
    fluid=None,
    block_k: int | None = None,
    interpret: bool | None = None,
):
    """The single-device composition (pre + post on the whole grid).
    Returns (pre, post, pad3, unpad3, halo). `fluid` switches on the
    obstacle mode with the global flag baked in as a padded constant."""
    pre, pad3, unpad3, h = make_fused_pre_3d(
        param, gkmax, gjmax, gimax, dx, dy, dz, dtype, fluid=fluid,
        block_k=block_k, interpret=interpret,
    )
    post, _p, _u, _h = make_fused_post_3d(
        param, gkmax, gjmax, gimax, dx, dy, dz, dtype, fluid=fluid,
        block_k=block_k, interpret=interpret,
    )
    return pre, post, pad3, unpad3, h


_PROBE_OK: bool | None = None


def probe_fused_3d() -> bool:
    """One-time smoke test of the 3-D fused pair on the real backend."""
    global _PROBE_OK
    if _PROBE_OK is None:
        try:
            from ..utils.params import Parameter

            param = Parameter(name="dcavity3d", imax=30, jmax=30, kmax=30)
            pre, post, pad3, _unpad3, _h = make_fused_step_3d(
                param, 30, 30, 30, 1.0 / 30, 1.0 / 30, 1.0 / 30,
                jnp.float32, interpret=False,
            )
            z = pad3(jnp.zeros((32, 32, 32), jnp.float32))
            offs = jnp.zeros((3,), jnp.int32)
            dt11 = jnp.full((1, 1), 0.01, jnp.float32)
            up, vp, wp, fp, gp, hp, _r = pre(offs, dt11, z, z, z)
            out = post(offs, dt11, up, vp, wp, fp, gp, hp, z)
            float(out[3])  # force completion
            _PROBE_OK = True
        except Exception as exc:  # lint: allow(broad-except) — probe contract: raise on TPU, report unavailable elsewhere
            from ..utils.dispatch import probe_failed

            _PROBE_OK = probe_failed("the fused NS-3D step-phase kernels", exc)
    return _PROBE_OK
