"""Fused NS-2D step-phase Pallas kernels — the non-solve timestep in two
HBM sweeps.

The round-5 north-star decomposition (results/northstar_dcavity4096.json)
isolated the blocker on the >=10x wall-clock bar: the pressure solve runs at
kernel rate, but the ~40-launch jnp phase chain around it (BCs + special BC
+ computeFG + RHS + adaptUV + CFL max) costs 6.4 ms/step against a ~0.8 ms
HBM-traffic floor — pure per-launch overhead. This module fuses that chain
into TWO kernels bracketing the solve, the same fixed-overhead-amortization
move the temporal-blocked SOR kernels made for the solve itself (and the
reference's comm/compute-overlap lesson one level down: launch latency
instead of message latency):

  PRE  (u, v, dt)        -> (u', v', F, G, rhs)
       wall BCs -> special BC -> obstacle velocity BC -> F/G predictor
       + wall fixups -> obstacle F/G mask -> Poisson RHS
  POST (u', v', F, G, p, dt) -> (u'', v'', max|u''|, max|v''|)
       projection adaptUV (+ obstacle face mask) + the CFL max reduction

The CFL max of the NEXT step is folded into POST: the step state carries
(umax, vmax) and the timestep becomes pure scalar math (ops/ns2d.cfl_dt).
max is exact under any reduction order, and adaptUV is the last writer of
u/v in a step, so max-at-end-of-step == max-at-start-of-next-step bitwise.

Equivalence policy (the quarters-kernel precedent, ops/sor_quarters.py):
every formula is the SAME function the jnp ops call (ops/ns2d
fg_predictor_terms / rhs_terms / adapt_terms with the kernel window's
roll), wall BCs are sequential where-updates writing the same values in
the same wall order as set_boundary_conditions, and all writes are gated
by GLOBAL coordinates — the discipline of ops/sor_obsdist.py, which makes
one kernel serve both the single-device solvers (offsets 0, block = whole
grid) and the distributed twins (per-shard deep-halo blocks, offsets via
scalar prefetch). Pure-copy phases (BC strips, the masked selects, the max
reductions given equal inputs) are BITWISE identical to the jnp chain; the
compound F/G/RHS/projection arithmetic is ulp-equivalent — the same ops in
the same order, differing only by compiler fusion (fma contraction), the
measured-and-accepted gap between ANY two XLA compilations of the same
formula (jit vs eager of the identical jnp function already differs at the
last ulp on CPU). Parity tests pin copies with array_equal and compound
terms at ulp-scale tolerances (tests/test_ns2d_fused.py).

Layout: the sor_pallas padded layout (pad_array/unpad_array, halo =
sublane alignment >= the 3-row validity chain BC->obstacleBC->FG->RHS).
Distributed callers pass the deep-halo extended block (jl + 2H rows,
H = FUSE_DEEP_HALO: cell (a, b) holds global extended index
(joff + a - H + 1, ...) — the stencil2d embed_deep convention) after one
depth-H exchange per step.

Obstacle flag fields compose branch-free: the padded 0/1 fluid flag rides
as a third input window and u_face/v_face are derived in-kernel from it
(integer-exact, matching ops/obstacle.make_masks including the
ghost-column wrap fix), so the obstacle velocity BC, F/G face mask and
projection face mask are the same flag-multiply forms the jnp path uses.
Single-device callers bake the global flag in as a padded constant
(`fluid=<array>`); distributed callers pass `fluid=True` and feed the
per-shard deep-halo slice of the global flag at call time (the
ops/sor_obsdist global-constant-slice convention — sliced blocks agree
wherever shards overlap, so redundant halo recompute stays consistent).

Ragged (pad-with-mask) shards are the SAME kernels at uneven block
bounds: every write is already global-coordinate-gated (hi walls sit
anywhere inside a trailing shard, exactly parallel/ragged2d.py's masked
forms), and POST(ragged=True) appends the live-mask multiply that zeroes
dead cells after the projection — the one extra op the jnp ragged chain
does (live_masks) so pad-cell garbage never reaches the ghost-inclusive
CFL scan.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import ns2d as ops
from .sor_pallas import (
    VMEM_LIMIT_BYTES,
    CompilerParams,
    _align,
    _check_dtype,
    pad_array,
    padded_width,
    pick_block_rows_tblock,
    pltpu,
    unpad_array,
)

NOSLIP, SLIP, OUTFLOW, PERIODIC = 1, 2, 3, 4

# validity consumed between the raw u/v window and the RHS: wall BC (reads
# <=1 cell), obstacle velocity BC (<=1), F/G predictor (<=1), RHS (<=1 but
# only on the low side) — 3 layers bound the chain stage-by-stage
FUSE_CHAIN = 3
# the MEASURED access footprint of the composed chain
# (halocheck.pre_chain_footprint, pinned by tests/test_analysis.py):
# RHS reads F/G only same-row/low-side and G reads u only northward, so
# no composed read path consumes all three budgeted layers — 2 is what
# the deep exchange must actually cover. A chain edit that widens the
# footprint fails halocheck's PRE entries (declared = FUSE_FOOTPRINT)
# before any distributed run can corrupt.
FUSE_FOOTPRINT = 2
# deep-halo exchange depth: the measured footprint plus the extended
# block's own ghost layer (which the depth-H exchange refreshes on
# partitioned axes). Was FUSE_CHAIN + 1 = 4 until the footprint
# derivation shrank it (ROADMAP carried-forward): one whole strip layer
# of exchange bytes saved on every dist step.
FUSE_DEEP_HALO = FUSE_FOOTPRINT + 1
# comm/compute overlap (parallel/overlap.py): extended-block cells at
# least this far from the block edge have a dependency cone (measured
# footprint FUSE_FOOTPRINT) that never reaches the exchanged deep-halo
# strips — the interior half of the split PRE call is gated to them
# (analysis/halocheck.py overlap-interior entries)
OVERLAP_RIM = FUSE_FOOTPRINT + 1


def fuse_halo(dtype) -> int:
    """Window halo rows: the 3-row validity chain rounded to the DMA
    sublane alignment (pass to pad_array/unpad_array)."""
    return max(_align(dtype), FUSE_CHAIN)


def apply_wall_bcs_2d(u, v, gj, gi, bc, gjmax, gimax, roll=jnp.roll):
    """setBoundaryConditions (ops/ns2d.set_boundary_conditions) as
    sequential global-coordinate-gated where-updates: same wall order
    (left, right, bottom, top), same written values, so later walls read
    earlier walls' writes exactly like the at[].set chain. `gj`/`gi` are
    global-extended-index arrays of the window cells."""
    bc_left, bc_right, bc_bottom, bc_top = bc
    rows = (gj >= 1) & (gj <= gjmax)
    cols = (gi >= 1) & (gi <= gimax)
    zero = jnp.zeros((), u.dtype)

    m = (gi == 0) & rows  # left wall: U on the wall, V ghost
    if bc_left == NOSLIP:
        u = jnp.where(m, zero, u)
        v = jnp.where(m, -roll(v, -1, axis=1), v)
    elif bc_left == SLIP:
        u = jnp.where(m, zero, u)
        v = jnp.where(m, roll(v, -1, axis=1), v)
    elif bc_left == OUTFLOW:
        u = jnp.where(m, roll(u, -1, axis=1), u)
        v = jnp.where(m, roll(v, -1, axis=1), v)
    mw = (gi == gimax) & rows   # right wall: U(imax) on the wall
    mg = (gi == gimax + 1) & rows  # right ghost column
    if bc_right == NOSLIP:
        u = jnp.where(mw, zero, u)
        v = jnp.where(mg, -roll(v, 1, axis=1), v)
    elif bc_right == SLIP:
        u = jnp.where(mw, zero, u)
        v = jnp.where(mg, roll(v, 1, axis=1), v)
    elif bc_right == OUTFLOW:
        u = jnp.where(mw, roll(u, 1, axis=1), u)
        v = jnp.where(mg, roll(v, 1, axis=1), v)
    m = (gj == 0) & cols  # bottom wall: V on the wall, U ghost
    if bc_bottom == NOSLIP:
        v = jnp.where(m, zero, v)
        u = jnp.where(m, -roll(u, -1, axis=0), u)
    elif bc_bottom == SLIP:
        v = jnp.where(m, zero, v)
        u = jnp.where(m, roll(u, -1, axis=0), u)
    elif bc_bottom == OUTFLOW:
        u = jnp.where(m, roll(u, -1, axis=0), u)
        v = jnp.where(m, roll(v, -1, axis=0), v)
    mw = (gj == gjmax) & cols    # top wall: V(jmax) on the wall
    mg = (gj == gjmax + 1) & cols  # top ghost row
    if bc_top == NOSLIP:
        v = jnp.where(mw, zero, v)
        u = jnp.where(mg, -roll(u, 1, axis=0), u)
    elif bc_top == SLIP:
        v = jnp.where(mw, zero, v)
        u = jnp.where(mg, roll(u, 1, axis=0), u)
    elif bc_top == OUTFLOW:
        u = jnp.where(mg, roll(u, 1, axis=0), u)
        v = jnp.where(mw, roll(v, 1, axis=0), v)
    return u, v


def apply_special_bc_2d(u, gj, gi, problem, gjmax, gimax, dy, ylength,
                        dtype, prof_dtype, roll=jnp.roll):
    """set_special_bc_dcavity / set_special_bc_canal in gated-where form.
    `prof_dtype` is the dtype the canal profile's y-coordinate math runs in
    before the cast to the field dtype — the field dtype for the
    single-device twin, the time/index dtype for the distributed one (both
    jnp twins' exact expressions)."""
    if problem == "dcavity":
        # lid skips the LAST interior i (the reference loop-bound quirk)
        m = (gj == gjmax + 1) & (gi >= 1) & (gi <= gimax - 1)
        u = jnp.where(m, 2.0 - roll(u, 1, axis=0), u)
    elif problem in ("canal", "canal_obstacle"):
        m = (gi == 0) & (gj >= 1) & (gj <= gjmax)
        y = ((gj.astype(prof_dtype) - 0.5) * dy).astype(dtype)
        prof = y * (ylength - y) * 4.0 / (ylength * ylength)
        u = jnp.where(m, prof, u)
    return u


def _obstacle_faces(fl, gj, gi, gjmax, gimax, roll=jnp.roll):
    """u_face/v_face derived from the 0/1 fluid flag window — integer-exact
    parity with ops/obstacle.make_masks (incl. its ghost-column/row
    wrap-fix: the last global ghost column/row is forced to a face)."""
    one = jnp.ones((), fl.dtype)
    u_face = jnp.where(gi == gimax + 1, one, fl * roll(fl, -1, axis=1))
    v_face = jnp.where(gj == gjmax + 1, one, fl * roll(fl, -1, axis=0))
    return u_face, v_face


def apply_obstacle_velocity_bc_window(u, v, fl, u_face, v_face,
                                      roll=jnp.roll):
    """ops/obstacle.apply_obstacle_velocity_bc transcribed on the window
    (same flag-multiply arithmetic; every wrapped read the full-array form
    relies on is multiplied by zero at the cells that could see window
    wrap, same as at the jnp path's array edges)."""
    one = jnp.ones((), u.dtype)
    u = u * u_face
    v = v * v_face
    both_obs_u = (one - fl) * (one - roll(fl, -1, axis=1))
    uf_n = roll(u_face, -1, axis=0)
    uf_s = roll(u_face, 1, axis=0)
    u_n = roll(u, -1, axis=0)
    u_s = roll(u, 1, axis=0)
    u = u + both_obs_u * (uf_n * (-u_n) + (one - uf_n) * uf_s * (-u_s))
    both_obs_v = (one - fl) * (one - roll(fl, -1, axis=0))
    vf_e = roll(v_face, -1, axis=1)
    vf_w = roll(v_face, 1, axis=1)
    v_e = roll(v, -1, axis=1)
    v_w = roll(v, 1, axis=1)
    v = v + both_obs_v * (vf_e * (-v_e) + (one - vf_e) * vf_w * (-v_w))
    return u, v


def _pre_kernel(
    sref,    # SMEM scalar prefetch: int32[2] = (joff, ioff) grid offsets
    dt_ref,  # SMEM (1, 1): the timestep
    *refs,   # [u_in, v_in(, flg)] + [u_out, v_out, f_out, g_out, r_out] + scratch
    block_rows: int,
    nblocks: int,
    gjmax: int,
    gimax: int,
    ljmax: int,   # local interior extents (== gjmax/gimax single-device)
    limax: int,
    ext_pad: int,  # deep layers beyond the extended block (dist: H-1)
    halo: int,
    bc: tuple,
    problem: str | None,
    re: float,
    gx: float,
    gy: float,
    gamma: float,
    dx: float,
    dy: float,
    ylength: float,
    prof_dtype,
    masked: bool,
    bands: tuple | None = None,
    dynamic: bool = False,
):
    if dynamic:
        # shape-class mode (fleet/shapeclass.py): the live extents and the
        # per-lane cell sizes arrive as SMEM scalars after dt, so one
        # compiled kernel at the padded CLASS geometry serves every lane
        # (every write below is already gated by the SAME comparisons)
        ext_ref, geo_ref, *refs = refs
    if masked:
        (u_in, v_in, flg, u_out, v_out, f_out, g_out, r_out,
         uw2, vw2, fw2, ob2, ld_sem, st_sem) = refs
    else:
        (u_in, v_in, u_out, v_out, f_out, g_out, r_out,
         uw2, vw2, ob2, ld_sem, st_sem) = refs
        flg = fw2 = None
    b = pl.program_id(0)
    br = block_rows
    h = halo
    slot = b % 2
    nslot = (b + 1) % 2
    joff = sref[0]
    ioff = sref[1]
    dt = dt_ref[0, 0]
    if dynamic:
        # single-device class lanes: local extents == global extents
        gjmax = ext_ref[0, 0]
        gimax = ext_ref[0, 1]
        ljmax = gjmax
        limax = gimax
        dx = geo_ref[0, 0]
        dy = geo_ref[0, 1]

    # banded (grid-restricted) sweeps (`tpu_overlap_restrict`,
    # parallel/overlap.region_plan): grid step k of band (s, n) covers
    # padded rows [s + j*br, ...) instead of [k*br, ...). The full-sweep
    # default keeps the literal k*br indexing, so the unrestricted
    # program traces byte-identically to the historical kernel.
    if bands is None or (len(bands) == 1 and bands[0][0] == 0):
        def row_of(k):
            return k * br
    else:
        # every band start and br share this factor: tell Mosaic, which
        # cannot see through the select that a DMA row offset is aligned
        step = math.gcd(br, *(s for s, _ in bands))

        def row_of(k):
            row, acc = None, 0
            for s, n in bands:
                r = s + (k - acc) * br
                row = r if row is None else jnp.where(k >= acc, r, row)
                acc += n
            return pl.multiple_of(row, step) if step > 1 else row

    def load(k, s):
        r0 = row_of(k)
        copies = [
            pltpu.make_async_copy(
                u_in.at[pl.ds(r0, br + 2 * h), :], uw2.at[s],
                ld_sem.at[s, 0]),
            pltpu.make_async_copy(
                v_in.at[pl.ds(r0, br + 2 * h), :], vw2.at[s],
                ld_sem.at[s, 1]),
        ]
        if masked:
            copies.append(pltpu.make_async_copy(
                flg.at[pl.ds(r0, br + 2 * h), :], fw2.at[s],
                ld_sem.at[s, 2]))
        return copies

    def store(k, s):
        r0 = row_of(k)
        outs = (u_out, v_out, f_out, g_out, r_out)
        return [
            pltpu.make_async_copy(
                ob2.at[s, q], outs[q].at[pl.ds(h + r0, br)],
                st_sem.at[s, q])
            for q in range(5)
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

    # padded row of window cell (w, c): rho = row_of(b) + w; global
    # extended index gj = (rho - h) - ext_pad + joff (ext_pad = 0 single-
    # device, H-1 on deep-halo dist blocks), gi likewise (columns are
    # unshifted)
    rho = row_of(b) + jax.lax.broadcasted_iota(jnp.int32, u.shape, 0)
    a_j = rho - h
    a_i = jax.lax.broadcasted_iota(jnp.int32, u.shape, 1)
    gj = a_j - ext_pad + joff
    gi = a_i - ext_pad + ioff

    # restore the dead-cell-zero invariant on the loaded windows: the
    # carried padded arrays' halo/tail rows are never stored by this
    # kernel, so they hold undefined data (NaN in interpret mode) — and
    # the obstacle path's MULTIPLICATIVE masks propagate 0*NaN into valid
    # cells where jnp.where would not
    ext_rows = ljmax + 2 + 2 * ext_pad
    ext_cols = limax + 2 + 2 * ext_pad
    live_in = (a_j >= 0) & (a_j < ext_rows) & (a_i >= 0) & (a_i < ext_cols)
    u = jnp.where(live_in, u, 0.0)
    v = jnp.where(live_in, v, 0.0)

    u, v = apply_wall_bcs_2d(u, v, gj, gi, bc, gjmax, gimax)
    u = apply_special_bc_2d(u, gj, gi, problem, gjmax, gimax, dy, ylength,
                            u.dtype, prof_dtype)
    if masked:
        fl = fw2[slot]
        u_face, v_face = _obstacle_faces(fl, gj, gi, gjmax, gimax)
        u, v = apply_obstacle_velocity_bc_window(u, v, fl, u_face, v_face)

    f_full, g_full = ops.fg_predictor_terms(
        u, v, dt, re, gx, gy, gamma, dx, dy
    )
    interior = (gj >= 1) & (gj <= gjmax) & (gi >= 1) & (gi <= gimax)
    rows = (gj >= 1) & (gj <= gjmax)
    cols = (gi >= 1) & (gi <= gimax)
    f = jnp.where(interior, f_full, 0.0)
    g = jnp.where(interior, g_full, 0.0)
    # wall fixups (apply_fg_wall_fixups / gated fg_fixups): F carries U on
    # vertical walls, G carries V on horizontal walls
    f = jnp.where((gi == 0) & rows, u, f)
    f = jnp.where((gi == gimax) & rows, u, f)
    g = jnp.where((gj == 0) & cols, v, g)
    g = jnp.where((gj == gjmax) & cols, v, g)
    if masked:
        one = jnp.ones((), u.dtype)
        f = u_face * f + (one - u_face) * u
        g = v_face * g + (one - v_face) * v

    # RHS clipped to the LOCAL interior too: the jnp dist chain leaves the
    # extended block's own ring zero (its solve exchanges rhs halos before
    # reading them) — identical to the global clip on a single device
    local_int = (
        (a_j >= ext_pad + 1) & (a_j <= ext_pad + ljmax)
        & (a_i >= ext_pad + 1) & (a_i <= ext_pad + limax)
    )
    rhs = jnp.where(
        interior & local_int, ops.rhs_terms(f, g, dt, dx, dy), 0.0
    )

    @pl.when(b >= 2)
    def _():
        for c in store(b - 2, slot):
            c.wait()

    for q, arr in enumerate((u, v, f, g, rhs)):
        ob2[slot, q] = arr[h: h + br, :]
    for c in store(b, slot):
        c.start()

    @pl.when(b == nblocks - 1)
    def _():
        for c in store(b, slot):
            c.wait()
        if nblocks > 1:  # static: drain the previous slot's stores too
            for c in store(b - 1, nslot):
                c.wait()


def _post_kernel(
    sref,    # SMEM scalar prefetch: int32[2] = (joff, ioff)
    dt_ref,  # SMEM (1, 1)
    *refs,   # [u, v, f, g, p(, flg)] + [u_out, v_out, umax, vmax] + scratch
    block_rows: int,
    nblocks: int,
    gjmax: int,
    gimax: int,
    ext_pad: int,
    halo: int,
    dx: float,
    dy: float,
    masked: bool,
    ragged: bool,
    dynamic: bool = False,
):
    """adaptUV + the CFL max|u|/max|v| reduction. u/v/f/g ride as owned
    bands (adaptUV reads them at the center only); p (and the flag, whose
    v_face needs one north row) ride as halo windows. The maxes scan every
    cell of the global extended array exactly once across blocks — the
    maxElement ghost-inclusive quirk — masked to the valid region so dist
    callers' stale deep-halo rows never leak in. `dynamic` as in
    _pre_kernel: extents/cell sizes as SMEM scalars (shape-class mode)."""
    if dynamic:
        ext_ref, geo_ref, *refs = refs
    if masked:
        (ub, vb, fb, gb, p_in, flg, u_out, v_out, umax, vmax,
         bw2, pw2, fw2, ob2, macc, ld_sem, st_sem) = refs
    else:
        (ub, vb, fb, gb, p_in, u_out, v_out, umax, vmax,
         bw2, pw2, ob2, macc, ld_sem, st_sem) = refs
        flg = fw2 = None
    b = pl.program_id(0)
    br = block_rows
    h = halo
    slot = b % 2
    nslot = (b + 1) % 2
    joff = sref[0]
    ioff = sref[1]
    dt = dt_ref[0, 0]
    if dynamic:
        gjmax = ext_ref[0, 0]
        gimax = ext_ref[0, 1]
        dx = geo_ref[0, 0]
        dy = geo_ref[0, 1]

    def load(k, s):
        copies = [
            pltpu.make_async_copy(
                arr.at[pl.ds(h + k * br, br), :], bw2.at[s, q],
                ld_sem.at[s, q])
            for q, arr in enumerate((ub, vb, fb, gb))
        ]
        copies.append(pltpu.make_async_copy(
            p_in.at[pl.ds(k * br, br + 2 * h), :], pw2.at[s],
            ld_sem.at[s, 4]))
        if masked:
            copies.append(pltpu.make_async_copy(
                flg.at[pl.ds(k * br, br + 2 * h), :], fw2.at[s],
                ld_sem.at[s, 5]))
        return copies

    def store(k, s):
        return [
            pltpu.make_async_copy(
                ob2.at[s, q], arr.at[pl.ds(h + k * br, br)],
                st_sem.at[s, q])
            for q, arr in enumerate((u_out, v_out))
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
    f = bw2[slot, 2]
    g = bw2[slot, 3]
    pw = pw2[slot]
    pc = pw[h: h + br, :]

    def roll_p(x, shift, axis):
        # adapt_terms' neighbour contract on the p window: the north
        # neighbour comes from the halo row above the owned band, the east
        # one is an in-row roll (identical values at every unmasked cell).
        # The axis-0 slice hard-codes roll(p, -1, axis=0); trace-time
        # assert rather than silently serving the p halo for anything else
        if axis == 0:
            assert x is pc and shift == -1, (
                "fused POST kernel only supports adapt_terms' "
                "roll(p, -1, axis=0); got shift="
                f"{shift} on axis 0"
            )
            return pw[h + 1: h + br + 1, :]
        return jnp.roll(x, shift, axis=axis)

    rho = b * br + jax.lax.broadcasted_iota(jnp.int32, u.shape, 0)
    a_j = rho
    a_i = jax.lax.broadcasted_iota(jnp.int32, u.shape, 1)
    gj = a_j - ext_pad + joff
    gi = a_i - ext_pad + ioff
    interior = (gj >= 1) & (gj <= gjmax) & (gi >= 1) & (gi <= gimax)

    ua, va = ops.adapt_terms(f, g, pc, dt, dx, dy, roll=roll_p)
    if masked:
        fl = fw2[slot]
        u_face, v_face = _obstacle_faces(
            fl[h: h + br, :], gj, gi, gjmax, gimax,
            roll=lambda x, s, axis: (
                fl[h + 1: h + br + 1, :] if axis == 0
                else jnp.roll(x, s, axis=axis)
            ),
        )
        ua = ua * u_face
        va = va * v_face
    u = jnp.where(interior, ua, u)
    v = jnp.where(interior, va, v)
    if ragged:
        # the jnp ragged chain's live-mask MULTIPLY (ragged2d.live_masks),
        # op-for-op: dead pad cells go to zero after the projection so the
        # next step's ghost-inclusive CFL scan never sees garbage
        live = ((gj <= gjmax + 1) & (gi <= gimax + 1)).astype(u.dtype)
        u = u * live
        v = v * live

    @pl.when(b >= 2)
    def _():
        for c in store(b - 2, slot):
            c.wait()

    ob2[slot, 0] = u
    ob2[slot, 1] = v
    for c in store(b, slot):
        c.start()

    # ghost-inclusive maxElement (solver.c:193-202 quirk): every global
    # extended cell, dead padding and stale deep halos excluded
    valid = (gj >= 0) & (gj <= gjmax + 1) & (gi >= 0) & (gi <= gimax + 1)
    zero = jnp.zeros((), u.dtype)
    au = jnp.max(jnp.where(valid, jnp.abs(u), zero), axis=0, keepdims=True)
    av = jnp.max(jnp.where(valid, jnp.abs(v), zero), axis=0, keepdims=True)
    macc[0:1, :] = jnp.maximum(macc[0:1, :], au)
    macc[1:2, :] = jnp.maximum(macc[1:2, :], av)

    @pl.when(b == nblocks - 1)
    def _():
        umax[0, 0] = jnp.max(macc[0:1, :])
        vmax[0, 0] = jnp.max(macc[1:2, :])
        for c in store(b, slot):
            c.wait()
        if nblocks > 1:
            for c in store(b - 1, nslot):
                c.wait()


def fused_vmem_bytes(br: int, h: int, wp: int, itemsize: int,
                     masked: bool) -> int:
    """Scratch bytes of the LARGER of the two kernels (pre: 2-3 windows +
    5 out bands; post: 4 in bands + 1-2 windows + 2 out bands), double
    buffered."""
    win = (br + 2 * h) * wp
    band = br * wp
    pre = 2 * ((3 if masked else 2) * win + 5 * band)
    post = 2 * (4 * band + (2 if masked else 1) * win + 2 * band)
    return itemsize * max(pre, post)


def fused_feasible(br: int, h: int, wp: int, itemsize: int,
                   masked: bool) -> bool:
    return fused_vmem_bytes(br, h, wp, itemsize, masked) <= VMEM_LIMIT_BYTES // 2


def _layout(ext_rows: int, ext_cols: int, dtype, block_rows):
    h = fuse_halo(dtype)
    if block_rows is None:
        block_rows = pick_block_rows_tblock(ext_rows - 2, ext_cols - 2,
                                            dtype, 1)
    wp = padded_width(ext_cols - 2)
    nblocks = -(-ext_rows // block_rows)
    rp = nblocks * block_rows + 2 * h
    return h, block_rows, wp, nblocks, rp


def _geom(param, gjmax, gimax, dtype, jl, il, ext_pad, fluid, prof_dtype,
          block_rows, interpret):
    """Shared geometry/feasibility resolution for the pre/post builders.
    `fluid` is None (no obstacles), a global (jmax+2, imax+2) 0/1 array
    (single-device: baked in as a padded constant), or True (distributed:
    the per-shard flag block is an extra call-time argument)."""
    if pltpu is None:
        raise ValueError("pallas TPU backend unavailable")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _check_dtype(dtype, interpret)
    ljmax = gjmax if jl is None else jl
    limax = gimax if il is None else il
    ext_rows = ljmax + 2 + 2 * ext_pad
    ext_cols = limax + 2 + 2 * ext_pad
    h, block_rows, wp, nblocks, rp = _layout(ext_rows, ext_cols, dtype,
                                             block_rows)
    itemsize = jnp.dtype(dtype).itemsize
    masked = fluid is not None
    if not fused_feasible(block_rows, h, wp, itemsize, masked):
        raise ValueError(
            f"fused step-phase scratch {fused_vmem_bytes(block_rows, h, wp, itemsize, masked) >> 20} MiB "
            f"exceeds the VMEM budget (block_rows={block_rows}, h={h}, "
            f"wp={wp}); the jnp phase chain is the fallback"
        )
    if prof_dtype is None:
        prof_dtype = dtype

    def _pad(x):
        return pad_array(x, block_rows, h)

    def _unpad(xp):
        return unpad_array(xp, ext_rows - 2, ext_cols - 2, h)

    flg_padded = None
    if masked and fluid is not True:
        import numpy as np

        flg_padded = _pad(jnp.asarray(np.asarray(fluid), dtype))
    return (interpret, ljmax, limax, h, block_rows, wp, nblocks, rp,
            masked, prof_dtype, _pad, _unpad, flg_padded)


def fused_layout_2d(jmax: int, imax: int, dtype, block_rows=None):
    """(block_rows, halo) of the single-device fused padded layout — what
    make_fused_step_2d resolves to. Callers that want the pressure solve on
    the SAME layout (the p-layout fold, models/ns2d) read it here and pass
    block_rows to both builders."""
    h, br, _wp, _nb, _rp = _layout(jmax + 2, imax + 2, dtype, block_rows)
    return br, h


def fused_deep_layout_2d(jl: int, il: int, dtype, ext_pad: int,
                         block_rows=None):
    """(block_rows, halo, width, nblocks) of the distributed deep-halo
    padded layout — the geometry `parallel/overlap.region_plan` bands
    over when the PRE halves are grid-restricted
    (`tpu_overlap_restrict`)."""
    h, br, wp, nb, _rp = _layout(jl + 2 + 2 * ext_pad,
                                 il + 2 + 2 * ext_pad, dtype, block_rows)
    return br, h, wp, nb


def make_fused_pre_2d(
    param,
    gjmax: int,
    gimax: int,
    dx: float,
    dy: float,
    dtype,
    *,
    jl: int | None = None,
    il: int | None = None,
    ext_pad: int = 0,
    fluid=None,
    prof_dtype=None,
    block_rows: int | None = None,
    interpret: bool | None = None,
    grid_bands: tuple | None = None,
    dynamic: bool = False,
):
    """Build the PRE kernel for one grid/shard geometry:
      pre(offs_i32[2], dt_11, u_pad, v_pad) -> (u', v', f, g, rhs)  [padded]
    plus (pad, unpad, halo) for its layout. Single-device: jl/il omitted,
    ext_pad 0, offsets zeros. Distributed: jl/il are the shard's interior
    extents, ext_pad = FUSE_DEEP_HALO - 1, arrays are the padded deep-halo
    blocks. fluid=True (distributed obstacles) appends a call-time flag
    argument: pre(offs, dt11, u_pad, v_pad, flg_pad), flg_pad the padded
    per-shard deep-halo slice of the global flag. Raises ValueError on
    VMEM infeasibility — the caller's contract is to fall back to the jnp
    chain.

    `grid_bands` (parallel/overlap.region_plan) restricts the Pallas grid
    to ((start_row, n_blocks), ...) row bands of the SAME padded layout —
    the grid-restricted overlap halves. Outputs outside the bands are
    never stored (the interior-merge mask must not select them); the
    layout, call signature and every stored value inside the bands are
    identical to the full sweep's (the kernel stays globally gated).

    `dynamic=True` (the shape-class chunk, fleet/shapeclass.py): gjmax/
    gimax set only the padded CLASS geometry — the live extents and the
    per-lane cell sizes become call-time SMEM scalars, so the call grows
    two operands: pre(offs, ext_i32_12, geo_12, dt11, u_pad, v_pad) with
    ext = (jmax, imax) and geo = (dx, dy). Single-device only
    (incompatible with fluid/grid_bands — class-ineligible modes)."""
    if dynamic and (fluid is not None or grid_bands is not None):
        raise ValueError(
            "dynamic extents are the single-device shape-class mode "
            "(no obstacle flags, no grid bands)")
    (interpret, ljmax, limax, h, block_rows, wp, nblocks, rp, masked,
     prof_dtype, _pad, _unpad, flg_padded) = _geom(
        param, gjmax, gimax, dtype, jl, il, ext_pad, fluid, prof_dtype,
        block_rows, interpret)
    bc = (param.bcLeft, param.bcRight, param.bcBottom, param.bcTop)
    if grid_bands is not None:
        from ..parallel.overlap import check_bands

        check_bands(grid_bands, block_rows, nblocks)
        nblocks = sum(n for _, n in grid_bands)

    pre_kernel = functools.partial(
        _pre_kernel,
        bands=grid_bands,
        block_rows=block_rows,
        nblocks=nblocks,
        gjmax=gjmax,
        gimax=gimax,
        ljmax=ljmax,
        limax=limax,
        ext_pad=ext_pad,
        halo=h,
        bc=bc,
        problem=param.name,
        re=param.re,
        gx=param.gx,
        gy=param.gy,
        gamma=param.gamma,
        dx=dx,
        dy=dy,
        ylength=param.ylength,
        prof_dtype=prof_dtype,
        masked=masked,
        dynamic=dynamic,
    )
    n_in = 3 if masked else 2
    pre_scratch = [
        pltpu.VMEM((2, block_rows + 2 * h, wp), dtype),
        pltpu.VMEM((2, block_rows + 2 * h, wp), dtype),
    ]
    if masked:
        pre_scratch.append(pltpu.VMEM((2, block_rows + 2 * h, wp), dtype))
    pre_scratch += [
        pltpu.VMEM((2, 5, block_rows, wp), dtype),
        pltpu.SemaphoreType.DMA((2, n_in)),
        pltpu.SemaphoreType.DMA((2, 5)),
    ]
    pre_call = pl.pallas_call(
        pre_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nblocks,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
            * (3 if dynamic else 1)
            + [pl.BlockSpec(memory_space=pl.ANY)] * n_in,
            out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 5,
            scratch_shapes=pre_scratch,
        ),
        out_shape=[jax.ShapeDtypeStruct((rp, wp), dtype)] * 5,
        compiler_params=CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES
        ),
        interpret=interpret,
    )

    if dynamic:

        def pre(offs, ext, geo, dt11, u_pad, v_pad):
            return pre_call(offs, dt11, ext, geo, u_pad, v_pad)
    elif masked and flg_padded is None:

        def pre(offs, dt11, u_pad, v_pad, flg_pad):
            return pre_call(offs, dt11, u_pad, v_pad, flg_pad)
    elif masked:

        def pre(offs, dt11, u_pad, v_pad):
            return pre_call(offs, dt11, u_pad, v_pad, flg_padded)
    else:

        def pre(offs, dt11, u_pad, v_pad):
            return pre_call(offs, dt11, u_pad, v_pad)

    return pre, _pad, _unpad, h


def make_fused_post_2d(
    param,
    gjmax: int,
    gimax: int,
    dx: float,
    dy: float,
    dtype,
    *,
    jl: int | None = None,
    il: int | None = None,
    ext_pad: int = 0,
    fluid=None,
    ragged: bool = False,
    block_rows: int | None = None,
    interpret: bool | None = None,
    dynamic: bool = False,
):
    """Build the POST kernel (same geometry contract as make_fused_pre_2d):
      post(offs_i32[2], dt_11, u_pad, v_pad, f_pad, g_pad, p_pad)
          -> (u'', v'', umax, vmax)                     [padded + scalars]
    Distributed callers build it on the PLAIN extended block (ext_pad 0):
    adaptUV reads only center/+1 values, all inside the exchanged halo-1
    ring. fluid=True appends a call-time flag argument (the padded
    per-shard EXTENDED-block slice of the global flag); ragged=True
    appends the dead-cell live-mask multiply after the projection.
    `dynamic=True` as in make_fused_pre_2d: the call becomes
    post(offs, ext, geo, dt11, u, v, f, g, p) with extent-gated masks."""
    if dynamic and fluid is not None:
        raise ValueError(
            "dynamic extents are the single-device shape-class mode "
            "(no obstacle flags)")
    (interpret, ljmax, limax, h, block_rows, wp, nblocks, rp, masked,
     _prof_dtype, _pad, _unpad, flg_padded) = _geom(
        param, gjmax, gimax, dtype, jl, il, ext_pad, fluid, None,
        block_rows, interpret)
    del ljmax, limax

    post_kernel = functools.partial(
        _post_kernel,
        block_rows=block_rows,
        nblocks=nblocks,
        gjmax=gjmax,
        gimax=gimax,
        ext_pad=ext_pad,
        halo=h,
        dx=dx,
        dy=dy,
        masked=masked,
        ragged=ragged,
        dynamic=dynamic,
    )
    n_in_post = 6 if masked else 5
    post_scratch = [
        pltpu.VMEM((2, 4, block_rows, wp), dtype),
        pltpu.VMEM((2, block_rows + 2 * h, wp), dtype),
    ]
    if masked:
        post_scratch.append(pltpu.VMEM((2, block_rows + 2 * h, wp), dtype))
    post_scratch += [
        pltpu.VMEM((2, 2, block_rows, wp), dtype),
        pltpu.VMEM((2, wp), dtype),  # per-lane |u|/|v| max accumulators
        pltpu.SemaphoreType.DMA((2, n_in_post)),
        pltpu.SemaphoreType.DMA((2, 2)),
    ]
    post_call = pl.pallas_call(
        post_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nblocks,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
            * (3 if dynamic else 1)
            + [pl.BlockSpec(memory_space=pl.ANY)] * n_in_post,
            out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2
            + [pl.BlockSpec(memory_space=pltpu.SMEM)] * 2,
            scratch_shapes=post_scratch,
        ),
        out_shape=[jax.ShapeDtypeStruct((rp, wp), dtype)] * 2
        + [jax.ShapeDtypeStruct((1, 1), dtype)] * 2,
        compiler_params=CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES
        ),
        interpret=interpret,
    )

    if dynamic:

        def post(offs, ext, geo, dt11, u_pad, v_pad, f_pad, g_pad, p_pad):
            u_pad, v_pad, um, vm = post_call(
                offs, dt11, ext, geo, u_pad, v_pad, f_pad, g_pad, p_pad
            )
            return u_pad, v_pad, um[0, 0], vm[0, 0]
    elif masked and flg_padded is None:

        def post(offs, dt11, u_pad, v_pad, f_pad, g_pad, p_pad, flg_pad):
            u_pad, v_pad, um, vm = post_call(
                offs, dt11, u_pad, v_pad, f_pad, g_pad, p_pad, flg_pad
            )
            return u_pad, v_pad, um[0, 0], vm[0, 0]
    elif masked:

        def post(offs, dt11, u_pad, v_pad, f_pad, g_pad, p_pad):
            u_pad, v_pad, um, vm = post_call(
                offs, dt11, u_pad, v_pad, f_pad, g_pad, p_pad, flg_padded
            )
            return u_pad, v_pad, um[0, 0], vm[0, 0]
    else:

        def post(offs, dt11, u_pad, v_pad, f_pad, g_pad, p_pad):
            u_pad, v_pad, um, vm = post_call(
                offs, dt11, u_pad, v_pad, f_pad, g_pad, p_pad
            )
            return u_pad, v_pad, um[0, 0], vm[0, 0]

    return post, _pad, _unpad, h


def make_fused_step_2d(
    param,
    gjmax: int,
    gimax: int,
    dx: float,
    dy: float,
    dtype,
    *,
    fluid=None,
    prof_dtype=None,
    block_rows: int | None = None,
    interpret: bool | None = None,
):
    """The single-device composition: PRE and POST on the same whole-grid
    geometry. Returns (pre, post, pad, unpad, halo); see the per-kernel
    builders for the call signatures. `fluid` switches on the obstacle
    mode with the global flag field baked in as a padded constant."""
    pre, _pad, _unpad, h = make_fused_pre_2d(
        param, gjmax, gimax, dx, dy, dtype, fluid=fluid,
        prof_dtype=prof_dtype, block_rows=block_rows, interpret=interpret,
    )
    post, _pad2, _unpad2, _h2 = make_fused_post_2d(
        param, gjmax, gimax, dx, dy, dtype, fluid=fluid,
        block_rows=block_rows, interpret=interpret,
    )
    return pre, post, _pad, _unpad, h


_PROBE_OK: bool | None = None


def probe_fused_2d() -> bool:
    """One-time smoke test of the fused step-phase pair on a tiny grid on
    the real backend (the sor_pallas.probe_pallas contract): toolchain-wide
    failures surface once, raised on a TPU backend."""
    global _PROBE_OK
    if _PROBE_OK is None:
        try:
            from ..utils.params import Parameter

            param = Parameter(name="dcavity", imax=126, jmax=126)
            pre, post, _pad, _unpad, _h = make_fused_step_2d(
                param, 126, 126, 1.0 / 126, 1.0 / 126, jnp.float32,
                interpret=False,
            )
            z = _pad(jnp.zeros((128, 128), jnp.float32))
            offs = jnp.zeros((2,), jnp.int32)
            dt11 = jnp.full((1, 1), 0.01, jnp.float32)
            up, vp, fp, gp, _r = pre(offs, dt11, z, z)
            up, vp, um, _vm = post(offs, dt11, up, vp, fp, gp, z)
            float(um)  # force completion: async errors surface here
            _PROBE_OK = True
        except Exception as exc:  # lint: allow(broad-except) — probe contract: raise on TPU, report unavailable elsewhere
            from ..utils.dispatch import probe_failed

            _PROBE_OK = probe_failed("the fused NS-2D step-phase kernels", exc)
    return _PROBE_OK
