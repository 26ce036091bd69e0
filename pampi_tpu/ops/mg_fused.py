"""Fused multigrid V-cycle: the whole restrict→smooth→prolong chain of
ops/multigrid.py as two Pallas launches per cycle (PR 16, ROADMAP item 1).

The historical MG program is a LADDER of small launches: every level runs
its own smoother kernels with jnp transfer glue between them — exactly the
launch-bound shape the phase fusion (PR 1) removed from the step, now on
the solve side. This module closes that chain with the dynamic-extent SMEM
machinery from the shape-class kernels (ops/sor_pallas make_rb_iter_tblock
``dynamic=True``): every MG level lives on ONE fixed padded plane, its live
extents and grid-derived coefficients arrive as call-time scalars, and the
pad cells are dead globally-gated writes — levels become extents, not
programs.

Layout: a level with interior extents (jl, il) occupies the top-left
(jl+2, il+2) corner of the (Jp, Ip) plane (ghost ring included, pad cells
zero), Jp a sublane multiple of the FINEST level's rows, Ip a lane
multiple. All level transfers are gather-free: restriction is
roll(-1)/reshape-mean/roll(+1), prolongation is roll(-1)/repeat/roll(+1),
with interior masks from ``broadcasted_iota`` against the live extents, so
the same code serves every level's geometry inside one launch.

Launch structure (solo cycle, ``make_cycle_kernels``):

- DOWN kernel: for levels 0..L-2 pre-smooth, residual, restrict; emits the
  (L, ...) p/rhs level stacks.
- bottom: stays a *jnp* application between the two launches — the exact
  direct solves of the ladder (DCT diagonalization for constant
  coefficients, dense pinv for obstacle bottoms, or the FFT-preconditioned
  coarse application) are not kernel material.
- UP kernel: prolong + Neumann + post-smooth from the bottom correction
  back to the fine level.

So one V-cycle is exactly TWO pallas launches regardless of depth. The
arithmetic is op-for-op the jnp ladder's (masked where-selects instead of
mask multiplies, dead cells bitwise unchanged), so the ladder stays the
parity oracle at the ulp contract.

The class-lane variant (``make_class_cycle_2d``) goes further: the whole
cycle (including an in-kernel smoothed bottom) is ONE launch, with the
level plan itself (live flags + extents + coefficients) computed OUTSIDE
the kernel from the lane's call-time scalars (``class_level_plan``), so one
compiled cycle kernel serves every lane of a shape class.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl

try:  # pallas TPU backend is absent on some CPU-only installs
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None

from .sor_pallas import (
    VMEM_LIMIT_BYTES,
    CompilerParams,
    _check_dtype,
    padded_width,
)


# The fused cycle does not lower for a TPU on the installed toolchain:
# `_restrict_plane` puts an in-kernel dynamic_update_slice ("Unimplemented
# primitive in Pallas TPU lowering") in front of a 2x2 reshape that Mosaic
# also refuses ("unsupported shape cast"); tests/test_chip_compile.py pins
# the refusal. `tpu_mg_fused auto` keeps the per-level ladder on a TPU,
# with this reason in the dispatch record; `on` still forces the cycle.
TPU_BLOCKER = "fused cycle not Mosaic-lowerable: 2x2 restriction"


def _pad8(n: int) -> int:
    return -(-n // 8) * 8


def fused_layout(extents) -> tuple:
    """Padded plane shape for a level hierarchy whose finest extents are
    ``extents`` ((jmax, imax) or (kmax, jmax, imax)): last dim lane-aligned,
    the rest sublane-aligned (all even, so the full-plane 2x restriction
    reshape is always legal)."""
    dims = [_pad8(e + 2) for e in extents[:-1]]
    dims.append(padded_width(extents[-1]))
    return tuple(dims)


def pad_plane(a, plane_shape):
    """Embed a (jmax+2, imax+2)[, 3-D] array at the origin of the zero
    plane (the fused layout above)."""
    out = jnp.zeros(plane_shape, a.dtype)
    return lax.dynamic_update_slice(out, a, (0,) * a.ndim)


def unpad_plane(a, extents):
    return a[tuple(slice(0, e + 2) for e in extents)]


def fused_vmem_bytes(n_levels: int, plane_shape, itemsize: int) -> int:
    """Worst-case VMEM residency of one cycle launch: the two (L, ...)
    level stacks plus the p/rhs planes and transfer temporaries."""
    return (2 * n_levels + 4) * math.prod(plane_shape) * itemsize


def plan_why_not(levels, dtype, interpret=None):
    """Reason the fused cycle cannot serve this level plan (None = it can).
    Recorded verbatim as the dispatch decision by the callers."""
    if pltpu is None:
        return "pallas TPU backend unavailable"
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if len(levels) < 2:
        return ("single-level plan: the direct bottom solve is the whole "
                "cycle (ragged/odd or budget-truncated grid)")
    if not interpret and jnp.dtype(dtype).itemsize > 4:
        return "dtype not Mosaic-lowerable"
    plane = fused_layout(levels[0])
    need = fused_vmem_bytes(len(levels), plane, jnp.dtype(dtype).itemsize)
    if need > VMEM_LIMIT_BYTES:
        return (f"level stack {need >> 20} MiB exceeds the VMEM budget "
                f"({VMEM_LIMIT_BYTES >> 20} MiB) at plane {plane}")
    return None


# ----------------------------------------------------------------------
# in-kernel building blocks — full-plane forms of the ladder's interior
# ops, parametrized by live extents (traced scalars). Axis convention:
# extents/planes ordered (j, i) or (k, j, i); inv2 ordered
# [idx2, idy2(, idz2)] pairing idx2 with the LAST (lane) axis, like the
# ladder's stencils.
# ----------------------------------------------------------------------


def _iotas(shape):
    return [lax.broadcasted_iota(jnp.int32, shape, d)
            for d in range(len(shape))]


def _interior(idx, ext):
    m = None
    for ax, e in zip(idx, ext):
        t = (ax >= 1) & (ax <= e)
        m = t if m is None else m & t
    return m


def _parity_mask(idx, ext, parity):
    # plane coords ARE the ladder's 1-based interior indices (content sits
    # at the origin), so the checkerboard is the plain coordinate sum
    s = idx[0]
    for ax in idx[1:]:
        s = s + ax
    return _interior(idx, ext) & ((s % 2) == parity)


def _lap_plain(p, inv2):
    nd = p.ndim
    out = None
    for k, w in enumerate(inv2):
        ax = nd - 1 - k
        t = (jnp.roll(p, -1, ax) - 2.0 * p + jnp.roll(p, 1, ax)) * w
        out = t if out is None else out + t
    return out


def _lap_obstacle(p, fl, inv2):
    # per-direction fluid coefficients recomputed from the flag plane —
    # exact 0/1 values, so bitwise the precomputed eps arrays
    nd = p.ndim
    out = None
    for k, w in enumerate(inv2):
        ax = nd - 1 - k
        eps_p = jnp.roll(fl, -1, ax) * fl
        eps_m = jnp.roll(fl, 1, ax) * fl
        t = (eps_p * (jnp.roll(p, -1, ax) - p)
             + eps_m * (jnp.roll(p, 1, ax) - p)) * w
        out = t if out is None else out + t
    return out


def _neumann_plane(p, idx, ext, gate=None):
    """The ladder's domain-wall ghost copy (_neumann2 / neumann_faces_3d):
    each face's ghost takes the adjacent interior value, tangential ranges
    only, edges/corners untouched. All reads are interior cells of the
    original p, so the sequential where-selects are exact."""
    out = p
    for d in range(len(ext)):
        tang = None
        for d2 in range(len(ext)):
            if d2 == d:
                continue
            t = (idx[d2] >= 1) & (idx[d2] <= ext[d2])
            tang = t if tang is None else tang & t
        lo = (idx[d] == 0) & tang
        hi = (idx[d] == ext[d] + 1) & tang
        if gate is not None:
            lo = lo & gate
            hi = hi & gate
        out = jnp.where(lo, jnp.roll(p, -1, d), out)
        out = jnp.where(hi, jnp.roll(p, 1, d), out)
    return out


def _smooth_plane(p, rhs, idx, ext, parities, factor, inv2, n,
                  fl=None, fac=None, gate=None):
    """n red-black sweeps, the _smooth2/_smooth3 (plain) or
    sor_pass_obstacle (fl/fac given) arithmetic on the full plane; cells
    outside the live interior (or outside ``gate``) are bitwise
    unchanged."""
    for _ in range(n):
        for par in parities:
            m = _parity_mask(idx, ext, par)
            if gate is not None:
                m = m & gate
            if fl is None:
                r = jnp.where(m, rhs - _lap_plain(p, inv2), 0.0)
                p = p - factor * r
            else:
                pm = jnp.where(m, fl, 0.0)
                r = (rhs - _lap_obstacle(p, fl, inv2)) * pm
                p = p - fac * r
        pn = _neumann_plane(p, idx, ext)
        p = pn if gate is None else jnp.where(gate, pn, p)
    return p


def _residual_plane(p, rhs, idx, ext, inv2, fl=None, gate=None):
    m = _interior(idx, ext)
    if gate is not None:
        m = m & gate
    if fl is None:
        return jnp.where(m, rhs - _lap_plain(p, inv2), 0.0)
    pm = jnp.where(m, fl, 0.0)
    return (rhs - _lap_obstacle(p, fl, inv2)) * pm


def _restrict_plane(r, idx, ext):
    """Gather-free 2x full-weighting onto the SAME plane: interior content
    rolls to the origin, the static reshape-mean halves it (the ladder's
    _restrict2/_restrict3 reduction), and the result rolls back behind the
    coarse ghost ring. Returns the coarse rhs plane (zero ghosts — the
    ladder's _embed2)."""
    nd = r.ndim
    rs = r
    for d in range(nd):
        rs = jnp.roll(rs, -1, d)
    resh = []
    for s in r.shape:
        resh += [s // 2, 2]
    c = rs.reshape(*resh).mean(axis=tuple(range(1, 2 * nd, 2)))
    full = lax.dynamic_update_slice(jnp.zeros_like(r), c, (0,) * nd)
    for d in range(nd):
        full = jnp.roll(full, 1, d)
    ext2 = [e // 2 for e in ext]
    return jnp.where(_interior(idx, ext2), full, 0.0)


def _prolong_plane(e):
    """Gather-free 2x piecewise-constant prolongation (the ladder's
    jnp.repeat _prolong2/_prolong3); the caller masks to the fine interior
    — coarse ghost values land strictly outside it."""
    nd = e.ndim
    ec = e
    for d in range(nd):
        ec = jnp.roll(ec, -1, d)
    ec = ec[tuple(slice(0, s // 2) for s in e.shape)]
    f = ec
    for d in range(nd):
        f = jnp.repeat(f, 2, axis=d)
    for d in range(nd):
        f = jnp.roll(f, 1, d)
    return f


# ----------------------------------------------------------------------
# solo cycle: DOWN + UP kernels over a static level plan
# ----------------------------------------------------------------------


def _down_body(*refs, L, nd, n_pre, parities, masked):
    if masked:
        (ext_ref, geo_ref, fl_ref, fac_ref, p_ref, rhs_ref,
         pstk_ref, rstk_ref) = refs
    else:
        ext_ref, geo_ref, p_ref, rhs_ref, pstk_ref, rstk_ref = refs
    p = p_ref[...]
    rhs = rhs_ref[...]
    idx = _iotas(p.shape)
    for l in range(L - 1):
        ext = [ext_ref[l, d] for d in range(nd)]
        inv2 = [geo_ref[l, d] for d in range(nd)]
        factor = geo_ref[l, nd]
        fl = fl_ref[l] if masked else None
        fac = fac_ref[l] if masked else None
        p = _smooth_plane(p, rhs, idx, ext, parities, factor, inv2, n_pre,
                          fl=fl, fac=fac)
        pstk_ref[l] = p
        rstk_ref[l] = rhs
        r = _residual_plane(p, rhs, idx, ext, inv2, fl=fl)
        rhs = _restrict_plane(r, idx, ext)
        p = jnp.zeros_like(p)
    pstk_ref[L - 1] = p
    rstk_ref[L - 1] = rhs


def _up_body(*refs, L, nd, n_post, parities, masked):
    if masked:
        (ext_ref, geo_ref, fl_ref, fac_ref, pstk_ref, rstk_ref,
         pbot_ref, out_ref) = refs
    else:
        ext_ref, geo_ref, pstk_ref, rstk_ref, pbot_ref, out_ref = refs
    e = pbot_ref[...]
    idx = _iotas(e.shape)
    for l in reversed(range(L - 1)):
        ext = [ext_ref[l, d] for d in range(nd)]
        inv2 = [geo_ref[l, d] for d in range(nd)]
        factor = geo_ref[l, nd]
        fl = fl_ref[l] if masked else None
        fac = fac_ref[l] if masked else None
        p = pstk_ref[l]
        rhs = rstk_ref[l]
        f = _prolong_plane(e)
        if masked:
            f = f * fl  # inject into fluid cells only (m.p_mask)
        p = p + jnp.where(_interior(idx, ext), f, 0.0)
        p = _neumann_plane(p, idx, ext)
        p = _smooth_plane(p, rhs, idx, ext, parities, factor, inv2, n_post,
                          fl=fl, fac=fac)
        e = p
    out_ref[...] = e


def make_cycle_kernels(levels, spacings, dtype, n_pre: int = 2,
                       n_post: int = 2, interpret=None,
                       fluid_levels=None, factor_levels=None):
    """Build the two fused-cycle launches for a static level plan.

    levels: [(jl, il), ...] or [(kl, jl, il), ...], finest first, len >= 2
    (the ladder's plan — callers refuse single-level plans via
    plan_why_not). spacings: (dx, dy[, dz]). For obstacle hierarchies pass
    ``fluid_levels`` (per-level (jl+2, il+2)[...] 0/1 flag arrays, ghost
    ring fluid) and ``factor_levels`` (the per-level ObstacleMasks.factor
    interior arrays — baked verbatim so the kernel relaxes with bitwise the
    ladder's precomputed ω=1 factors).

    Returns (down, up, plane_shape):
      down(p_plane, rhs_plane) -> (p_stack, rhs_stack)   [1 launch]
      up(p_stack, rhs_stack, p_bottom_plane) -> p_plane  [1 launch]
    """
    import numpy as np

    if pltpu is None:
        raise RuntimeError("pallas TPU backend unavailable")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _check_dtype(dtype, interpret)
    L = len(levels)
    if L < 2:
        raise ValueError("fused cycle needs a multi-level plan")
    nd = len(levels[0])
    plane = fused_layout(levels[0])
    masked = fluid_levels is not None
    # odd-parity-first is the 3-D sweep order; red (parity 0) first in 2-D
    parities = (1, 0) if nd == 3 else (0, 1)

    ext = jnp.asarray(np.asarray(levels, np.int32))
    geo_rows = []
    for lvl in range(L):
        sp = [s * (2 ** lvl) for s in spacings]
        sq = [s * s for s in sp]
        inv2 = [1.0 / q for q in sq]
        if nd == 2:
            factor = 0.5 * (sq[0] * sq[1]) / (sq[0] + sq[1])
        else:
            factor = 0.5 * (sq[0] * sq[1] * sq[2]) / (
                sq[1] * sq[2] + sq[0] * sq[2] + sq[0] * sq[1])
        geo_rows.append(inv2 + [factor])
    geo = jnp.asarray(np.asarray(geo_rows), dtype)

    stacks = None
    if masked:
        fl_np = np.zeros((L,) + plane)
        fac_np = np.zeros((L,) + plane)
        for lvl, (flu, fac) in enumerate(zip(fluid_levels, factor_levels)):
            flu = np.asarray(flu)
            sl = tuple(slice(0, s) for s in flu.shape)
            fl_np[(lvl,) + sl] = flu.astype(np.float64)  # lint: allow(dtype-policy) host-side mask coeffs
            isl = tuple(slice(1, 1 + s) for s in np.asarray(fac).shape)
            fac_np[(lvl,) + isl] = np.asarray(fac)
        stacks = (jnp.asarray(fl_np, dtype), jnp.asarray(fac_np, dtype))

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    zeros = (0,) * nd

    def _vspec(shape):
        n = len(shape)
        return pl.BlockSpec(shape, lambda i, _n=n: (0,) * _n)

    cp = CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)
    stack_shape = (L,) + plane

    down_call = pl.pallas_call(
        functools.partial(_down_body, L=L, nd=nd, n_pre=n_pre,
                          parities=parities, masked=masked),
        name="mg2d_down",
        grid=(1,),
        in_specs=[smem, smem]
        + ([_vspec(stack_shape)] * 2 if masked else [])
        + [_vspec(plane)] * 2,
        out_specs=[_vspec(stack_shape)] * 2,
        out_shape=[jax.ShapeDtypeStruct(stack_shape, dtype)] * 2,
        compiler_params=cp,
        interpret=interpret,
    )
    up_call = pl.pallas_call(
        functools.partial(_up_body, L=L, nd=nd, n_post=n_post,
                          parities=parities, masked=masked),
        name="mg2d_up",
        grid=(1,),
        in_specs=[smem, smem]
        + ([_vspec(stack_shape)] * 2 if masked else [])
        + [_vspec(stack_shape)] * 2 + [_vspec(plane)],
        out_specs=[_vspec(plane)],
        out_shape=[jax.ShapeDtypeStruct(plane, dtype)],
        compiler_params=cp,
        interpret=interpret,
    )

    if masked:
        fl_stack, fac_stack = stacks

        def down(p_plane, rhs_plane):
            return down_call(ext, geo, fl_stack, fac_stack,
                             p_plane, rhs_plane)

        def up(p_stack, rhs_stack, p_bottom):
            (out,) = up_call(ext, geo, fl_stack, fac_stack,
                             p_stack, rhs_stack, p_bottom)
            return out
    else:

        def down(p_plane, rhs_plane):
            return down_call(ext, geo, p_plane, rhs_plane)

        def up(p_stack, rhs_stack, p_bottom):
            (out,) = up_call(ext, geo, p_stack, rhs_stack, p_bottom)
            return out

    return down, up, plane


# ----------------------------------------------------------------------
# class-lane cycle: the whole V-cycle in ONE launch, level plan from
# call-time scalars (fleet/shapeclass padded-class lanes)
# ----------------------------------------------------------------------


def class_level_max(jmax_c: int, imax_c: int) -> int:
    """Static unroll depth covering every lane a class can pad: an extent
    e yields at most floor(log2(e)) - 1 levels (mg_levels min_size=4)."""
    return max(1, int(math.floor(math.log2(max(8, min(jmax_c, imax_c))))) - 1)


def class_level_plan(jl, il, idx2, idy2, lmax: int, dtype,
                     min_size: int = 4):
    """The mg_levels rule as jnp over the lane's call-time extents: level
    l+1 is live while level l's extents are even and >= 2*min_size.
    Returns (ext (lmax, 3) int32 rows [jl, il, live],
    geo (lmax, 3) dtype rows [idx2, idy2, factor])."""
    jl = jnp.asarray(jl, jnp.int32)
    il = jnp.asarray(il, jnp.int32)
    idx2 = jnp.asarray(idx2, dtype)
    idy2 = jnp.asarray(idy2, dtype)
    live = jnp.asarray(1, jnp.int32)
    ext_rows, geo_rows = [], []
    for lvl in range(lmax):
        scale = jnp.asarray(4.0 ** lvl, dtype)
        i2, j2 = idx2 / scale, idy2 / scale
        ext_rows.append(jnp.stack([jl, il, live]))
        geo_rows.append(jnp.stack([i2, j2, 0.5 / (i2 + j2)]))
        can = ((jl % 2 == 0) & (il % 2 == 0)
               & (jl >= 2 * min_size) & (il >= 2 * min_size))
        live = live * can.astype(jnp.int32)
        jl = jl // 2
        il = il // 2
    return jnp.stack(ext_rows), jnp.stack(geo_rows)


def _class_cycle_body(ext_ref, geo_ref, p_ref, rhs_ref, out_ref, res_ref,
                      *, lmax, n_pre, n_post, n_bottom):
    p = p_ref[...]
    rhs = rhs_ref[...]
    idx = _iotas(p.shape)
    parities = (0, 1)
    p_lv, rhs_lv, exts, geos, lives = [], [], [], [], []
    for l in range(lmax):
        ext = [ext_ref[l, 0], ext_ref[l, 1]]
        inv2 = [geo_ref[l, 0], geo_ref[l, 1]]
        factor = geo_ref[l, 2]
        live = ext_ref[l, 2] > 0
        p = _smooth_plane(p, rhs, idx, ext, parities, factor, inv2, n_pre,
                          gate=live)
        p_lv.append(p)
        rhs_lv.append(rhs)
        exts.append(ext)
        geos.append((inv2, factor))
        lives.append(live)
        r = _residual_plane(p, rhs, idx, ext, inv2, gate=live)
        rhs = _restrict_plane(r, idx, ext)
        p = jnp.zeros_like(p)
    e = jnp.zeros_like(p)
    for l in reversed(range(lmax)):
        ext = exts[l]
        inv2, factor = geos[l]
        live = lives[l]
        child = lives[l + 1] if l + 1 < lmax else jnp.asarray(False)
        is_bottom = live & jnp.logical_not(child)
        p = p_lv[l]
        rhs = rhs_lv[l]
        f = _prolong_plane(e)
        p = p + jnp.where(_interior(idx, ext) & child, f, 0.0)
        p = jnp.where(child, _neumann_plane(p, idx, ext), p)
        # the deepest live level replaces the direct solve with extra
        # smoothing — the class cycle's in-kernel bottom
        p = _smooth_plane(p, rhs, idx, ext, parities, factor, inv2,
                          n_bottom, gate=is_bottom)
        p = _smooth_plane(p, rhs, idx, ext, parities, factor, inv2,
                          n_post, gate=live)
        e = jnp.where(live, p, e)
    ext0 = [ext_ref[0, 0], ext_ref[0, 1]]
    inv20 = [geo_ref[0, 0], geo_ref[0, 1]]
    r = _residual_plane(e, rhs_lv[0], idx, ext0, inv20)
    res_ref[0, 0] = jnp.sum(r * r)
    out_ref[...] = e


def make_class_cycle_2d(jmax_c: int, imax_c: int, dtype, n_pre: int = 2,
                        n_post: int = 2, n_bottom: int = 8,
                        interpret=None):
    """One-launch dynamic-extent V-cycle for a padded shape class.

    Returns (cycle, plane_shape, lmax) with
    ``cycle(p_plane, rhs_plane, ext, geo) -> (p_plane, res_sumsq)`` where
    (ext, geo) come from class_level_plan at the lane's live extents. The
    fine-level residual sum-of-squares rides back through SMEM so the
    convergence loop costs no extra launch."""
    if pltpu is None:
        raise RuntimeError("pallas TPU backend unavailable")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _check_dtype(dtype, interpret)
    lmax = class_level_max(jmax_c, imax_c)
    plane = fused_layout((jmax_c, imax_c))

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    call = pl.pallas_call(
        functools.partial(_class_cycle_body, lmax=lmax, n_pre=n_pre,
                          n_post=n_post, n_bottom=n_bottom),
        name="mg2d_class_cycle",
        grid=(1,),
        in_specs=[smem, smem,
                  pl.BlockSpec(plane, lambda i: (0, 0)),
                  pl.BlockSpec(plane, lambda i: (0, 0))],
        out_specs=[
            pl.BlockSpec(plane, lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(plane, dtype),
            jax.ShapeDtypeStruct((1, 1), dtype),
        ],
        compiler_params=CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )

    def cycle(p_plane, rhs_plane, ext, geo):
        p_out, res = call(ext, geo, p_plane, rhs_plane)
        return p_out, res[0, 0]

    return cycle, plane, lmax


# ----------------------------------------------------------------------
# probe — one-time real-backend smoke (the probe_pallas contract)
# ----------------------------------------------------------------------

_PROBE_OK = None


def probe_mg_fused() -> bool:
    """Compile and run a tiny two-level fused cycle on the real backend
    once per process; any failure (missing Mosaic op, lowering error)
    is raised on a TPU backend (utils/dispatch.probe_failed)."""
    global _PROBE_OK
    if _PROBE_OK is None:
        try:
            levels = [(16, 16), (8, 8)]
            down, up, plane = make_cycle_kernels(
                levels, (1.0 / 16, 1.0 / 16), jnp.float32,
                interpret=False,
            )
            p = pad_plane(jnp.zeros((18, 18), jnp.float32), plane)
            r = pad_plane(jnp.ones((18, 18), jnp.float32), plane)
            pstk, rstk = down(p, r)
            out = up(pstk, rstk, jnp.zeros_like(p))
            jax.block_until_ready(out)
            _PROBE_OK = True
        except Exception as exc:  # lint: allow(broad-except) — probe contract: raise on TPU, report unavailable elsewhere
            from ..utils.dispatch import probe_failed

            _PROBE_OK = probe_failed("the fused MG cycle kernels", exc)
    return _PROBE_OK
