"""Per-shard Pallas kernel for the DISTRIBUTED 3-D flag-masked (obstacle)
SOR — the 3-D companion of ops/sor_obsdist.py, completing the
kernel-per-shard family over every distributed pressure-solve surface
(quarters 2-D, octants 3-D, masked 2-D, masked 3-D).

The masked mode of sor3d_pallas._tblock3d_kernel generalized to a shard of
a ("k","j","i") mesh: global-coordinate masks via three scalar-prefetch
offsets, frozen outermost stored ring, owned-only residual, per-direction
fluid coefficients from the shard's deep flag block (shared math:
sor3d_pallas.masked_stencil_ops_3d / rb_inner_sweeps_3d). jnp twin:
ops/obstacle3d.ca_rb_iters_obstacle_3d.

Layout: the (kl+2H, jl+2H, il+2H) deep-halo extended block (H = 2n) in
sor3d_pallas's padded layout (pad_array_3d; block axis k, window halo
h = 2n planes). Cell (a, b, c) holds global extended index
(a - H + koff + 1, b - H + joff + 1, c - H + ioff + 1)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .sor3d_pallas import (
    VMEM_LIMIT_BYTES,
    CompilerParams,
    _check_dtype,
    masked_stencil_ops_3d,
    padded_ji,
    pick_block_k,
    pltpu,
    rb_inner_sweeps_3d,
    tblock3d_halo,
)


def _obsdist3d_kernel(
    sref,   # SMEM scalar prefetch: int32[3] = (koff, joff, ioff)
    p_in, rhs, flg, p_out, res,
    pw2, rw2, fw2, ob2, vacc, ld_sem, st_sem,
    *,
    n_inner: int,
    block_k: int,
    nblocks: int,
    gkmax: int, gjmax: int, gimax: int,
    kl: int, jl: int, il: int,
    H: int,
    halo: int,
    omega: float,
    idx2: float, idy2: float, idz2: float,
):
    b = pl.program_id(0)
    bk = block_k
    h = halo
    slot = b % 2
    nslot = (b + 1) % 2
    koff, joff, ioff = sref[0], sref[1], sref[2]

    def load(k, s):
        return [
            pltpu.make_async_copy(
                p_in.at[pl.ds(k * bk, bk + 2 * h)], pw2.at[s],
                ld_sem.at[s, 0]),
            pltpu.make_async_copy(
                rhs.at[pl.ds(k * bk, bk + 2 * h)], rw2.at[s],
                ld_sem.at[s, 1]),
            pltpu.make_async_copy(
                flg.at[pl.ds(k * bk, bk + 2 * h)], fw2.at[s],
                ld_sem.at[s, 2]),
        ]

    def store(k, s):
        return pltpu.make_async_copy(
            ob2.at[s], p_out.at[pl.ds(h + k * bk, bk)], st_sem.at[s]
        )

    @pl.when(b == 0)
    def _():
        res[0, 0] = jnp.zeros((), res.dtype)
        vacc[...] = jnp.zeros_like(vacc)
        for c in load(0, 0):
            c.start()

    @pl.when(b + 1 < nblocks)
    def _():
        for c in load(b + 1, nslot):
            c.start()

    for c in load(b, slot):
        c.wait()

    p = pw2[slot]
    rw = rw2[slot]
    fl = fw2[slot]

    # padded plane of window cell (wk, wj, wi): s = b*bk + wk; local deep
    # index a_k = s - h; global extended gk = a_k - H + koff + 1 (j/i have
    # no kernel padding offset: a_j = wj, a_i = wi)
    s_k = b * bk + jax.lax.broadcasted_iota(jnp.int32, p.shape, 0)
    a_k = s_k - h
    a_j = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
    a_i = jax.lax.broadcasted_iota(jnp.int32, p.shape, 2)
    gk = a_k - H + koff + 1
    gj = a_j - H + joff + 1
    gi = a_i - H + ioff + 1
    interior = (
        (gk >= 1) & (gk <= gkmax)
        & (gj >= 1) & (gj <= gjmax)
        & (gi >= 1) & (gi <= gimax)
    )
    valid_upd = (
        (a_k >= 1) & (a_k <= kl + 2 * H - 2)
        & (a_j >= 1) & (a_j <= jl + 2 * H - 2)
        & (a_i >= 1) & (a_i <= il + 2 * H - 2)
    )
    fluid = fl != 0
    par = (gi + gj + gk) % 2
    odd = interior & (par == 1) & fluid & valid_upd
    even = interior & (par == 0) & fluid & valid_upd
    tan_ji = (gj >= 1) & (gj <= gjmax) & (gi >= 1) & (gi <= gimax)
    tan_ki = (gk >= 1) & (gk <= gkmax) & (gi >= 1) & (gi <= gimax)
    tan_kj = (gk >= 1) & (gk <= gkmax) & (gj >= 1) & (gj <= gjmax)
    front = (gk == 0) & tan_ji & valid_upd
    back = (gk == gkmax + 1) & tan_ji & valid_upd
    bottom = (gj == 0) & tan_ki & valid_upd
    top = (gj == gjmax + 1) & tan_ki & valid_upd
    left = (gi == 0) & tan_kj & valid_upd
    right = (gi == gimax + 1) & tan_kj & valid_upd
    owned = (
        (a_k >= H) & (a_k < H + kl)
        & (a_j >= H) & (a_j < H + jl)
        & (a_i >= H) & (a_i < H + il)
    )

    fac, lap = masked_stencil_ops_3d(fl, idx2, idy2, idz2, omega)
    p, r_odd, r_evn = rb_inner_sweeps_3d(
        p, rw, n_inner, odd, even, fac, lap,
        (front, back, bottom, top, left, right),
    )

    @pl.when(b >= 2)
    def _():
        store(b - 2, slot).wait()

    ob2[slot] = p[h: h + bk]
    store(b, slot).start()

    ro = jnp.where(owned, r_odd * r_odd + r_evn * r_evn, 0.0)
    vacc[...] += jnp.sum(ro[h: h + bk], axis=(0, 1))[None, :]

    @pl.when(b == nblocks - 1)
    def _():
        res[0, 0] += jnp.sum(vacc[...])
        store(b, slot).wait()
        if nblocks > 1:
            store(b - 1, nslot).wait()


def make_rb_iters_obsdist_3d(kmax, jmax, imax, kl, jl, il, n, dx, dy, dz,
                             omega, dtype, *,
                             interpret: bool | None = None,
                             block_k: int | None = None):
    """Build `(offs_i32[3], p_padded, rhs_padded, flg_padded) ->
    (p_padded', owned res sum of last iter)` performing n 3-D red-black
    eps-coefficient iterations on the padded (kl+2H, jl+2H, il+2H) deep
    block (pad with sor3d_pallas.pad_array_3d(x, block_k, n)). Returns
    (rb_iters, block_k). offs = [koff, joff, ioff] grid offsets."""
    if pltpu is None:
        return None, 0
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _check_dtype(dtype, interpret)
    H = 2 * n
    ext_k, ext_j, ext_i = kl + 2 * H, jl + 2 * H, il + 2 * H
    h = tblock3d_halo(n)
    if block_k is None:
        block_k = pick_block_k(ext_k - 2, ext_j - 2, ext_i - 2, dtype, n,
                               masked=True)
    jp, ip = padded_ji(ext_j - 2, ext_i - 2, dtype)
    plane = jp * ip * jnp.dtype(dtype).itemsize
    # masked resident planes: 15*bk + 18*h (pick_block_k's accounting)
    if (15 * block_k + 18 * h) * plane > VMEM_LIMIT_BYTES // 2:
        raise ValueError(
            f"obstacle-dist-3d scratch exceeds the VMEM budget "
            f"(block_k={block_k}, h={h}, plane={jp}x{ip}); reduce "
            "tpu_ca_inner or the shard size"
        )
    from .sor3d_pallas import block_k_degenerate

    if block_k_degenerate(block_k, ext_k - 2, n):
        # the budget (not the grid) forced block_k below the halo depth:
        # >3x redundant halo recompute per grid step — the dispatcher
        # should take the jnp CA path instead of a pathological kernel
        raise ValueError(
            f"obstacle-dist-3d block_k={block_k} degenerate below halo "
            f"h={h} on this shard plane ({jp}x{ip}); jnp path is faster"
        )
    nblocks = -(-ext_k // block_k)
    kp = nblocks * block_k + 2 * h
    kernel = functools.partial(
        _obsdist3d_kernel,
        n_inner=n, block_k=block_k, nblocks=nblocks,
        gkmax=kmax, gjmax=jmax, gimax=imax,
        kl=kl, jl=jl, il=il, H=H, halo=h, omega=omega,
        idx2=1.0 / (dx * dx), idy2=1.0 / (dy * dy), idz2=1.0 / (dz * dz),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nblocks,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, block_k + 2 * h, jp, ip), dtype),
            pltpu.VMEM((2, block_k + 2 * h, jp, ip), dtype),
            pltpu.VMEM((2, block_k + 2 * h, jp, ip), dtype),
            pltpu.VMEM((2, block_k, jp, ip), dtype),
            pltpu.VMEM((1, ip), dtype),
            pltpu.SemaphoreType.DMA((2, 3)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    call = pl.pallas_call(
        kernel,
        name="sor3d_rb_obsdist",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((kp, jp, ip), dtype),
            jax.ShapeDtypeStruct((1, 1), dtype),
        ],
        compiler_params=CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES
        ),
        interpret=interpret,
    )

    def rb_iters(offs, p_padded, rhs_padded, flg_padded):
        p_padded, r = call(offs, p_padded, rhs_padded, flg_padded)
        return p_padded, r[0, 0]

    return rb_iters, block_k


def padded_deep_exchange_3d(xp, comm, H, k0, ext_k, ext_j, ext_i):
    """halo_exchange(depth=H) on the PADDED 3-D layout (pad_array_3d):
    logical k-planes at [k0, k0+ext_k), j at [0, ext_j), i at [0, ext_i) —
    the 3-D twin of sor_obsdist.padded_deep_exchange."""
    from jax import lax

    from ..parallel.comm import _nbr_perm

    for axis, name, off, ext in (
        (0, "k", k0, ext_k), (1, "j", 0, ext_j), (2, "i", 0, ext_i)
    ):
        nper = comm.axis_size(name)
        if nper == 1:
            continue
        idx = lax.axis_index(name)
        lo_g, hi_g = off, off + ext - H
        lo_o, hi_o = off + H, off + ext - 2 * H

        def sl(start):
            return lax.slice_in_dim(xp, start, start + H, axis=axis)

        from_lo = lax.ppermute(sl(hi_o), name, _nbr_perm(nper, True, False))
        from_hi = lax.ppermute(sl(lo_o), name, _nbr_perm(nper, False, False))
        from_lo = jnp.where(idx > 0, from_lo, sl(lo_g))
        from_hi = jnp.where(idx < nper - 1, from_hi, sl(hi_g))
        xp = lax.dynamic_update_slice_in_dim(xp, from_lo, lo_g, axis=axis)
        xp = lax.dynamic_update_slice_in_dim(xp, from_hi, hi_g, axis=axis)
    return xp
