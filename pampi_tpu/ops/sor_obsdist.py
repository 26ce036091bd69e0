"""Per-shard Pallas kernel for the DISTRIBUTED flag-masked (obstacle) SOR.

Completes the kernel-per-shard family (ops/sor_qdist.py quarters 2-D,
ops/sor_odist.py octants 3-D): the obstacle configs use the masked
CHECKERBOARD layout (compressed layouts don't carry flag fields), so this
is the masked mode of sor_pallas._tblock_kernel generalized to a shard of a
("j","i") mesh — masks from GLOBAL coordinates via scalar prefetch, updates
clipped to the stored block with a frozen outermost ring, owned-only
residual, and per-direction fluid coefficients computed in-kernel from the
shard's deep flag block (identical flag VALUES on every shard that sees a
cell, so redundant halo recompute stays consistent — the CA discipline of
ops/obstacle.make_dist_obstacle_solver, whose jnp path ca_rb_iters_obstacle
is this kernel's twin).

Layout: the (jl+2H, il+2H) deep-halo extended block (H = 2n grid cells) in
sor_pallas's padded layout (pad_array with halo = tblock_halo(n)); cell
(a, b) of the extended block holds global extended index
(a - H + joff + 1, b - H + ioff + 1) — ghost row gj = 0 is the physical
wall. One call performs n red-black iterations + globally-gated Neumann
wall refresh — exactly the validity one depth-2n halo_exchange provides.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .sor_pallas import (
    CompilerParams,
    VMEM_LIMIT_BYTES,
    _check_dtype,
    masked_stencil_ops,
    padded_width,
    pick_block_rows_tblock,
    pltpu,
    rb_inner_sweeps,
    tblock_feasible,
    tblock_halo,
    tblock_vmem_bytes,
)


def _obsdist_kernel(
    sref,   # SMEM scalar prefetch: int32[2] = (joff, ioff) grid offsets
    p_in,   # ANY padded deep block
    rhs,    # ANY
    flg,    # ANY padded deep fluid flags (0/1)
    p_out,  # ANY
    res,    # SMEM (1, 1)
    pw2,    # VMEM (2, br+2h, wp)
    rw2,    # VMEM (2, br+2h, wp)
    fw2,    # VMEM (2, br+2h, wp)
    ob2,    # VMEM (2, br, wp)
    vacc,   # VMEM (1, wp)
    ld_sem,  # DMA (2, 3)
    st_sem,  # DMA (2,)
    *,
    n_inner: int,
    block_rows: int,
    nblocks: int,
    gjmax: int,
    gimax: int,
    jl: int,
    il: int,
    H: int,      # deep-halo depth in grid cells (= 2*n_inner)
    halo: int,   # window halo (>= H, sublane-aligned)
    omega: float,
    idx2: float,
    idy2: float,
    loop_sweeps: bool = False,
):
    b = pl.program_id(0)
    br = block_rows
    h = halo
    slot = b % 2
    nslot = (b + 1) % 2
    joff = sref[0]
    ioff = sref[1]

    def load(k, s):
        return [
            pltpu.make_async_copy(
                p_in.at[pl.ds(k * br, br + 2 * h), :], pw2.at[s],
                ld_sem.at[s, 0]),
            pltpu.make_async_copy(
                rhs.at[pl.ds(k * br, br + 2 * h), :], rw2.at[s],
                ld_sem.at[s, 1]),
            pltpu.make_async_copy(
                flg.at[pl.ds(k * br, br + 2 * h), :], fw2.at[s],
                ld_sem.at[s, 2]),
        ]

    def store(k, s):
        return pltpu.make_async_copy(
            ob2.at[s], p_out.at[pl.ds(h + k * br, br)], st_sem.at[s]
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

    # padded row of window cell (w, c): rho = b*br + w; local deep index
    # a = rho - h; global extended index gj = a - H + joff + 1, gi likewise
    rho = b * br + jax.lax.broadcasted_iota(jnp.int32, p.shape, 0)
    ccol = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
    a_j = rho - h
    a_i = ccol
    gj = a_j - H + joff + 1
    gi = a_i - H + ioff + 1
    interior = (gj >= 1) & (gj <= gjmax) & (gi >= 1) & (gi <= gimax)
    # freeze the outermost stored ring (its neighbours are dead padding —
    # same CA equivalence as parallel/quarters_dist.q_masks)
    valid_upd = (
        (a_j >= 1) & (a_j <= jl + 2 * H - 2)
        & (a_i >= 1) & (a_i <= il + 2 * H - 2)
    )
    fluid = fl != 0
    red = interior & (((gi + gj) % 2) == 0) & fluid & valid_upd
    black = interior & (((gi + gj) % 2) == 1) & fluid & valid_upd
    # globally-gated Neumann wall refresh, tangentially clipped
    tan_i = (gi >= 1) & (gi <= gimax)
    tan_j = (gj >= 1) & (gj <= gjmax)
    row_ghost_lo = (gj == 0) & tan_i & valid_upd
    row_ghost_hi = (gj == gjmax + 1) & tan_i & valid_upd
    col_ghost_lo = (gi == 0) & tan_j & valid_upd
    col_ghost_hi = (gi == gimax + 1) & tan_j & valid_upd
    # owned region for the residual (static layout bounds)
    owned = (
        (a_j >= H) & (a_j < H + jl) & (a_i >= H) & (a_i < H + il)
    )

    # shared masked-stencil math + inner loop (sor_pallas — one home, so
    # this kernel and _tblock_kernel's masked mode cannot drift)
    fac, lap = masked_stencil_ops(fl, idx2, idy2, omega)
    p, r_red, r_blk = rb_inner_sweeps(
        p, rw, n_inner, red, black, fac, lap,
        (row_ghost_lo, row_ghost_hi, col_ghost_lo, col_ghost_hi),
        loop=loop_sweeps,
    )

    @pl.when(b >= 2)
    def _():
        store(b - 2, slot).wait()

    ob2[slot] = p[h: h + br, :]
    store(b, slot).start()

    ro = jnp.where(owned, r_red * r_red + r_blk * r_blk, 0.0)
    vacc[...] += jnp.sum(ro[h: h + br, :], axis=0, keepdims=True)

    @pl.when(b == nblocks - 1)
    def _():
        res[0, 0] += jnp.sum(vacc[...])
        store(b, slot).wait()
        if nblocks > 1:
            store(b - 1, nslot).wait()


def make_rb_iters_obsdist(jmax, imax, jl, il, n, dx, dy, omega, dtype, *,
                          interpret: bool | None = None,
                          block_rows: int | None = None,
                          ragged: bool = False,
                          loop_sweeps: bool = False):
    """Build `(offs_i32[2], p_padded, rhs_padded, flg_padded) ->
    (p_padded', owned res sum of last iter)` performing n red-black
    eps-coefficient iterations on the padded (jl+2H, il+2H) deep block
    (H = ca_halo(n, ragged) = 2n, or 2n+1 on ragged decompositions — the
    wall-ghost refresh of a trailing/dead shard consumes one extra layer,
    parallel/stencil2d.ca_halo; pad with sor_pallas.pad_array(x,
    block_rows, halo)). The kernel body is global-coordinate gated
    throughout, so ragged layouts need no body change — dead cells beyond
    the global ghost ring sit outside `interior` and carry zero flags.
    Returns (rb_iters, block_rows, halo). offs = [joff, ioff] grid
    offsets. block_rows overrides the picker (tests use it to force the
    multi-block DMA pipeline on small geometries)."""
    from ..parallel.stencil2d import ca_halo

    if pltpu is None:
        return None, 0, 0
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if loop_sweeps and not interpret:
        # the looped (scf.for) kernel is bitwise-correct in interpret mode
        # but CRASHES the production Mosaic compiler at any depth on the
        # current toolchain (round-5 measured outcome, see the depth note
        # below) — a compile-time crash is not catchable by the dispatch
        # backoff, so refuse here instead of letting the opt-in reach the
        # real compiler (ADVICE round-5 item)
        raise ValueError(
            "loop_sweeps=True is an interpret-mode-only form: the scf.for "
            "sweep loop crashes the production Mosaic compiler (round-5 "
            "record, results/obsdist2048.json); use the unrolled default "
            "on TPU"
        )
    _check_dtype(dtype, interpret)
    H = ca_halo(n, ragged)
    ext_j = jl + 2 * H  # logical rows of the deep block incl. its "+2"
    ext_i = il + 2 * H
    h = tblock_halo(n, dtype)
    if h < H:  # ragged's +1 layer crossed a sublane-alignment boundary
        from .sor_pallas import _align

        a = _align(dtype)
        h = -(-H // a) * a
    if block_rows is None:
        block_rows = pick_block_rows_tblock(ext_j - 2, ext_i - 2, dtype, n)
    wp = padded_width(ext_i - 2)
    itemsize = jnp.dtype(dtype).itemsize
    if not tblock_feasible(block_rows, h, wp, itemsize, masked=True):
        raise ValueError(
            f"obstacle-dist scratch {tblock_vmem_bytes(block_rows, h, wp, itemsize, True) >> 20} MiB "
            f"exceeds the VMEM budget (block_rows={block_rows}, h={h}, "
            f"wp={wp}); reduce tpu_ca_inner or the shard width"
        )
    # Mosaic's STACK for the unrolled n-sweep body scales with n in a way
    # the declared-scratch formula cannot see (each unrolled sweep keeps
    # window-sized temporaries live). Empirical anchor on v5e f32 at a
    # 512x2048 shard: n=16 OOMs the scoped vmem at compile (122.05M vs
    # 117.53M) while n=8 compiles and runs; ~(n+8) live window-sized
    # buffers reproduces both points. Raise a CATCHABLE error so the
    # dispatcher can back off the depth instead of crashing at compile.
    #
    # Round 5 tried the obvious fix — WINDOW the sweeps through scf.for
    # (rb_inner_sweeps(loop=True)), whose live set is one sweep's
    # regardless of n. MEASURED OUTCOME (VERDICT r4 item 7, the
    # "documented loss" arm): the looped kernel is bitwise-correct in
    # interpret mode (tests/test_quarters_dist.py windowed-sweeps test)
    # but CRASHES the production Mosaic compiler at ANY depth on the
    # current toolchain (tpu_compile_helper subprocess exit 1 at n=8 and
    # n=16, 512x2048 shard, same session in which the unrolled n=8 kernel
    # measured 21.0G). So `loop_sweeps` stays an EXPLICIT opt-in for
    # interpret/tests, auto mode keeps the unrolled form + depth backoff,
    # and the depth-16 co-tune remains closed off by the toolchain, not by
    # this kernel's structure.
    window = (block_rows + 2 * h) * wp * itemsize
    if not loop_sweeps and window * (n + 8) > VMEM_LIMIT_BYTES:
        raise ValueError(
            f"obstacle-dist unrolled-sweep stack estimate "
            f"{(window * (n + 8)) >> 20} MiB exceeds the VMEM budget at "
            f"depth n={n} (window {window >> 20} MiB); reduce the depth"
        )
    nblocks = -(-ext_j // block_rows)
    rp = nblocks * block_rows + 2 * h
    kernel = functools.partial(
        _obsdist_kernel,
        n_inner=n,
        block_rows=block_rows,
        nblocks=nblocks,
        gjmax=jmax,
        gimax=imax,
        jl=jl,
        il=il,
        H=H,
        halo=h,
        omega=omega,
        idx2=1.0 / (dx * dx),
        idy2=1.0 / (dy * dy),
        loop_sweeps=loop_sweeps,
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
            pltpu.VMEM((2, block_rows + 2 * h, wp), dtype),
            pltpu.VMEM((2, block_rows + 2 * h, wp), dtype),
            pltpu.VMEM((2, block_rows + 2 * h, wp), dtype),
            pltpu.VMEM((2, block_rows, wp), dtype),
            pltpu.VMEM((1, wp), dtype),
            pltpu.SemaphoreType.DMA((2, 3)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    call = pl.pallas_call(
        kernel,
        name="sor2d_rb_obsdist",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((rp, wp), dtype),
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

    return rb_iters, block_rows, h


def padded_deep_exchange(xp, comm, H, row0, ext_j, ext_i):
    """halo_exchange(depth=H) operating directly on the PADDED layout, so
    the solve loop can carry the padded array and pay pad/unpad once per
    SOLVE instead of once per body iteration (the dominant envelope cost at
    small shard sizes). Logical deep-block rows live at padded rows
    [row0, row0+ext_j), cols at [0, ext_i); same ppermute choreography and
    PROC_NULL masking as parallel/comm._exchange_axis with static offsets."""
    from jax import lax

    from ..parallel.comm import _nbr_perm

    nper = comm.axis_size("j")
    if nper > 1:
        idx = lax.axis_index("j")
        lo_g, hi_g = row0, row0 + ext_j - H
        lo_o, hi_o = row0 + H, row0 + ext_j - 2 * H
        from_lo = lax.ppermute(
            xp[hi_o:hi_o + H], "j", _nbr_perm(nper, True, False)
        )
        from_hi = lax.ppermute(
            xp[lo_o:lo_o + H], "j", _nbr_perm(nper, False, False)
        )
        from_lo = jnp.where(idx > 0, from_lo, xp[lo_g:lo_g + H])
        from_hi = jnp.where(idx < nper - 1, from_hi, xp[hi_g:hi_g + H])
        xp = xp.at[lo_g:lo_g + H].set(from_lo)
        xp = xp.at[hi_g:hi_g + H].set(from_hi)

    nper = comm.axis_size("i")
    if nper > 1:
        idx = lax.axis_index("i")
        lo_g, hi_g = 0, ext_i - H
        lo_o, hi_o = H, ext_i - 2 * H
        from_lo = lax.ppermute(
            xp[:, hi_o:hi_o + H], "i", _nbr_perm(nper, True, False)
        )
        from_hi = lax.ppermute(
            xp[:, lo_o:lo_o + H], "i", _nbr_perm(nper, False, False)
        )
        from_lo = jnp.where(idx > 0, from_lo, xp[:, lo_g:lo_g + H])
        from_hi = jnp.where(idx < nper - 1, from_hi, xp[:, hi_g:hi_g + H])
        xp = xp.at[:, lo_g:lo_g + H].set(from_lo)
        xp = xp.at[:, hi_g:hi_g + H].set(from_hi)
    return xp
