"""Per-shard Pallas kernel for the DISTRIBUTED quarter-layout red-black SOR.

The production multi-chip hot kernel (≙ the reference's per-rank SOR kernel,
assignment-5/ex5-nazifkar/src/solver.c:586-655): the temporal-blocked quarter
kernel of ops/sor_pallas.make_rb_iter_tblock_quarters, generalized to a shard
of a ("j","i") mesh — masks come from GLOBAL quarter coordinates via two
scalar-prefetch offsets (qoff_j, qoff_i) instead of static bounds, updates
are clipped to the shard's stored logical region, and the residual counts
OWNED cells only (ghost cells are redundantly recomputed by both neighbours
— parallel/quarters_dist.py has the layout derivation and the jnp twin this
kernel must match bitwise in interpret mode).

One call performs g.n red-black iterations (+ the globally-gated Neumann
wall refresh between iterations) in a single HBM sweep — exactly the
validity a depth-n q_exchange provides, so the distributed convergence loop
is: exchange, kernel, psum(residual), repeat.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..parallel.quarters_dist import QGeom, SLOT_PARITY
from .sor_pallas import (
    CompilerParams,
    VMEM_LIMIT_BYTES,
    _check_dtype,
    pltpu,
    quarters_feasible,
    quarters_vmem_bytes,
)


def _qdist_kernel(
    sref,   # SMEM scalar prefetch: int32[2] = (qoff_j, qoff_i)
    p_in,   # ANY (4, rp, w2p) stacked stored plane [R0, R1, B0, B1]
    rhs,    # ANY (4, rp, w2p)
    p_out,  # ANY (4, rp, w2p)
    res,    # SMEM (1, 1) owned-residual accumulator
    pw2,    # VMEM (2, 4, brq+2h, w2p) double-buffered p windows
    rw2,    # VMEM (2, 4, brq+2h, w2p)
    ob2,    # VMEM (2, 4, brq, w2p) out bands
    vacc,   # VMEM (1, w2p) per-lane residual accumulator
    ld_sem,  # DMA (2, 8)
    st_sem,  # DMA (2, 4)
    *,
    g: QGeom,
    factor: float,
    idx2: float,
    idy2: float,
):
    b = pl.program_id(0)
    brq = g.brq
    h = g.h
    slot = b % 2
    nslot = (b + 1) % 2
    qoff_j = sref[0]
    qoff_i = sref[1]

    def load(k, s):
        copies = []
        for qi in range(4):
            copies.append(pltpu.make_async_copy(
                p_in.at[qi, pl.ds(k * brq, brq + 2 * h), :],
                pw2.at[s, qi], ld_sem.at[s, qi]))
            copies.append(pltpu.make_async_copy(
                rhs.at[qi, pl.ds(k * brq, brq + 2 * h), :],
                rw2.at[s, qi], ld_sem.at[s, 4 + qi]))
        return copies

    def store(k, s):
        return [pltpu.make_async_copy(
            ob2.at[s, qi], p_out.at[qi, pl.ds(h + k * brq, brq), :],
            st_sem.at[s, qi]) for qi in range(4)]

    @pl.when(b == 0)
    def _():
        res[0, 0] = jnp.zeros((), p_out.dtype)
        vacc[...] = jnp.zeros_like(vacc)
        for c in load(0, 0):
            c.start()

    @pl.when(b + 1 < g.nblocks)
    def _():
        for c in load(b + 1, nslot):
            c.start()

    for c in load(b, slot):
        c.wait()

    R0, R1, B0, B1 = (pw2[slot, qi] for qi in range(4))
    F0, F1, G0, G1 = (rw2[slot, qi] for qi in range(4))

    # stored row of window cell (w, c): rho = b*brq + w; logical lam = rho-h;
    # global quarter coords gqr = lam - n + qoff_j, gqc = c - n + qoff_i
    # (parallel/quarters_dist.q_masks — keep the formulas in lockstep)
    rho = b * brq + jax.lax.broadcasted_iota(jnp.int32, R0.shape, 0)
    ccol = jax.lax.broadcasted_iota(jnp.int32, R0.shape, 1)
    lam = rho - h
    gqr = lam - g.n + qoff_j
    gqc = ccol - g.n + qoff_i
    valid = (lam >= 0) & (lam < g.jq) & (ccol >= 0) & (ccol < g.iq)
    # freeze the outermost stored ring (parallel/quarters_dist.q_masks)
    valid_upd = (
        (lam >= 1) & (lam <= g.jq - 2) & (ccol >= 1) & (ccol <= g.iq - 2)
    )

    def row_int(pr):
        if pr == 0:
            return (gqr >= 1) & (gqr <= g.jmax // 2)
        return (gqr >= 0) & (gqr <= g.jmax // 2 - 1)

    def col_int(pc):
        if pc == 0:
            return (gqc >= 1) & (gqc <= g.imax // 2)
        return (gqc >= 0) & (gqc <= g.imax // 2 - 1)

    m_upd = [row_int(pr) & col_int(pc) & valid_upd for pr, pc in SLOT_PARITY]
    row_lo_pc0 = (gqr == 0) & col_int(0) & valid
    row_lo_pc1 = (gqr == 0) & col_int(1) & valid
    row_hi_pc0 = (gqr == g.jmax // 2) & col_int(0) & valid
    row_hi_pc1 = (gqr == g.jmax // 2) & col_int(1) & valid
    col_lo_pr0 = (gqc == 0) & row_int(0) & valid
    col_lo_pr1 = (gqc == 0) & row_int(1) & valid
    col_hi_pr0 = (gqc == g.imax // 2) & row_int(0) & valid
    col_hi_pr1 = (gqc == g.imax // 2) & row_int(1) & valid
    # owned region (residual accounting; static layout bounds)
    own = []
    for pr, pc in SLOT_PARITY:
        osr = g.row_base + (1 if pr == 0 else 0)
        osc = g.col_base + (1 if pc == 0 else 0)
        own.append(
            (rho >= osr) & (rho < osr + g.jl // 2)
            & (ccol >= osc) & (ccol < osc + g.il // 2)
        )

    def upd(center, rhs_q, w, e, s, n_, mask):
        r = rhs_q - ((e - 2.0 * center + w) * idx2
                     + (n_ - 2.0 * center + s) * idy2)
        rm = jnp.where(mask, r, jnp.zeros_like(r))
        return center - factor * rm, rm

    def east(x):
        return jnp.roll(x, -1, axis=1)

    def west(x):
        return jnp.roll(x, 1, axis=1)

    def north(x):
        return jnp.roll(x, -1, axis=0)

    def south(x):
        return jnp.roll(x, 1, axis=0)

    r0 = r1 = r2 = r3 = None
    for _ in range(g.n):
        R0, r0 = upd(R0, F0, west(B0), B0, south(B1), B1, m_upd[0])
        R1, r1 = upd(R1, F1, B1, east(B1), B0, north(B0), m_upd[1])
        B0, r2 = upd(B0, G0, R0, east(R0), south(R1), R1, m_upd[2])
        B1, r3 = upd(B1, G1, west(R1), R1, R0, north(R0), m_upd[3])
        R0 = jnp.where(row_lo_pc0, B1, R0)
        B0 = jnp.where(row_lo_pc1, R1, B0)
        R1 = jnp.where(row_hi_pc1, B0, R1)
        B1 = jnp.where(row_hi_pc0, R0, B1)
        R0 = jnp.where(col_lo_pr0, B0, R0)
        B1 = jnp.where(col_lo_pr1, R1, B1)
        B0 = jnp.where(col_hi_pr0, R0, B0)
        R1 = jnp.where(col_hi_pr1, B1, R1)

    @pl.when(b >= 2)
    def _():
        for c in store(b - 2, slot):
            c.wait()

    for qi, arr in enumerate((R0, R1, B0, B1)):
        ob2[slot, qi] = arr[h: h + brq, :]
    for c in store(b, slot):
        c.start()

    # residual of the final iteration, OWNED cells only (ghosts are the
    # neighbours' cells; where-select so ghost garbage can't poison via 0·inf)
    acc = jnp.zeros_like(vacc[...])
    for rq, ow in zip((r0, r1, r2, r3), own):
        rq_own = jnp.where(ow, rq * rq, jnp.zeros_like(rq))
        acc = acc + jnp.sum(rq_own[h: h + brq, :], axis=0, keepdims=True)
    vacc[...] += acc

    @pl.when(b == g.nblocks - 1)
    def _():
        res[0, 0] += jnp.sum(vacc[...])

    @pl.when(b == g.nblocks - 1)
    def _():
        for c in store(b, slot):
            c.wait()
        if g.nblocks > 1:
            for c in store(b - 1, nslot):
                c.wait()


def make_rb_iters_qdist(g: QGeom, dx: float, dy: float, omega: float, dtype,
                        *, interpret: bool | None = None):
    """Build `(qoffs_i32[2], p_stacked, rhs_stacked) ->
    (p_stacked', owned res sum of last iter)` performing g.n red-black
    iterations on the (4, rp, w2p) stored plane of parallel/quarters_dist.
    Call INSIDE shard_map with qoffs = [joff//2, ioff//2]."""
    if pltpu is None:
        return None
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _check_dtype(dtype, interpret)
    itemsize = jnp.dtype(dtype).itemsize
    if not quarters_feasible(g.brq, g.h, g.w2p, itemsize):
        raise ValueError(
            f"quarters-dist scratch {quarters_vmem_bytes(g.brq, g.h, g.w2p, itemsize) >> 20} MiB "
            f"exceeds the VMEM budget (brq={g.brq}, h={g.h}, w2p={g.w2p}); "
            "reduce tpu_ca_inner or the per-shard width"
        )

    dx2, dy2 = dx * dx, dy * dy
    kernel = functools.partial(
        _qdist_kernel,
        g=g,
        factor=omega * 0.5 * (dx2 * dy2) / (dx2 + dy2),
        idx2=1.0 / dx2,
        idy2=1.0 / dy2,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(g.nblocks,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, 4, g.brq + 2 * g.h, g.w2p), dtype),
            pltpu.VMEM((2, 4, g.brq + 2 * g.h, g.w2p), dtype),
            pltpu.VMEM((2, 4, g.brq, g.w2p), dtype),
            pltpu.VMEM((1, g.w2p), dtype),
            pltpu.SemaphoreType.DMA((2, 8)),
            pltpu.SemaphoreType.DMA((2, 4)),
        ],
    )
    call = pl.pallas_call(
        kernel,
        name="sor2d_rb_qdist",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((4, g.rp, g.w2p), dtype),
            jax.ShapeDtypeStruct((1, 1), dtype),
        ],
        compiler_params=CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES
        ),
        interpret=interpret,
    )

    def rb_iters(qoffs, p_stacked, rhs_stacked):
        p_stacked, res = call(qoffs, p_stacked, rhs_stacked)
        return p_stacked, res[0, 0]

    return rb_iters
