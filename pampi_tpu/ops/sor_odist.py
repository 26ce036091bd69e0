"""Per-shard Pallas kernel for the DISTRIBUTED octant-layout 3-D SOR.

The 3-D companion of ops/sor_qdist.py (≙ the reference's per-rank 3-D SOR,
assignment-6/src/solver.c:175-297, running on every chip of the mesh): the
temporal-blocked octant kernel of sor3d_pallas.make_rb_iter_tblock_3d_octants
generalized to a shard of a ("k","j","i") mesh — masks from GLOBAL octant
coordinates via three scalar-prefetch offsets, updates clipped to the stored
logical region with a frozen outermost ring, owned-only residual. Layout and
jnp twin: parallel/octants_dist.py (keep the mask formulas in lockstep)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..parallel.octants_dist import OGeom, QIDX
from .sor_octants import BITS, EVEN, ODD, _flip
from .sor_pallas import CompilerParams, VMEM_LIMIT_BYTES, _check_dtype, pltpu


def octants_dist_vmem_bytes(g: OGeom, itemsize: int) -> int:
    win = 16 * (g.bk + 2 * g.h) * g.jp2 * g.ip2
    out = 16 * g.bk * g.jp2 * g.ip2
    return itemsize * (2 * win + out + g.ip2)


def octants_dist_feasible(g: OGeom, itemsize: int) -> bool:
    return octants_dist_vmem_bytes(g, itemsize) <= VMEM_LIMIT_BYTES // 2


def _odist_kernel(
    sref,   # SMEM scalar prefetch: int32[3] = (qoff_k, qoff_j, qoff_i)
    p_in,   # ANY (8, sp, jp2, ip2) stacked stored volume, BITS order
    rhs,    # ANY (8, sp, jp2, ip2)
    p_out,  # ANY (8, sp, jp2, ip2)
    res,    # SMEM (1, 1)
    pw2,    # VMEM (16, bk+2h, jp2, ip2): slot*8 + octant (Mosaic wants <=4-D)
    rw2,    # VMEM (16, bk+2h, jp2, ip2)
    ob2,    # VMEM (16, bk, jp2, ip2)
    vacc,   # VMEM (1, ip2)
    ld_sem,  # DMA (2, 16)
    st_sem,  # DMA (2, 8)
    *,
    g: OGeom,
    factor: float,
    idx2: float,
    idy2: float,
    idz2: float,
):
    b = pl.program_id(0)
    bk = g.bk
    h = g.h
    slot = b % 2
    nslot = (b + 1) % 2
    qidx = QIDX
    # axes without a deep halo (mesh size 1) have a statically-zero shard
    # offset: substituting the constant lets Mosaic fold their masks to
    # static iota compares, as in the single-device octant kernel
    qoff = tuple(sref[a] if g.d[a] > 0 else 0 for a in range(3))

    def load(k, s):
        copies = []
        for qi in range(8):
            copies.append(pltpu.make_async_copy(
                p_in.at[qi, pl.ds(k * bk, bk + 2 * h)], pw2.at[s * 8 + qi],
                ld_sem.at[s, qi]))
            copies.append(pltpu.make_async_copy(
                rhs.at[qi, pl.ds(k * bk, bk + 2 * h)], rw2.at[s * 8 + qi],
                ld_sem.at[s, 8 + qi]))
        return copies

    def store(k, s):
        return [pltpu.make_async_copy(
            ob2.at[s * 8 + qi], p_out.at[qi, pl.ds(h + k * bk, bk)],
            st_sem.at[s, qi]) for qi in range(8)]

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

    octs = {bits: pw2[slot * 8 + qidx[bits]] for bits in BITS}
    rhs_o = {bits: rw2[slot * 8 + qidx[bits]] for bits in BITS}

    shape = octs[(0, 0, 0)].shape
    # stored coords of window cell: s = b*bk + wk, r = wj, c = wi
    st_s = b * bk + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    st_r = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    st_c = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
    stored = (st_s, st_r, st_c)
    lam = (st_s - h, st_r, st_c)
    go = tuple(lam[a] - g.d[a] + qoff[a] for a in range(3))
    # frozen-ring clip only on deep-halo axes (octants_dist.o_masks)
    spans = (g.kq, g.jq, g.iq)
    valid_upd = None
    for a in range(3):
        if g.d[a] == 0:
            continue
        term = (lam[a] >= 1) & (lam[a] <= spans[a] - 2)
        valid_upd = term if valid_upd is None else (valid_upd & term)
    if valid_upd is None:
        valid_upd = jnp.ones_like(lam[0], dtype=bool)
    valid_any = (
        (lam[0] >= 0) & (lam[0] < g.kq)
        & (lam[1] >= 0) & (lam[1] < g.jq)
        & (lam[2] >= 0) & (lam[2] < g.iq)
    )

    def ax_int(axis, bit):
        if bit == 0:
            return (go[axis] >= 1) & (go[axis] <= g.gmax2(axis))
        return (go[axis] >= 0) & (go[axis] <= g.gmax2(axis) - 1)

    def ax_own(axis, bit):
        from ..parallel.octants_dist import _owned_start

        os = _owned_start(g, axis, bit)
        return (stored[axis] >= os) & (stored[axis] < os + g.local2(axis))

    # ownership differs from the update interior only on deep-halo axes
    # (redundantly-recomputed ghost cells); on d_ax = 0 axes rm is already
    # zero outside owned cells, so those ax_own terms (and, on an all-owned
    # shard, the whole residual select) drop out
    own_axes = [a for a in range(3) if g.d[a] > 0]
    m_upd = {}
    m_own = {}
    for bits in BITS:
        m_upd[bits] = (
            ax_int(0, bits[0]) & ax_int(1, bits[1]) & ax_int(2, bits[2])
            & valid_upd
        )
        own = None
        for a in own_axes:
            term = ax_own(a, bits[a])
            own = term if own is None else (own & term)
        m_own[bits] = own

    def nbrs(bits):
        def ax_pair(axis):
            partner = octs[_flip(bits, axis)]
            if bits[axis] == 0:
                return jnp.roll(partner, 1, axis), partner
            return partner, jnp.roll(partner, -1, axis)

        f, bk_ = ax_pair(0)
        s_, n_ = ax_pair(1)
        w, e = ax_pair(2)
        return w, e, s_, n_, f, bk_

    resids = {}
    for _t in range(g.n):
        for group in (ODD, EVEN):
            for bits in group:
                cen = octs[bits]
                w, e, s_, n_, f, bk_ = nbrs(bits)
                r = rhs_o[bits] - (
                    (e - 2.0 * cen + w) * idx2
                    + (n_ - 2.0 * cen + s_) * idy2
                    + (bk_ - 2.0 * cen + f) * idz2
                )
                rm = jnp.where(m_upd[bits], r, jnp.zeros_like(r))
                octs[bits] = cen - factor * rm
                resids[bits] = rm
        # globally-gated Neumann face refresh: same-index partner selects
        for axis in range(3):
            for hi in (False, True):
                plane = go[axis] == (g.gmax2(axis) if hi else 0)
                for bits in BITS:
                    if bits[axis] != (1 if hi else 0):
                        continue
                    a2, a3 = [a for a in range(3) if a != axis]
                    sel = (plane & ax_int(a2, bits[a2])
                           & ax_int(a3, bits[a3]) & valid_any)
                    octs[bits] = jnp.where(
                        sel, octs[_flip(bits, axis)], octs[bits]
                    )

    @pl.when(b >= 2)
    def _():
        for c in store(b - 2, slot):
            c.wait()

    for bits in BITS:
        ob2[slot * 8 + qidx[bits]] = octs[bits][h: h + bk]
    for c in store(b, slot):
        c.start()

    acc = jnp.zeros_like(vacc[...])
    for bits in BITS:
        rq = resids[bits]
        if m_own[bits] is None:
            rq_own = rq * rq
        else:
            rq_own = jnp.where(m_own[bits], rq * rq, jnp.zeros_like(rq))
        acc = acc + jnp.sum(rq_own[h: h + bk], axis=(0, 1))[None, :]
    vacc[...] += acc

    @pl.when(b == g.nblocks - 1)
    def _():
        res[0, 0] += jnp.sum(vacc[...])
        for c in store(b, slot):
            c.wait()
        if g.nblocks > 1:
            for c in store(b - 1, nslot):
                c.wait()


def make_rb_iters_odist(g: OGeom, dx: float, dy: float, dz: float,
                        omega: float, dtype, *,
                        interpret: bool | None = None):
    """Build `(qoffs_i32[3], p_stacked, rhs_stacked) ->
    (p_stacked', owned res sum of last iter)` performing g.n 3-D red-black
    iterations on the (8, sp, jp2, ip2) stored volume. Call INSIDE shard_map
    with qoffs = [koff//2, joff//2, ioff//2]."""
    if pltpu is None:
        return None
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _check_dtype(dtype, interpret)
    itemsize = jnp.dtype(dtype).itemsize
    if not octants_dist_feasible(g, itemsize):
        raise ValueError(
            f"octants-dist scratch {octants_dist_vmem_bytes(g, itemsize) >> 20}"
            f" MiB exceeds the VMEM budget (bk={g.bk}, h={g.h}, "
            f"plane={g.jp2}x{g.ip2}); reduce tpu_ca_inner or the shard size"
        )

    from ..models.ns3d import sor_coefficients_3d

    factor, idx2, idy2, idz2 = sor_coefficients_3d(dx, dy, dz, omega)
    kernel = functools.partial(
        _odist_kernel, g=g, factor=factor, idx2=idx2, idy2=idy2, idz2=idz2
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
            pltpu.VMEM((16, g.bk + 2 * g.h, g.jp2, g.ip2), dtype),
            pltpu.VMEM((16, g.bk + 2 * g.h, g.jp2, g.ip2), dtype),
            pltpu.VMEM((16, g.bk, g.jp2, g.ip2), dtype),
            pltpu.VMEM((1, g.ip2), dtype),
            pltpu.SemaphoreType.DMA((2, 16)),
            pltpu.SemaphoreType.DMA((2, 8)),
        ],
    )
    call = pl.pallas_call(
        kernel,
        name="sor3d_rb_odist",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((8, g.sp, g.jp2, g.ip2), dtype),
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
