"""3-D red-black SOR as a Pallas TPU kernel — the NS-3D pressure-solve hot op.

Capability parity: the reference's 3-D red-black pressure solve
(/root/reference/assignment-6/src/solver.c: solve:175-297 — the ksw/jsw/isw
checkerboard, 7-point stencil, 6-face Neumann ghost refresh), re-designed for
the TPU memory hierarchy exactly like the 2-D kernel (`ops/sor_pallas.py`):

- One `pallas_call` performs `n_inner` FULL red-black iterations (odd
  half-sweep, even half-sweep, 6-face Neumann refresh) plus the residual of
  the last iteration, in a single HBM sweep — temporal blocking over k-plane
  blocks. The jnp path (`models/ns3d.sor_pass_3d`) streams p and rhs through
  HBM twice per iteration.
- The block axis is k, the MAJOR array axis: a window slices whole (j, i)
  planes, and leading-axis DMA slices carry no tile-alignment constraint
  (tiles live on the minor two axes), so no sublane rounding of the block
  size is needed — only j (sublane) and i (lane) are padded.
- Halo arithmetic is identical to the 2-D kernel, one dimension up: one RB
  iteration consumes 2 planes of window validity (odd reads ±1 plane, even
  reads odd-updated values ±1 plane), so `halo = 2·n_inner` planes on each
  side of the owned block yield a fully-valid owned block with no second HBM
  pass. Halo planes are recomputed redundantly by both neighbouring blocks
  (same data, same arithmetic — identical values).
- The checkerboard is branch-free: parity mask (i+j+k) % 2 from
  `broadcasted_iota` on GLOBAL logical coordinates; pass 0 visits odd parity,
  pass 1 even — the reference's sweep order (isw/jsw/ksw stride-2 loops).
- The 6-face Neumann refresh runs INSIDE the sweep between iterations (mask
  form of `models/ns3d.neumann_faces_3d`: faces only, tangentially clipped to
  the interior, edges/corners and dead padding untouched).
- Residual: accumulated for the LAST iteration only over the owned block,
  reduced along k and sublanes into a per-lane vector accumulator; the
  cross-lane reduction happens once in the final grid step (measured ~25%
  of kernel time when done per block in the 2-D kernel).

Layout: logical arrays are (kmax+2, jmax+2, imax+2), [k, j, i], i minor.
Padded shape: (nblocks·block_k + 2·halo, sublane_round(jmax+2),
lane_round(imax+2)); dead cells are zero on entry and never written.
`pad_array_3d`/`unpad_array_3d` convert at the convergence-loop boundary
only — the loop carries the padded array.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:  # pallas TPU backend is absent on some CPU-only installs
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None

from .sor_pallas import CompilerParams, LANE, VMEM_LIMIT_BYTES, _align, _check_dtype


def padded_ji(jmax: int, imax: int, dtype) -> tuple[int, int]:
    """In-plane padded shape: j+2 to the sublane tile, i+2 to the lane tile."""
    a = _align(dtype)
    jp = -(-(jmax + 2) // a) * a
    ip = -(-(imax + 2) // LANE) * LANE
    return jp, ip


def tblock3d_halo(n_inner: int) -> int:
    """Window halo in planes: 2 per fused iteration; the k axis is untiled so
    no alignment rounding applies."""
    return 2 * n_inner


def _neighbours3(x):
    return (
        jnp.roll(x, -1, axis=2), jnp.roll(x, 1, axis=2),   # east, west
        jnp.roll(x, -1, axis=1), jnp.roll(x, 1, axis=1),   # north, south
        jnp.roll(x, -1, axis=0), jnp.roll(x, 1, axis=0),   # back, front
    )


def masked_stencil_ops_3d(fl, idx2, idy2, idz2, omega):
    """(fac, lap) for the 3-D flag-masked (obstacle) stencil — the single
    home of the eps-coefficient kernel math, shared by _tblock3d_kernel's
    masked mode and the distributed ops/sor_obsdist3d kernel (same
    discipline as sor_pallas.masked_stencil_ops). Arithmetic matches
    ops/obstacle3d.sor_pass_obstacle_3d."""
    eps_e, eps_w, eps_n, eps_s, eps_b, eps_f = _neighbours3(fl)
    denom = ((eps_e + eps_w) * idx2 + (eps_n + eps_s) * idy2
             + (eps_b + eps_f) * idz2)
    fac = jnp.where(denom > 0, omega / denom, 0.0) * fl

    def lap(x):
        east, west, north, south, back_, frnt = _neighbours3(x)
        return (
            (eps_e * (east - x) + eps_w * (west - x)) * idx2
            + (eps_n * (north - x) + eps_s * (south - x)) * idy2
            + (eps_b * (back_ - x) + eps_f * (frnt - x)) * idz2
        )

    return fac, lap


def rb_inner_sweeps_3d(p, rw, n_inner, odd, even, fac, lap, faces):
    """The fused 3-D red-black inner loop (ODD parity first — the
    reference's sweep order) + per-iteration 6-face Neumann refresh, shared
    by _tblock3d_kernel and the distributed obstacle kernel. `faces` =
    (front, back, bottom, top, left, right) select masks. Returns
    (p, r_odd, r_evn) of the LAST iteration."""
    front, back, bottom, top, left, right = faces
    r_odd = r_evn = None
    for _t in range(n_inner):
        r_odd = jnp.where(odd, rw - lap(p), 0.0)
        p = p - fac * r_odd
        r_evn = jnp.where(even, rw - lap(p), 0.0)
        p = p - fac * r_evn
        p = jnp.where(front, jnp.roll(p, -1, axis=0), p)
        p = jnp.where(back, jnp.roll(p, 1, axis=0), p)
        p = jnp.where(bottom, jnp.roll(p, -1, axis=1), p)
        p = jnp.where(top, jnp.roll(p, 1, axis=1), p)
        p = jnp.where(left, jnp.roll(p, -1, axis=2), p)
        p = jnp.where(right, jnp.roll(p, 1, axis=2), p)
    return p, r_odd, r_evn


def pick_block_k(kmax: int, jmax: int, imax: int, dtype=jnp.float32,
                 n_inner: int = 1, masked: bool = False) -> int:
    """Block depth (planes per grid step). The kernel's resident planes are
    2·(bk+2h) window + 2·bk store buffers = 6·bk + 8·h; budget them against
    ~half the raised VMEM limit (Mosaic temporaries take the rest), capped by
    the whole grid and a per-step-overhead floor.

    masked adds a third double-buffered flag window (+2·(bk+2h) planes) AND
    seven flag-derived full-window temporaries (eps_e..eps_f, fac) live
    across the inner loop — budget 15·bk + 18·h resident planes there."""
    jp, ip = padded_ji(jmax, imax, dtype)
    plane = jp * ip * jnp.dtype(dtype).itemsize
    h = tblock3d_halo(n_inner)
    # ~4 MiB per window buffer measured fastest at 128³ on v5e (larger blocks
    # add VMEM pressure, smaller ones pay more per-grid-step overhead) ...
    bk = (4 << 20) // plane - 2 * h
    # ... clamped to what the resident planes can actually hold
    per_bk, per_h = (15, 18) if masked else (6, 8)
    feasible = ((VMEM_LIMIT_BYTES // 2) // plane - per_h * h) // per_bk
    return max(1, min(bk, feasible, kmax + 2, 64))


def block_k_degenerate(block_k: int, kmax: int, n_inner: int) -> bool:
    """True when the budget (not the grid) forced block_k below the halo
    depth — the redundant halo recompute then exceeds ~3x and VMEM likely
    can't hold the windows; the dispatcher should use the jnp path instead
    of a pathological kernel."""
    h = tblock3d_halo(n_inner)
    return block_k < h and block_k < kmax + 2


def padded_k(kmax: int, block_k: int, n_inner: int = 1) -> int:
    nblocks = -(-(kmax + 2) // block_k)
    return nblocks * block_k + 2 * tblock3d_halo(n_inner)


def pad_array_3d(x, block_k: int, n_inner: int = 1):
    """(kmax+2, jmax+2, imax+2) -> padded layout, dead cells zero."""
    kmax = x.shape[0] - 2
    jp, ip = padded_ji(x.shape[1] - 2, x.shape[2] - 2, x.dtype)
    kp = padded_k(kmax, block_k, n_inner)
    h = tblock3d_halo(n_inner)
    out = jnp.zeros((kp, jp, ip), x.dtype)
    return out.at[h : h + kmax + 2, : x.shape[1], : x.shape[2]].set(x)


def unpad_array_3d(xp, kmax: int, jmax: int, imax: int, n_inner: int = 1):
    h = tblock3d_halo(n_inner)
    return xp[h : h + kmax + 2, : jmax + 2, : imax + 2]


def _tblock3d_kernel(
    *refs,  # see unpacking below: [p_in, rhs(, flg)] + [p_out, res] + scratch
    n_inner: int,
    block_k: int,
    nblocks: int,
    kmax: int,
    jmax: int,
    imax: int,
    halo: int,
    factor: float,
    omega: float,
    idx2: float,
    idy2: float,
    idz2: float,
    masked: bool,
):
    """masked=True adds a fluid-flag input (ops/obstacle3d.py flag field,
    padded) and switches the stencil to per-direction fluid coefficients
    with a per-cell relaxation ω/denom — the 3-D form of the 2-D kernel's
    masked mode (_tblock_kernel); arithmetic matches
    ops/obstacle3d.sor_pass_obstacle_3d term-for-term. Flag-derived
    coefficient arrays are computed once per block, outside the iteration
    loop."""
    if masked:
        (p_in, rhs, flg, p_out, res,
         pw2, rw2, fw2, ob2, vacc, ld_sem, st_sem) = refs
    else:
        (p_in, rhs, p_out, res,
         pw2, rw2, ob2, vacc, ld_sem, st_sem) = refs
        flg = fw2 = None
    b = pl.program_id(0)
    bk = block_k
    h = halo
    slot = b % 2
    nslot = (b + 1) % 2

    def load(k, s):
        copies = [
            pltpu.make_async_copy(
                p_in.at[pl.ds(k * bk, bk + 2 * h)], pw2.at[s], ld_sem.at[s, 0]
            ),
            pltpu.make_async_copy(
                rhs.at[pl.ds(k * bk, bk + 2 * h)], rw2.at[s], ld_sem.at[s, 1]
            ),
        ]
        if masked:
            copies.append(
                pltpu.make_async_copy(
                    flg.at[pl.ds(k * bk, bk + 2 * h)], fw2.at[s],
                    ld_sem.at[s, 2],
                )
            )
        return copies

    def store(k, s):
        return pltpu.make_async_copy(
            ob2.at[s], p_out.at[pl.ds(h + k * bk, bk)], st_sem.at[s]
        )

    @pl.when(b == 0)
    def _():
        res[0, 0] = jnp.zeros((), p_out.dtype)
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

    # logical (k, j, i) of window cell (wk, wj, wi): k = b*bk + wk - h
    kk = b * bk - h + jax.lax.broadcasted_iota(jnp.int32, p.shape, 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
    ii = jax.lax.broadcasted_iota(jnp.int32, p.shape, 2)
    interior = (
        (kk >= 1) & (kk <= kmax)
        & (jj >= 1) & (jj <= jmax)
        & (ii >= 1) & (ii <= imax)
    )
    odd = interior & (((ii + jj + kk) % 2) == 1)
    even = interior & (((ii + jj + kk) % 2) == 0)
    # 6-face Neumann refresh masks, tangentially clipped to the interior
    # (models/ns3d.neumann_faces_3d: [1:-1] tangential ranges)
    tan_ji = (jj >= 1) & (jj <= jmax) & (ii >= 1) & (ii <= imax)
    tan_ki = (kk >= 1) & (kk <= kmax) & (ii >= 1) & (ii <= imax)
    tan_kj = (kk >= 1) & (kk <= kmax) & (jj >= 1) & (jj <= jmax)
    front = (kk == 0) & tan_ji
    back = (kk == kmax + 1) & tan_ji
    bottom = (jj == 0) & tan_ki
    top = (jj == jmax + 1) & tan_ki
    left = (ii == 0) & tan_kj
    right = (ii == imax + 1) & tan_kj

    if masked:
        # per-block constants (flags don't change across inner iterations)
        fl = fw2[slot]
        odd = odd & (fl != 0)
        even = even & (fl != 0)
        fac, lap = masked_stencil_ops_3d(fl, idx2, idy2, idz2, omega)
    else:
        fac = factor

        def lap(x):
            east, west, north, south, back_, frnt = _neighbours3(x)
            return (
                (east - 2.0 * x + west) * idx2
                + (north - 2.0 * x + south) * idy2
                + (back_ - 2.0 * x + frnt) * idz2
            )

    p, r_odd, r_evn = rb_inner_sweeps_3d(
        p, rw, n_inner, odd, even, fac, lap,
        (front, back, bottom, top, left, right),
    )

    @pl.when(b >= 2)
    def _():
        store(b - 2, slot).wait()

    ob2[slot] = p[h : h + bk]
    store(b, slot).start()

    # residual of the final iteration, owned block only; reduce k + sublanes
    # into the per-lane accumulator, cross-lane reduction once at the end
    ro = r_odd[h : h + bk]
    eo = r_evn[h : h + bk]
    vacc[...] += jnp.sum(ro * ro + eo * eo, axis=(0, 1))[None, :]

    @pl.when(b == nblocks - 1)
    def _():
        res[0, 0] += jnp.sum(vacc[...])
        store(b, slot).wait()
        if nblocks > 1:  # static: drain the previous slot's store too
            store(b - 1, nslot).wait()


def make_rb_iter_tblock_3d(
    imax: int,
    jmax: int,
    kmax: int,
    dx: float,
    dy: float,
    dz: float,
    omega: float,
    dtype,
    *,
    n_inner: int = 1,
    block_k: int | None = None,
    interpret: bool | None = None,
    fluid=None,
):
    """Build `(p_padded, rhs_padded) -> (p_padded', res_sumsq_of_last_iter)`
    where one call performs `n_inner` 3-D red-black iterations + Neumann BCs.
    Returns (rb_iter, block_k); pad with `pad_array_3d(x, block_k, n_inner)`.

    fluid: optional (kmax+2, jmax+2, imax+2) 0/1 flag field
    (ops/obstacle3d.py) — switches to the obstacle stencil (per-direction
    fluid coefficients, per-cell factor); the padded flag array is baked
    into the returned closure as a constant.
    """
    if pltpu is None:
        return None, 0
    if block_k is None:
        block_k = pick_block_k(kmax, jmax, imax, dtype, n_inner,
                               masked=fluid is not None)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _check_dtype(dtype, interpret)

    # lazy: models.ns3d imports this module for backend dispatch
    from ..models.ns3d import sor_coefficients_3d

    factor, idx2, idy2, idz2 = sor_coefficients_3d(dx, dy, dz, omega)
    masked = fluid is not None
    h = tblock3d_halo(n_inner)
    jp, ip = padded_ji(jmax, imax, dtype)
    nblocks = -(-(kmax + 2) // block_k)
    kp = nblocks * block_k + 2 * h
    kernel = functools.partial(
        _tblock3d_kernel,
        n_inner=n_inner,
        block_k=block_k,
        nblocks=nblocks,
        kmax=kmax,
        jmax=jmax,
        imax=imax,
        halo=h,
        factor=factor,
        omega=omega,
        idx2=idx2,
        idy2=idy2,
        idz2=idz2,
        masked=masked,
    )
    n_in = 3 if masked else 2
    scratch = [
        pltpu.VMEM((2, block_k + 2 * h, jp, ip), dtype),
        pltpu.VMEM((2, block_k + 2 * h, jp, ip), dtype),
    ]
    if masked:
        scratch.append(pltpu.VMEM((2, block_k + 2 * h, jp, ip), dtype))
    scratch += [
        pltpu.VMEM((2, block_k, jp, ip), dtype),
        pltpu.VMEM((1, ip), dtype),
        pltpu.SemaphoreType.DMA((2, n_in)),
        pltpu.SemaphoreType.DMA((2,)),
    ]
    call = pl.pallas_call(
        kernel,
        grid=(nblocks,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * n_in,
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, 1), lambda b: (0, 0), memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((kp, jp, ip), dtype),
            jax.ShapeDtypeStruct((1, 1), dtype),
        ],
        scratch_shapes=scratch,
        compiler_params=CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES
        ),
        interpret=interpret,
    )

    if masked:
        flg_padded = pad_array_3d(jnp.asarray(fluid, dtype), block_k, n_inner)

        def rb_iter(p_padded, rhs_padded):
            p_padded, res = call(p_padded, rhs_padded, flg_padded)
            return p_padded, res[0, 0]
    else:

        def rb_iter(p_padded, rhs_padded):
            p_padded, res = call(p_padded, rhs_padded)
            return p_padded, res[0, 0]

    return rb_iter, block_k


def _tblock3d_octants_kernel(
    p_in,   # ANY (8, sp, jp2, ip2) stacked octants, sor_octants.BITS order
    rhs,    # ANY (8, sp, jp2, ip2)
    p_out,  # ANY (8, sp, jp2, ip2)
    res,    # SMEM (1, 1)
    pw2,    # VMEM (16, bk+2h, jp2, ip2): slot*8 + octant (Mosaic wants ≤4-D)
    rw2,    # VMEM (16, bk+2h, jp2, ip2)
    ob2,    # VMEM (16, bk, jp2, ip2)
    vacc,   # VMEM (1, ip2)
    ld_sem,  # DMA (2, 16)
    st_sem,  # DMA (2, 8)
    *,
    n_inner: int,
    block_k: int,  # octant planes per block
    nblocks: int,
    k2: int,  # (kmax+2)//2 etc. — logical octant extents
    j2: int,
    i2: int,
    halo: int,
    factor: float,
    idx2: float,
    idy2: float,
    idz2: float,
):
    """Temporal-blocked 3-D red-black sweep in the OCTANT layout
    (ops/sor_octants.py): every 7-point neighbour a uniform shift, every
    lane productive, the 6-face Neumann refresh 24 same-index plane
    selects. One iteration consumes ONE octant plane of halo per side
    (= 2 grid planes, matching the checkerboard kernel)."""
    from .sor_octants import BITS, EVEN, ODD, _flip

    b = pl.program_id(0)
    bk = block_k
    h = halo
    slot = b % 2
    nslot = (b + 1) % 2
    qidx = {bits: i for i, bits in enumerate(BITS)}

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

    @pl.when(b + 1 < nblocks)
    def _():
        for c in load(b + 1, nslot):
            c.start()

    for c in load(b, slot):
        c.wait()

    octs = {bits: pw2[slot * 8 + qidx[bits]] for bits in BITS}
    rhs_o = {bits: rw2[slot * 8 + qidx[bits]] for bits in BITS}

    shape = octs[(0, 0, 0)].shape
    ss = b * bk - h + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    rr = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    cc = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
    coords = (ss, rr, cc)
    extents = (k2, j2, i2)

    def ax_interior(axis, par):
        x, n = coords[axis], extents[axis]
        if par == 0:
            return (x >= 1) & (x <= n - 1)
        return (x >= 0) & (x <= n - 2)

    def interior(bits):
        return (ax_interior(0, bits[0]) & ax_interior(1, bits[1])
                & ax_interior(2, bits[2]))

    masks = {bits: interior(bits) for bits in BITS}

    def nbrs(bits):
        def ax_pair(axis):
            partner = octs[_flip(bits, axis)]
            if bits[axis] == 0:
                return jnp.roll(partner, 1, axis), partner
            return partner, jnp.roll(partner, -1, axis)

        f, bk_ = ax_pair(0)
        s_, n = ax_pair(1)
        w, e = ax_pair(2)
        return w, e, s_, n, f, bk_

    resids = {}
    for _t in range(n_inner):
        for group in (ODD, EVEN):
            for bits in group:
                c = octs[bits]
                w, e, s_, n, f, bk_ = nbrs(bits)
                r = rhs_o[bits] - (
                    (e - 2.0 * c + w) * idx2
                    + (n - 2.0 * c + s_) * idy2
                    + (bk_ - 2.0 * c + f) * idz2
                )
                rm = jnp.where(masks[bits], r, 0.0)
                octs[bits] = c - factor * rm
                resids[bits] = rm
        # Neumann refresh: 24 same-index plane selects
        for axis in range(3):
            for hi in (False, True):
                x, nax = coords[axis], extents[axis]
                plane = (x == nax - 1) if hi else (x == 0)
                for bits in BITS:
                    if bits[axis] != (1 if hi else 0):
                        continue
                    a2, a3 = [a for a in range(3) if a != axis]
                    sel = (plane & ax_interior(a2, bits[a2])
                           & ax_interior(a3, bits[a3]))
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
        band = resids[bits][h: h + bk]
        acc = acc + jnp.sum(band * band, axis=(0, 1))[None, :]
    vacc[...] += acc

    @pl.when(b == nblocks - 1)
    def _():
        res[0, 0] += jnp.sum(vacc[...])
        for c in store(b, slot):
            c.wait()
        if nblocks > 1:
            for c in store(b - 1, nslot):
                c.wait()


def octants_padded_ji(jmax: int, imax: int, dtype) -> tuple[int, int]:
    """Octant in-plane padded shape: (jmax+2)/2 to the sublane tile,
    (imax+2)/2 to the lane tile."""
    a = _align(dtype)
    jp2 = -(-((jmax + 2) // 2) // a) * a
    ip2 = -(-((imax + 2) // 2) // LANE) * LANE
    return jp2, ip2


def pad_octants(p, block_k: int, n_inner: int):
    """(kmax+2, jmax+2, imax+2) even-shaped -> (8, sp, jp2, ip2) stacked
    padded octants in sor_octants.BITS order.

    Packing is STAGED single-axis stride-2 slices — one combined
    all-axes stride-2 gather per octant measured ~100 ms per NS-3D solve
    at 128³ on v5e, and the reshape-transpose alternative plans
    intermediates with a size-2 minor dim whose 128-lane tile padding OOMs
    the Mosaic/XLA compiler at large grids (f32[4097,2,4097,2] → 17 GB;
    see sor_pallas.pad_quarters). Axis-at-a-time slices (major-dim k split
    = strided DMA, then sublane j split, then lane i split on
    eighth-sized slabs) keep every intermediate in a sane layout."""
    K, J, I = p.shape
    k2, j2, i2 = K // 2, J // 2, I // 2
    slabs = {}
    for pk in (0, 1):
        sk = p[pk::2]
        for pj in (0, 1):
            skj = sk[:, pj::2]
            for pi in (0, 1):
                slabs[(pk, pj, pi)] = skj[:, :, pi::2]
    from .sor_octants import BITS

    stacked = jnp.stack([slabs[bits] for bits in BITS])
    jp2, ip2 = octants_padded_ji(J - 2, I - 2, p.dtype)
    nblocks = -(-k2 // block_k)
    sp = nblocks * block_k + 2 * n_inner
    out = jnp.zeros((8, sp, jp2, ip2), p.dtype)
    return out.at[:, n_inner: n_inner + k2, :j2, :i2].set(stacked)


def unpad_octants(xo, kmax: int, jmax: int, imax: int, n_inner: int):
    """Inverse of pad_octants, staged axis-at-a-time scatter form (lane
    interleave per (pk, pj) slab, then sublane, then outer — same
    layout-safety/perf constraint as pad_octants; a combined all-axes
    stride-2 scatter per octant mirrors the gather the pack refactor
    removed)."""
    from .sor_octants import BITS

    k2, j2, i2 = (kmax + 2) // 2, (jmax + 2) // 2, (imax + 2) // 2
    stacked = xo[:, n_inner: n_inner + k2, :j2, :i2]
    q = {bits: stacked[qi] for qi, bits in enumerate(BITS)}
    kj = {}
    for pk in (0, 1):
        for pj in (0, 1):
            m = jnp.zeros((k2, j2, 2 * i2), xo.dtype)
            m = m.at[:, :, 0::2].set(q[(pk, pj, 0)])
            m = m.at[:, :, 1::2].set(q[(pk, pj, 1)])
            kj[(pk, pj)] = m
    slabs = {}
    for pk in (0, 1):
        m = jnp.zeros((k2, 2 * j2, 2 * i2), xo.dtype)
        m = m.at[:, 0::2].set(kj[(pk, 0)])
        m = m.at[:, 1::2].set(kj[(pk, 1)])
        slabs[pk] = m
    p = jnp.zeros((2 * k2, 2 * j2, 2 * i2), xo.dtype)
    p = p.at[0::2].set(slabs[0])
    p = p.at[1::2].set(slabs[1])
    return p


def pick_block_k_octants(kmax: int, jmax: int, imax: int, dtype,
                         n_inner: int) -> int:
    """Octant planes per block. Resident octant planes: p windows
    16·(bk+2h) + rhs windows 16·(bk+2h) + store buffers 16·bk
    = 48·bk + 64·h, budgeted against ~half the VMEM limit (Mosaic
    temporaries — the 8 octant values and their rolls — take the rest).
    Getting this wrong crashes the remote Mosaic compiler outright
    (HTTP 500, no diagnostic), it does not error gracefully."""
    return max(1, min(_octants_feasible(jmax, imax, dtype, n_inner),
                      (kmax + 2) // 2, 64))


def _octants_feasible(jmax: int, imax: int, dtype, n_inner: int) -> int:
    """Largest VMEM-feasible octant block depth — the single home of the
    resident-plane accounting (pick_block_k_octants clamps it, the
    degenerate guard checks it; diverging copies would let an infeasible
    build through, which crashes the remote Mosaic compiler)."""
    jp2, ip2 = octants_padded_ji(jmax, imax, dtype)
    plane = jp2 * ip2 * jnp.dtype(dtype).itemsize
    return ((VMEM_LIMIT_BYTES // 2) // max(plane, 1) - 64 * n_inner) // 48


def block_k_octants_degenerate(block_k: int, kmax: int, jmax: int, imax: int,
                               dtype, n_inner: int) -> bool:
    """True when the VMEM budget (not the grid) forced the octant block
    size below feasibility: either the budget admits no block at all
    (feasible < 1 — pick clamps to 1, which n_inner=1 dispatch tests can't
    catch) or the block is thinner than the halo while the grid isn't.
    Mirrors block_k_degenerate for the checkerboard kernel."""
    if _octants_feasible(jmax, imax, dtype, n_inner) < 1:
        return True
    return block_k < n_inner and block_k < (kmax + 2) // 2


def make_rb_iter_tblock_3d_octants(
    imax: int,
    jmax: int,
    kmax: int,
    dx: float,
    dy: float,
    dz: float,
    omega: float,
    dtype,
    *,
    n_inner: int = 1,
    block_k: int | None = None,
    interpret: bool | None = None,
):
    """Temporal-blocked OCTANT-layout 3-D kernel: builds
    `(p_stacked, rhs_stacked) -> (p_stacked', res_sumsq_of_last_iter)` on
    the (8, sp, jp2, ip2) layout of `pad_octants`. Requires even
    imax/jmax/kmax. Returns (rb_iter, block_k, halo=n_inner). Numerics:
    ulp-equivalent to the masked paths (ops/sor_octants.py)."""
    if pltpu is None:
        return None, 0, 0
    if imax % 2 or jmax % 2 or kmax % 2:
        raise ValueError("octant layout needs even imax, jmax, kmax")
    if block_k is None:
        block_k = pick_block_k_octants(kmax, jmax, imax, dtype, n_inner)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _check_dtype(dtype, interpret)

    from ..models.ns3d import sor_coefficients_3d

    factor, idx2, idy2, idz2 = sor_coefficients_3d(dx, dy, dz, omega)
    h = n_inner
    k2, j2, i2 = (kmax + 2) // 2, (jmax + 2) // 2, (imax + 2) // 2
    jp2, ip2 = octants_padded_ji(jmax, imax, dtype)
    nblocks = -(-k2 // block_k)
    sp = nblocks * block_k + 2 * h
    kernel = functools.partial(
        _tblock3d_octants_kernel,
        n_inner=n_inner,
        block_k=block_k,
        nblocks=nblocks,
        k2=k2,
        j2=j2,
        i2=i2,
        halo=h,
        factor=factor,
        idx2=idx2,
        idy2=idy2,
        idz2=idz2,
    )
    call = pl.pallas_call(
        kernel,
        grid=(nblocks,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, 1), lambda b: (0, 0), memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((8, sp, jp2, ip2), dtype),
            jax.ShapeDtypeStruct((1, 1), dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((16, block_k + 2 * h, jp2, ip2), dtype),
            pltpu.VMEM((16, block_k + 2 * h, jp2, ip2), dtype),
            pltpu.VMEM((16, block_k, jp2, ip2), dtype),
            pltpu.VMEM((1, ip2), dtype),
            pltpu.SemaphoreType.DMA((2, 16)),
            pltpu.SemaphoreType.DMA((2, 8)),
        ],
        compiler_params=CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES
        ),
        interpret=interpret,
    )

    def rb_iter(p_stacked, rhs_stacked):
        p_stacked, res = call(p_stacked, rhs_stacked)
        return p_stacked, res[0, 0]

    return rb_iter, block_k, h


def make_octants_solve_loop(rb_iter, block_k: int, eff: int, norm: float,
                            eps: float, itermax: int,
                            kmax: int, jmax: int, imax: int, dtype):
    """make_tblock_solve_loop on the stacked OCTANT layout: same convergence
    contract, only the pad/unpad pair differs."""
    return make_tblock_solve_loop(
        rb_iter, block_k, eff, norm, eps, itermax, kmax, jmax, imax, dtype,
        pad=lambda x: pad_octants(x, block_k, eff),
        unpad=lambda xo: unpad_octants(xo, kmax, jmax, imax, eff),
    )


def make_tblock_solve_loop(rb_iter, block_k: int, eff: int, norm: float,
                           eps: float, itermax: int,
                           kmax: int, jmax: int, imax: int, dtype,
                           pad=None, unpad=None):
    """The tblock convergence loop every 3-D pressure solver shares
    (uniform: models/ns3d.make_pressure_solve_3d; masked:
    ops/obstacle3d.make_obstacle_solver_fn_3d; octants:
    make_octants_solve_loop via the pad/unpad overrides): carry the PADDED
    array, one rb_iter call = eff fused iterations, convergence checked
    every eff iterations (honest `it` accounting), optional PAMPI_DEBUG
    residual line per check."""
    from ..utils import flags as _flags

    epssq = eps * eps
    if pad is None:
        def pad(x):
            return pad_array_3d(x, block_k, eff)
    if unpad is None:
        def unpad(xp):
            return unpad_array_3d(xp, kmax, jmax, imax, eff)

    def solve(p, rhs):
        pp = pad(p)
        rp = pad(rhs)

        def cond(c):
            _, res, it = c
            return jnp.logical_and(res >= epssq, it < itermax)

        def body(c):
            pp, _, it = c
            pp, rsq = rb_iter(pp, rp)
            res = rsq / norm
            if _flags.debug():
                jax.debug.print("{} Residuum: {}", it + (eff - 1), res)
            return pp, res, it + eff

        pp, res, it = jax.lax.while_loop(
            cond, body,
            (pp, jnp.asarray(1.0, dtype), jnp.asarray(0, jnp.int32)),
        )
        return unpad(pp), res, it

    return solve


_PROBE3D_OK: bool | None = None


def probe_pallas_3d() -> bool:
    """One-time smoke test of the 3-D kernel on the real backend (same
    contract as sor_pallas.probe_pallas): chip/toolchain-wide failures
    surface here once, raised on a TPU backend."""
    global _PROBE3D_OK
    if _PROBE3D_OK is None:
        try:
            rb, bk = make_rb_iter_tblock_3d(
                30, 30, 30, 1.0 / 30, 1.0 / 30, 1.0 / 30, 1.7, jnp.float32,
                n_inner=1, interpret=False,
            )
            z = pad_array_3d(jnp.zeros((32, 32, 32), jnp.float32), bk, 1)
            _, res = rb(z, z)
            float(res)  # force completion: async errors surface here
            _PROBE3D_OK = True
        except Exception as exc:  # lint: allow(broad-except) — probe contract: raise on TPU, report unavailable elsewhere
            from ..utils.dispatch import probe_failed

            _PROBE3D_OK = probe_failed("the 3-D SOR Pallas kernel", exc)
    return _PROBE3D_OK
