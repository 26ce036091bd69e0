"""Red-black SOR as a Pallas TPU kernel — the framework's hot op.

Capability parity: the reference's red-black Poisson kernels
(/root/reference/assignment-4/src/solver.c: solveRB:179, solveRBA:240),
re-designed for the TPU memory hierarchy instead of translated:

- One `pallas_call` performs a FULL red-black iteration (both half-sweeps +
  the residual reduction). The jnp fallback (`ops/sor.py`) issues two fused
  XLA passes per iteration, each streaming p and rhs through HBM and
  allocating a fresh output; this kernel streams row blocks HBM->VMEM with
  explicit async DMA, updates p in place (input/output aliased), and
  accumulates the residual in SMEM.
- grid = (2, nblocks): the outer grid dimension is the color phase (0 = red,
  1 = black; same cell ordering as the reference's isw/jsw stride-2 loops),
  the inner is the row-block sweep. TPU grid steps execute sequentially, so
  the black phase reads the red phase's in-place updates — the Gauss-Seidel
  dependency the reference gets from its in-place double loop.
- The checkerboard is branch-free: a parity mask from `broadcasted_iota` on
  GLOBAL interior indices (i + j), applied to the update and the residual.
- In-place halo safety: a half-sweep modifies only parity-`phase` cells, and
  a block's halo rows contribute only opposite-parity neighbours, so the
  value an adjacent block reads is the same whether its window DMA lands
  before or after this block's write-back.

Alignment: Mosaic requires DMA slices aligned to the tile — sublane (8 for
f32) in dim 0, lane (128) in dim 1 — so the solver state lives in a PADDED
layout: `pad` rows of dead cells above and below the logical
(jmax+2, imax+2) array, and dead columns on the right up to the next lane
multiple. Each block owns an aligned band of `block_rows` padded rows (ghost
+ out-of-range rows masked out of the update), loads the aligned window
[band - pad, band + pad) at full padded width, and stores back exactly its
band. Dead columns are zero on entry and never written, so round-tripping
them through VMEM is harmless. `pad_array`/`unpad_array` convert at the loop
boundary only — the convergence loop carries the padded array, so padding
costs one copy per solve, not per iteration.

Layout: arrays are (jmax+2, imax+2) row-major [j, i] — i is the lane
dimension; padded shape ((nblocks*block_rows + 2*pad), lane_round(imax+2)).

Measured design notes (v5e, 4096² f32): n_inner=5 × block_rows=256 is the
sweep optimum (k=3..8 × 128/256/512). A compressed red-black layout
(separate dense red/black half-width arrays — all lanes productive, n/s
neighbours become pure sublane shifts) measured 1.6× SLOWER than the
masked checkerboard in like-for-like minimal kernels: the row-parity lane
selects (`where(row_even, x, roll(x))` per e/w neighbour) cost more than
the checkerboard masking they remove, so the masked form ships.
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

CompilerParams = pltpu.CompilerParams if pltpu is not None else None


LANE = 128  # lane tile; DMA slice widths must be multiples of this

# v5e has 128 MiB of VMEM; the default scoped-vmem compile limit is 16 MiB,
# which caps the fused kernel at ~48-row blocks (one grid step per 48 rows —
# per-step overhead then dominates). Raised per-kernel via CompilerParams.
VMEM_LIMIT_BYTES = 100 << 20


def _align(dtype) -> int:
    """Sublane tile for the dtype (f32: 8, bf16: 16); DMA row offsets and
    lengths must be multiples of this."""
    return max(8, 32 // jnp.dtype(dtype).itemsize)


def padded_width(imax: int) -> int:
    """Logical width imax+2 rounded up to the lane tile."""
    return -(-(imax + 2) // LANE) * LANE


_PROBE_OK: bool | None = None


def probe_pallas() -> bool:
    """One-time smoke test: compile and run the fused kernel on a tiny grid
    on the real backend. Chip/toolchain-wide pallas failures (missing Mosaic
    support, compile errors) surface here once, before any run is built —
    raised on a TPU backend (utils/dispatch.probe_failed). Memoized per
    process; the probe shape hits the jit cache afterwards."""
    global _PROBE_OK
    if _PROBE_OK is None:
        try:
            rb, br, h = make_rb_iter_tblock(
                126, 126, 1.0 / 126, 1.0 / 126, 1.9, jnp.float32,
                n_inner=1, interpret=False,
            )
            z = pad_array(jnp.zeros((128, 128), jnp.float32), br, h)
            _, res = rb(z, z)
            float(res)  # force completion: async errors surface here
            _PROBE_OK = True
        except Exception as exc:  # lint: allow(broad-except) — probe contract: raise on TPU, report unavailable elsewhere
            from ..utils.dispatch import probe_failed

            _PROBE_OK = probe_failed("the 2-D SOR Pallas kernel", exc)
    return _PROBE_OK


def _check_dtype(dtype, interpret: bool) -> None:
    if not interpret and jnp.dtype(dtype).itemsize > 4:
        raise ValueError(
            f"Mosaic cannot lower {jnp.dtype(dtype).name} on TPU; use float32 "
            "(or bfloat16), or the jnp backend for float64"
        )


def pick_block_rows(jmax: int, imax: int, dtype=jnp.float32) -> int:
    """Largest aligned block height keeping the two VMEM windows
    ((BR+2A, Wp) + (BR, Wp)) under ~4 MiB, capped at one block per grid."""
    a = _align(dtype)
    itemsize = jnp.dtype(dtype).itemsize
    wp = padded_width(imax)
    budget = (4 << 20) // (2 * itemsize * wp)
    whole = -(-(jmax + 2) // a) * a  # one block covering everything
    br = max(a, min(budget // a * a, whole, 512))
    return br


def padded_rows(jmax: int, block_rows: int, dtype=jnp.float32,
                halo: int | None = None) -> int:
    a = halo if halo is not None else _align(dtype)
    nblocks = -(-(jmax + 2) // block_rows)
    return nblocks * block_rows + 2 * a


def pad_array(x, block_rows: int, halo: int | None = None):
    """(jmax+2, imax+2) -> padded layout; dead rows/columns are zero.
    `halo` rows of padding above/below (default: the sublane alignment)."""
    jmax = x.shape[0] - 2
    rp = padded_rows(jmax, block_rows, x.dtype, halo)
    a = halo if halo is not None else _align(x.dtype)
    out = jnp.zeros((rp, padded_width(x.shape[1] - 2)), x.dtype)
    return out.at[a : a + jmax + 2, : x.shape[1]].set(x)


def unpad_array(xp, jmax: int, imax: int, halo: int | None = None):
    a = halo if halo is not None else _align(xp.dtype)
    return xp[a : a + jmax + 2, : imax + 2]


def _rb_kernel(
    p_in,  # ANY (aliased to p_out) — unused; reads go through p_out
    rhs,  # ANY, padded like p
    p_out,  # ANY, aliased with p_in
    res,  # SMEM (1, 1) accumulator
    pw,  # VMEM (BR+2A, W) scratch: p window, owned band at rows [A, A+BR)
    rw,  # VMEM (BR, W) scratch: rhs band
    sem,  # DMA semaphores (2,)
    *,
    block_rows: int,
    width: int,
    jmax: int,
    pad: int,
    factor: float,
    idx2: float,
    idy2: float,
):
    del p_in
    phase = pl.program_id(0)  # 0 = red, 1 = black
    b = pl.program_id(1)
    br = block_rows
    a = pad
    band0 = a + b * br  # first padded row of the owned band

    ld_p = pltpu.make_async_copy(
        p_out.at[pl.ds(band0 - a, br + 2 * a), :], pw, sem.at[0]
    )
    ld_r = pltpu.make_async_copy(rhs.at[pl.ds(band0, br), :], rw, sem.at[1])
    ld_p.start()
    ld_r.start()
    ld_p.wait()
    ld_r.wait()

    c = pw[a : a + br, 1 : width - 1]
    east = pw[a : a + br, 2:width]
    west = pw[a : a + br, 0 : width - 2]
    north = pw[a + 1 : a + br + 1, 1 : width - 1]
    south = pw[a - 1 : a + br - 1, 1 : width - 1]
    lap = (east - 2.0 * c + west) * idx2 + (north - 2.0 * c + south) * idy2
    r = rw[:, 1 : width - 1] - lap

    # logical row j of local row l is b*br + l (padded row band0+l minus pad);
    # interior means 1 <= j <= jmax and the (i + j) checkerboard parity
    jj = b * br + jax.lax.broadcasted_iota(jnp.int32, r.shape, 0)
    ii = 1 + jax.lax.broadcasted_iota(jnp.int32, r.shape, 1)
    live = jnp.logical_and(
        ((ii + jj) % 2) == phase, jnp.logical_and(jj >= 1, jj <= jmax)
    )
    rm = jnp.where(live, r, jnp.zeros_like(r))

    pw[a : a + br, 1 : width - 1] = c - factor * rm

    @pl.when(jnp.logical_and(phase == 0, b == 0))
    def _():
        res[0, 0] = jnp.zeros((), rm.dtype)

    res[0, 0] += jnp.sum(rm * rm)

    st = pltpu.make_async_copy(
        pw.at[pl.ds(a, br), :], p_out.at[pl.ds(band0, br), :], sem.at[0]
    )
    st.start()
    st.wait()


def _tblock_kernel(
    *refs,
    n_inner: int,
    block_rows: int,
    nblocks: int,
    width: int,
    jmax: int,
    halo: int,
    factor: float,
    omega: float,
    idx2: float,
    idy2: float,
    masked: bool,
    dynamic: bool = False,
):
    """`n_inner` FULL red-black iterations (each incl. the Neumann ghost
    refresh) in a single HBM sweep — temporal blocking.

    One RB iteration consumes 2 rows of halo validity (red reads ±1 row,
    black reads red-updated values ±1 row), so a window of the owned band
    ±`halo` rows (halo ≥ 2·n_inner) yields a fully-converged owned band after
    n_inner iterations with no second HBM pass: HBM traffic per iteration
    drops to ~3/n_inner arrays. Halo rows are recomputed redundantly by both
    neighbouring blocks (identical values — same data, same unrolled
    arithmetic). The Neumann BC runs INSIDE the sweep between iterations
    (mask form of `neumann_bc_padded`: ghost rows/cols only, corners and
    dead padding untouched), because interior updates of iteration t+1 read
    ghost values refreshed after iteration t.

    masked=True adds a fluid-flag input (padded 0/1 array, ops/obstacle.py
    flag field) and switches the stencil to per-direction fluid coefficients
    with a per-cell relaxation factor ω/denom — homogeneous Neumann on
    obstacle surfaces, branch-free (the north-star requirement). The
    eps/factor arrays are derived from the flags ONCE per block, outside the
    iteration loop; arithmetic matches ops/obstacle.sor_pass_obstacle
    term-for-term. Flags are static config, so the extra HBM traffic is one
    array load per sweep (amortized over n_inner iterations).

    Residual: accumulated for the LAST iteration only (static slice of the
    owned band), so a convergence loop stepping this kernel observes the
    residual of its final iteration — the same value a per-iteration loop
    would see at that count.

    dynamic=True is the SHAPE-CLASS mode (fleet/shapeclass.py): the live
    extents and the grid-derived update constants arrive as SMEM scalars
    (ext int32 (1,2) = (jmax, imax); geo (1,3) = (factor, idx2, idy2))
    instead of trace constants, so one compiled kernel at the padded
    CLASS geometry serves every lane — the interior/parity/ghost masks
    are extent-gated per call and cells beyond the live extent pass
    through untouched (where-selects, never multiplies, so garbage
    there cannot reach any stored value or the residual).
    """
    if dynamic:
        (p_in, rhs, ext_ref, geo_ref, p_out, res,
         pw2, rw2, ob2, vacc, ld_sem, st_sem) = refs
        flg = fw2 = None
    elif masked:
        (p_in, rhs, flg, p_out, res,
         pw2, rw2, fw2, ob2, vacc, ld_sem, st_sem) = refs
    else:
        (p_in, rhs, p_out, res,
         pw2, rw2, ob2, vacc, ld_sem, st_sem) = refs
        flg = fw2 = None
    b = pl.program_id(0)
    br = block_rows
    h = halo
    slot = b % 2
    nslot = (b + 1) % 2

    def load(k, s):
        copies = [
            pltpu.make_async_copy(
                p_in.at[pl.ds(k * br, br + 2 * h), :], pw2.at[s], ld_sem.at[s, 0]
            ),
            pltpu.make_async_copy(
                rhs.at[pl.ds(k * br, br + 2 * h), :], rw2.at[s], ld_sem.at[s, 1]
            ),
        ]
        if masked:
            copies.append(
                pltpu.make_async_copy(
                    flg.at[pl.ds(k * br, br + 2 * h), :], fw2.at[s],
                    ld_sem.at[s, 2],
                )
            )
        return copies

    def store(k, s):
        return pltpu.make_async_copy(
            ob2.at[s], p_out.at[pl.ds(h + k * br, br), :], st_sem.at[s]
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

    # logical (j, i) of window cell (w, c): j = b*br + w - h, i = c.
    # dynamic mode reads the live extents from SMEM (the static path's
    # `width - 2` IS its imax, so the two forms are the same masks)
    if dynamic:
        jmax = ext_ref[0, 0]
        imax_d = ext_ref[0, 1]
    else:
        imax_d = width - 2
    jj = b * br - h + jax.lax.broadcasted_iota(jnp.int32, p.shape, 0)
    ii = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
    interior = (jj >= 1) & (jj <= jmax) & (ii >= 1) & (ii <= imax_d)
    red = interior & (((ii + jj) % 2) == 0)
    black = interior & (((ii + jj) % 2) == 1)
    row_ghost_lo = (jj == 0) & (ii >= 1) & (ii <= imax_d)
    row_ghost_hi = (jj == jmax + 1) & (ii >= 1) & (ii <= imax_d)
    row_int = (jj >= 1) & (jj <= jmax)
    col_ghost_lo = (ii == 0) & row_int
    col_ghost_hi = (ii == imax_d + 1) & row_int

    if masked:
        # per-block constants (flags don't change across inner iterations):
        # eps_d = "neighbour in direction d is fluid"; the update factor is
        # ω/denom on fluid cells, 0 elsewhere (ops/obstacle.make_masks parity)
        fl = fw2[slot]
        red = red & (fl != 0)
        black = black & (fl != 0)
        fac, lap = masked_stencil_ops(fl, idx2, idy2, omega)
    else:
        if dynamic:
            # per-lane update constants (computed host-side in Python f64
            # with the solo solver's own expressions — the shape-class
            # bitwise-coefficient contract)
            fac = geo_ref[0, 0]
            idx2 = geo_ref[0, 1]
            idy2 = geo_ref[0, 2]
        else:
            fac = factor

        def lap(x):
            east = jnp.roll(x, -1, axis=1)
            west = jnp.roll(x, 1, axis=1)
            north = jnp.roll(x, -1, axis=0)
            south = jnp.roll(x, 1, axis=0)
            return (east - 2.0 * x + west) * idx2 + (
                north - 2.0 * x + south
            ) * idy2

    p, r_red, r_blk = rb_inner_sweeps(
        p, rw, n_inner, red, black, fac, lap,
        (row_ghost_lo, row_ghost_hi, col_ghost_lo, col_ghost_hi),
    )

    @pl.when(b >= 2)
    def _():
        store(b - 2, slot).wait()

    ob2[slot] = p[h : h + br, :]
    store(b, slot).start()

    # residual of the final iteration, owned band only (static slice).
    # Reduce along sublanes only and accumulate a per-lane vector; the
    # expensive cross-lane reduction happens ONCE in the last block instead
    # of per block (measured ~25% of kernel time when done per block).
    ro = r_red[h : h + br, :]
    bo = r_blk[h : h + br, :]
    vacc[...] += jnp.sum(ro * ro + bo * bo, axis=0, keepdims=True)

    @pl.when(b == nblocks - 1)
    def _():
        res[0, 0] += jnp.sum(vacc[...])

    @pl.when(b == nblocks - 1)
    def _():
        store(b, slot).wait()
        if nblocks > 1:  # static: drain the previous slot's store too
            store(b - 1, nslot).wait()


def tblock_halo(n_inner: int, dtype) -> int:
    """Window halo for n_inner fused iterations: 2 rows per iteration,
    rounded up to the DMA sublane alignment."""
    a = _align(dtype)
    return max(a, -(-(2 * n_inner) // a) * a)


def masked_stencil_ops(fl, idx2, idy2, omega):
    """(fac, lap) for the flag-masked (obstacle) stencil, derived from a
    0/1 flag window — the SINGLE home of the eps-coefficient kernel math
    (used by _tblock_kernel's masked mode and the distributed
    ops/sor_obsdist kernel; flag values are identical on every shard that
    sees a cell, so sharing this keeps the two term-for-term identical).
    Arithmetic matches ops/obstacle.sor_pass_obstacle."""
    eps_e = jnp.roll(fl, -1, axis=1)
    eps_w = jnp.roll(fl, 1, axis=1)
    eps_n = jnp.roll(fl, -1, axis=0)
    eps_s = jnp.roll(fl, 1, axis=0)
    denom = (eps_e + eps_w) * idx2 + (eps_n + eps_s) * idy2
    fac = jnp.where(denom > 0, omega / denom, 0.0) * fl

    def lap(x):
        east = jnp.roll(x, -1, axis=1)
        west = jnp.roll(x, 1, axis=1)
        north = jnp.roll(x, -1, axis=0)
        south = jnp.roll(x, 1, axis=0)
        return (eps_e * (east - x) + eps_w * (west - x)) * idx2 + (
            eps_n * (north - x) + eps_s * (south - x)
        ) * idy2

    return fac, lap


def rb_inner_sweeps(p, rw, n_inner, red, black, fac, lap, ghosts,
                    loop: bool = False):
    """The fused red-black inner loop + per-iteration Neumann ghost refresh
    shared by every 2-D checkerboard-layout kernel (single-device
    _tblock_kernel and distributed _obsdist_kernel — one home so the two
    cannot drift). `ghosts` = (row_lo, row_hi, col_lo, col_hi) select
    masks. Returns (p, r_red, r_blk) of the LAST iteration.

    `loop=True` runs the sweeps through a `lax.fori_loop` (scf.for in
    Mosaic) instead of unrolling: Mosaic's STACK for the unrolled body
    scales with n (each unrolled sweep keeps window-sized temporaries
    live — the ca16-at-512-wide-shards OOM of round 4), while the looped
    body's live set is one sweep's. Same op sequence per sweep -> bitwise
    identical results; the default stays unrolled (the tuned headline
    kernels' codegen is untouched)."""
    row_lo, row_hi, col_lo, col_hi = ghosts

    def sweep(p):
        r_red = jnp.where(red, rw - lap(p), 0.0)
        p = p - fac * r_red
        r_blk = jnp.where(black, rw - lap(p), 0.0)
        p = p - fac * r_blk
        p = jnp.where(row_lo, jnp.roll(p, -1, axis=0), p)
        p = jnp.where(row_hi, jnp.roll(p, 1, axis=0), p)
        p = jnp.where(col_lo, jnp.roll(p, -1, axis=1), p)
        p = jnp.where(col_hi, jnp.roll(p, 1, axis=1), p)
        return p, r_red, r_blk

    if loop:
        return jax.lax.fori_loop(
            0, n_inner, lambda _t, c: sweep(c[0]),
            (p, jnp.zeros_like(p), jnp.zeros_like(p)),
        )
    r_red = r_blk = None
    for _t in range(n_inner):
        p, r_red, r_blk = sweep(p)
    return p, r_red, r_blk


def pick_block_rows_tblock(jmax: int, imax: int, dtype=jnp.float32,
                           n_inner: int = 4) -> int:
    """Block height for the temporal-blocked kernel. The round-2 sweep
    (tools/perf_sweep_tblock.py, dispatch-latency-amortized: SWEEP_TOTAL=960,
    k ∈ {3..8} × br ∈ {64..256} at 4096² f32, and the 8192² region harness)
    measured a flat surface 36-41G updates/s with the optimum at 128 rows
    for BOTH 4224- and 8320-lane widths — so large grids get a flat 128.
    Small grids keep the single-block window (no redundant halo recompute;
    the window fits VMEM outright)."""
    a = _align(dtype)
    h = tblock_halo(n_inner, dtype)
    wp = padded_width(imax)
    whole = -(-(jmax + 2) // a) * a  # one block covering everything
    if whole >= 1024:
        return max(a, h, 128)
    target = 256 * 4224 * 4  # bytes per window buffer that fit comfortably
    br = target // (wp * jnp.dtype(dtype).itemsize) // a * a
    return max(a, h, min(br, 512, whole))


def tblock_vmem_bytes(block_rows: int, h: int, wp: int, itemsize: int,
                      masked: bool = False) -> int:
    """Scratch bytes of the checkerboard tblock kernel: double-buffered p and
    rhs (+ flag) windows, out bands, per-lane accumulator."""
    nwin = 3 if masked else 2
    win = 2 * (block_rows + 2 * h) * wp
    return itemsize * (nwin * win + 2 * block_rows * wp + wp)


def tblock_feasible(block_rows: int, h: int, wp: int, itemsize: int,
                    masked: bool = False) -> bool:
    """VMEM guard for the checkerboard kernel (same contract as
    quarters_feasible — an infeasible build crashes Mosaic at first
    dispatch, so the dispatcher must get a catchable error instead)."""
    return (
        tblock_vmem_bytes(block_rows, h, wp, itemsize, masked)
        <= VMEM_LIMIT_BYTES // 2
    )


def make_rb_iter_tblock(
    imax: int,
    jmax: int,
    dx: float,
    dy: float,
    omega: float,
    dtype,
    *,
    n_inner: int = 4,
    block_rows: int | None = None,
    interpret: bool | None = None,
    fluid=None,
    dynamic: bool = False,
):
    """Temporal-blocked fused kernel (see `_tblock_kernel`): builds
    `(p_padded, rhs_padded) -> (p_padded', res_sumsq_of_last_iter)` where one
    call performs `n_inner` red-black iterations + Neumann BCs. The padded
    layout uses `halo = tblock_halo(n_inner)` rows of padding (pass it to
    `pad_array`/`unpad_array`). Returns (rb_iter, block_rows, halo).

    fluid: optional (jmax+2, imax+2) 0/1 flag field (ops/obstacle.py) —
    switches to the obstacle stencil (per-direction fluid coefficients,
    per-cell factor); the padded flag array is baked into the returned
    closure as a constant.

    dynamic=True (the shape-class padded-layout solve): imax/jmax set the
    padded CLASS geometry only; the live extents and update constants are
    call-time SMEM scalars, so rb_iter becomes
    `(p_padded, rhs_padded, ext_i32_12, geo_13) -> (p', res_sumsq)` with
    ext = (jmax, imax) and geo = (factor, idx2, idy2). Incompatible with
    `fluid` (obstacle lanes are class-ineligible)."""
    if pltpu is None:
        return None, 0, 0
    if dynamic and fluid is not None:
        raise ValueError("dynamic extents and obstacle flags are exclusive")
    h = tblock_halo(n_inner, dtype)
    if block_rows is None:
        block_rows = pick_block_rows_tblock(jmax, imax, dtype, n_inner)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _check_dtype(dtype, interpret)
    masked = fluid is not None
    itemsize = jnp.dtype(dtype).itemsize
    if not tblock_feasible(block_rows, h, padded_width(imax), itemsize,
                           masked):
        raise ValueError(
            f"tblock scratch {tblock_vmem_bytes(block_rows, h, padded_width(imax), itemsize, masked) >> 20} MiB "
            f"exceeds the VMEM budget (block_rows={block_rows}, h={h}, "
            f"wp={padded_width(imax)}); the grid is too wide for the fused "
            "kernel — the jnp path is the fallback"
        )

    dx2, dy2 = dx * dx, dy * dy
    width = imax + 2
    wp = padded_width(imax)
    nblocks = -(-(jmax + 2) // block_rows)
    rp = nblocks * block_rows + 2 * h
    kernel = functools.partial(
        _tblock_kernel,
        n_inner=n_inner,
        block_rows=block_rows,
        nblocks=nblocks,
        width=width,
        jmax=jmax,
        halo=h,
        factor=omega * 0.5 * (dx2 * dy2) / (dx2 + dy2),
        omega=omega,
        idx2=1.0 / dx2,
        idy2=1.0 / dy2,
        masked=masked,
        dynamic=dynamic,
    )

    n_any = 3 if masked else 2  # DMA'd HBM operands (sem count)
    scratch = [
        pltpu.VMEM((2, block_rows + 2 * h, wp), dtype),
        pltpu.VMEM((2, block_rows + 2 * h, wp), dtype),
    ]
    if masked:
        scratch.append(pltpu.VMEM((2, block_rows + 2 * h, wp), dtype))
    scratch += [
        pltpu.VMEM((2, block_rows, wp), dtype),
        pltpu.VMEM((1, wp), dtype),  # per-lane residual accumulator
        pltpu.SemaphoreType.DMA((2, n_any)),
        pltpu.SemaphoreType.DMA((2,)),
    ]
    in_specs = [pl.BlockSpec(memory_space=pl.ANY)] * n_any
    if dynamic:
        # the per-lane extent/constant scalars ride SMEM after the arrays
        in_specs += [pl.BlockSpec(memory_space=pltpu.SMEM)] * 2
    call = pl.pallas_call(
        kernel,
        grid=(nblocks,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, 1), lambda b: (0, 0), memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rp, wp), dtype),
            jax.ShapeDtypeStruct((1, 1), dtype),
        ],
        scratch_shapes=scratch,
        compiler_params=CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES
        ),
        interpret=interpret,
    )

    if dynamic:

        def rb_iter(p_padded, rhs_padded, ext, geo):
            p_padded, res = call(p_padded, rhs_padded, ext, geo)
            return p_padded, res[0, 0]
    elif masked:
        flg_padded = pad_array(jnp.asarray(fluid, dtype), block_rows, h)

        def rb_iter(p_padded, rhs_padded):
            p_padded, res = call(p_padded, rhs_padded, flg_padded)
            return p_padded, res[0, 0]
    else:

        def rb_iter(p_padded, rhs_padded):
            p_padded, res = call(p_padded, rhs_padded)
            return p_padded, res[0, 0]

    return rb_iter, block_rows, h


def _tblock_quarters_kernel(
    p_in,   # ANY (4, rp, W2p) stacked quarters [R0, R1, B0, B1]
    rhs,    # ANY (4, rp, W2p) stacked rhs quarters [F0, F1, G0, G1]
    p_out,  # ANY (4, rp, W2p)
    res,    # SMEM (1, 1)
    pw2,    # VMEM (2, 4, brq+2h, W2p) p windows, double-buffered
    rw2,    # VMEM (2, 4, brq+2h, W2p) rhs windows
    ob2,    # VMEM (2, 4, brq, W2p) out bands
    vacc,   # VMEM (1, W2p) per-lane residual accumulator
    ld_sem,  # DMA (2, 8)
    st_sem,  # DMA (2, 4)
    *,
    n_inner: int,
    block_rows: int,  # quarter rows per block
    nblocks: int,
    j2: int,   # (jmax+2)//2 logical quarter rows
    i2: int,   # (imax+2)//2 logical quarter lanes
    halo: int,
    factor: float,
    idx2: float,
    idy2: float,
    compute_dtype=None,
):
    """Temporal-blocked red-black sweep in the QUARTER layout
    (ops/sor_quarters.py derivation): every neighbour a uniform ±1 shift,
    every lane productive, the Neumann refresh 8 same-index edge selects.
    One iteration consumes ONE quarter-row of halo per side (= 2 grid rows,
    matching the checkerboard kernel's 2·n_inner grid-row halo).

    compute_dtype: when set (the bf16-storage mode), windows are loaded in
    the storage dtype (half the HBM traffic and VMEM footprint), upcast once
    per block, iterated in compute_dtype (f32), and downcast at the store —
    bf16 touches only the HBM arrays, never the arithmetic."""
    b = pl.program_id(0)
    brq = block_rows
    h = halo
    slot = b % 2
    nslot = (b + 1) % 2

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

    R0, R1, B0, B1 = (pw2[slot, qi] for qi in range(4))
    F0, F1, G0, G1 = (rw2[slot, qi] for qi in range(4))
    if compute_dtype is not None:
        R0, R1, B0, B1 = (x.astype(compute_dtype) for x in (R0, R1, B0, B1))
        F0, F1, G0, G1 = (x.astype(compute_dtype) for x in (F0, F1, G0, G1))

    # quarter-space coordinates of window cell (w, c): r = b*brq - h + w
    rr = b * brq - h + jax.lax.broadcasted_iota(jnp.int32, R0.shape, 0)
    cc = jax.lax.broadcasted_iota(jnp.int32, R0.shape, 1)
    # rectangular interiors per quarter (module docstring of sor_quarters)
    m_r0 = (rr >= 1) & (rr <= j2 - 1) & (cc >= 1) & (cc <= i2 - 1)
    m_r1 = (rr >= 0) & (rr <= j2 - 2) & (cc <= i2 - 2)
    m_b0 = (rr >= 1) & (rr <= j2 - 1) & (cc <= i2 - 2)
    m_b1 = (rr >= 0) & (rr <= j2 - 2) & (cc >= 1) & (cc <= i2 - 1)
    # Neumann edge-strip selects (same-index copies between quarters)
    row_lo = rr == 0
    row_hi = rr == j2 - 1
    col_lo = cc == 0
    col_hi_even = cc == i2 - 1   # i = imax (even-i quarters' last lane)
    j_int_even = (rr >= 1) & (rr <= j2 - 1)
    j_int_odd = (rr >= 0) & (rr <= j2 - 2)

    def upd(center, rhs_q, w, e, s, n, mask):
        r = rhs_q - ((e - 2.0 * center + w) * idx2
                     + (n - 2.0 * center + s) * idy2)
        rm = jnp.where(mask, r, 0.0)
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
    for _ in range(n_inner):
        # red pass (reads black)
        R0, r0 = upd(R0, F0, west(B0), B0, south(B1), B1, m_r0)
        R1, r1 = upd(R1, F1, B1, east(B1), B0, north(B0), m_r1)
        # black pass (reads updated red)
        B0, r2 = upd(B0, G0, R0, east(R0), south(R1), R1, m_b0)
        B1, r3 = upd(B1, G1, west(R1), R1, R0, north(R0), m_b1)
        # Neumann ghost refresh: 8 same-index edge selects
        R0 = jnp.where(row_lo & (cc >= 1) & (cc <= i2 - 1), B1, R0)
        B0 = jnp.where(row_lo & (cc <= i2 - 2), R1, B0)
        R1 = jnp.where(row_hi & (cc <= i2 - 2), B0, R1)
        B1 = jnp.where(row_hi & (cc >= 1) & (cc <= i2 - 1), R0, B1)
        R0 = jnp.where(col_lo & j_int_even, B0, R0)
        B1 = jnp.where(col_lo & j_int_odd, R1, B1)
        B0 = jnp.where(col_hi_even & j_int_even, R0, B0)
        R1 = jnp.where(col_hi_even & j_int_odd, B1, R1)

    @pl.when(b >= 2)
    def _():
        for c in store(b - 2, slot):
            c.wait()

    for qi, arr in enumerate((R0, R1, B0, B1)):
        band = arr[h: h + brq, :]
        if compute_dtype is not None:
            band = band.astype(p_out.dtype)
        ob2[slot, qi] = band
    for c in store(b, slot):
        c.start()

    # residual of the final iteration, owned bands only
    acc = jnp.zeros_like(vacc[...])
    for rq in (r0, r1, r2, r3):
        band = rq[h: h + brq, :]
        acc = acc + jnp.sum(band * band, axis=0, keepdims=True)
    vacc[...] += acc

    @pl.when(b == nblocks - 1)
    def _():
        res[0, 0] += jnp.sum(vacc[...])

    @pl.when(b == nblocks - 1)
    def _():
        for c in store(b, slot):
            c.wait()
        if nblocks > 1:
            for c in store(b - 1, nslot):
                c.wait()


def quarters_halo(n_inner: int, dtype) -> int:
    """Quarter-row halo for n_inner fused iterations: 1 quarter row per
    iteration per side, rounded to the sublane alignment."""
    a = _align(dtype)
    return max(a, -(-n_inner // a) * a)


def pad_quarters(p, block_rows_q: int, halo: int):
    """(jmax+2, imax+2) even-shaped array -> (4, rp, W2p) stacked padded
    quarter layout [R0, R1, B0, B1].

    LAYOUT SAFETY: any intermediate with a size-2 dim in the minor-two
    (tiled) positions explodes — [j2, 2, i2, 2] tiles the trailing 2 to a
    128-lane tile, a 64× blowup that OOMs the compiler outright at 8192²
    (f32[4097,2,4097,2] plans as 17 GB). Packing therefore uses staged
    single-axis stride-2 slices (outer-dim row split is a strided DMA,
    lane split a lane gather on the halved rows), which keep every
    intermediate in a sane layout."""
    J, I = p.shape
    j2, i2 = J // 2, I // 2
    r_even, r_odd = p[0::2], p[1::2]
    stacked = jnp.stack([
        r_even[:, 0::2],  # R0
        r_odd[:, 1::2],   # R1
        r_even[:, 1::2],  # B0
        r_odd[:, 0::2],   # B1
    ])
    nblocks = -(-j2 // block_rows_q)
    rp = nblocks * block_rows_q + 2 * halo
    w2p = -(-i2 // LANE) * LANE
    out = jnp.zeros((4, rp, w2p), p.dtype)
    return out.at[:, halo: halo + j2, :i2].set(stacked)


def unpad_quarters(xq, jmax: int, imax: int, halo: int):
    """Inverse of pad_quarters -> (jmax+2, imax+2), staged axis-at-a-time
    scatter form (lane interleave per row parity, then row interleave —
    same layout-safety/perf constraint as pad_quarters)."""
    j2, i2 = (jmax + 2) // 2, (imax + 2) // 2
    q = xq[:, halo: halo + j2, :i2]  # [R0, R1, B0, B1]
    r_even = jnp.zeros((j2, 2 * i2), xq.dtype)
    r_even = r_even.at[:, 0::2].set(q[0])  # R0
    r_even = r_even.at[:, 1::2].set(q[2])  # B0
    r_odd = jnp.zeros((j2, 2 * i2), xq.dtype)
    r_odd = r_odd.at[:, 0::2].set(q[3])   # B1
    r_odd = r_odd.at[:, 1::2].set(q[1])   # R1
    p = jnp.zeros((2 * j2, 2 * i2), xq.dtype)
    p = p.at[0::2].set(r_even)
    p = p.at[1::2].set(r_odd)
    return p


def quarters_vmem_bytes(brq: int, h: int, w2p: int, itemsize: int) -> int:
    """Scratch bytes of the quarters kernels (single-device and distributed
    share the buffer set): double-buffered p and rhs windows, out bands,
    per-lane accumulator."""
    win = 2 * 4 * (brq + 2 * h) * w2p
    return itemsize * (2 * win + 2 * 4 * brq * w2p + w2p)


def quarters_feasible(brq: int, h: int, w2p: int, itemsize: int) -> bool:
    """VMEM-feasibility guard (mirrors the octant accounting of
    sor3d_pallas._octants_feasible): the scratch set must fit the raised
    compile limit with headroom for Mosaic's own temporaries. A forced
    quarters layout on an extremely wide grid would otherwise crash the
    Mosaic compiler at first dispatch."""
    return quarters_vmem_bytes(brq, h, w2p, itemsize) <= VMEM_LIMIT_BYTES // 2


def make_rb_iter_tblock_quarters(
    imax: int,
    jmax: int,
    dx: float,
    dy: float,
    omega: float,
    dtype,
    *,
    n_inner: int = 4,
    block_rows_q: int | None = None,
    interpret: bool | None = None,
):
    """Temporal-blocked QUARTER-layout kernel: builds
    `(p_stacked, rhs_stacked) -> (p_stacked', res_sumsq_of_last_iter)`
    on the (4, rp, W2p) layout of `pad_quarters`. Requires even imax/jmax.
    Returns (rb_iter, block_rows_q, halo).

    Numerics: per-cell arithmetic keeps the reference association and is
    ulp-equivalent to the masked paths (compiler fma/fusion differences
    only — ops/sor_quarters.py); the residual summation order differs.

    bfloat16 `dtype` selects the bf16-storage / f32-compute mode: the HBM
    arrays and VMEM windows are bf16 (half the bytes on the roofline's HBM
    wall), the per-block iteration runs in f32, and the residual is
    accumulated and returned in f32 (bf16's 8-bit mantissa cannot hold a
    meaningful sum of squares)."""
    if pltpu is None:
        return None, 0, 0
    if imax % 2 or jmax % 2:
        raise ValueError("quarter layout needs even imax and jmax")
    h = quarters_halo(n_inner, dtype)
    if block_rows_q is None:
        # round-2 optimum at n_inner<=8 was 64 quarter-rows (= 128 grid
        # rows); the round-3 depth sweep (4096² f32, 3 same-session runs)
        # found deeper blocking wants taller blocks to amortize the larger
        # halo recompute: n16/brq128 measures 127-131G vs n8/brq64's
        # 76-84G, with n20+ falling off again (h=24 recompute)
        j2 = (jmax + 2) // 2
        whole = -(-j2 // _align(dtype)) * _align(dtype)
        base = 64 if n_inner < 12 else 128
        block_rows_q = max(_align(dtype), h, min(base, whole))
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _check_dtype(dtype, interpret)

    dx2, dy2 = dx * dx, dy * dy
    j2, i2 = (jmax + 2) // 2, (imax + 2) // 2
    w2p = -(-i2 // LANE) * LANE
    nblocks = -(-j2 // block_rows_q)
    rp = nblocks * block_rows_q + 2 * h
    itemsize = jnp.dtype(dtype).itemsize
    if not quarters_feasible(block_rows_q, h, w2p, itemsize):
        raise ValueError(
            f"quarters scratch {quarters_vmem_bytes(block_rows_q, h, w2p, itemsize) >> 20} MiB "
            f"exceeds the VMEM budget (brq={block_rows_q}, h={h}, "
            f"w2p={w2p}); reduce tpu_sor_inner or use tpu_sor_layout "
            "checkerboard"
        )
    # bf16 storage iterates in f32 (see docstring); f32/f64 compute as stored
    bf16 = jnp.dtype(dtype) == jnp.dtype(jnp.bfloat16)
    compute_dtype = jnp.float32 if bf16 else None
    acc_dtype = jnp.float32 if bf16 else dtype
    kernel = functools.partial(
        _tblock_quarters_kernel,
        n_inner=n_inner,
        block_rows=block_rows_q,
        nblocks=nblocks,
        j2=j2,
        i2=i2,
        halo=h,
        factor=omega * 0.5 * (dx2 * dy2) / (dx2 + dy2),
        idx2=1.0 / dx2,
        idy2=1.0 / dy2,
        compute_dtype=compute_dtype,
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
            jax.ShapeDtypeStruct((4, rp, w2p), dtype),
            jax.ShapeDtypeStruct((1, 1), acc_dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, 4, block_rows_q + 2 * h, w2p), dtype),
            pltpu.VMEM((2, 4, block_rows_q + 2 * h, w2p), dtype),
            pltpu.VMEM((2, 4, block_rows_q, w2p), dtype),
            pltpu.VMEM((1, w2p), acc_dtype),
            pltpu.SemaphoreType.DMA((2, 8)),
            pltpu.SemaphoreType.DMA((2, 4)),
        ],
        compiler_params=CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES
        ),
        interpret=interpret,
    )

    def rb_iter(p_stacked, rhs_stacked):
        p_stacked, res = call(p_stacked, rhs_stacked)
        return p_stacked, res[0, 0]

    return rb_iter, block_rows_q, h


def neumann_bc_padded(p, jmax: int, imax: int):
    """Homogeneous-Neumann ghost copy in the padded layout (parity with
    ops/sor.py `neumann_bc`: walls only, corners untouched)."""
    a = _align(p.dtype)
    lo, hi = a, a + jmax + 1  # padded indices of the ghost rows
    p = p.at[lo, 1 : imax + 1].set(p[lo + 1, 1 : imax + 1])
    p = p.at[hi, 1 : imax + 1].set(p[hi - 1, 1 : imax + 1])
    p = p.at[lo + 1 : hi, 0].set(p[lo + 1 : hi, 1])
    p = p.at[lo + 1 : hi, imax + 1].set(p[lo + 1 : hi, imax])
    return p


def make_rb_iter_pallas(
    imax: int,
    jmax: int,
    dx: float,
    dy: float,
    omega: float,
    dtype,
    *,
    block_rows: int | None = None,
    interpret: bool | None = None,
):
    """Build `(p_padded, rhs_padded) -> (p_padded', res_sumsq)`: one full
    red-black SOR iteration (red then black half-sweep) with the
    un-normalized residual sum of r² over both sweeps. Operates on the padded
    layout (`pad_array`/`unpad_array`); returns (rb_iter, block_rows)."""
    if pltpu is None:
        return None, 0
    if block_rows is None:
        block_rows = pick_block_rows(jmax, imax, dtype)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _check_dtype(dtype, interpret)

    dx2, dy2 = dx * dx, dy * dy
    width = imax + 2
    wp = padded_width(imax)
    a = _align(dtype)
    kernel = functools.partial(
        _rb_kernel,
        block_rows=block_rows,
        width=width,
        jmax=jmax,
        pad=a,
        factor=omega * 0.5 * (dx2 * dy2) / (dx2 + dy2),
        idx2=1.0 / dx2,
        idy2=1.0 / dy2,
    )
    nblocks = -(-(jmax + 2) // block_rows)
    rp = nblocks * block_rows + 2 * a

    call = pl.pallas_call(
        kernel,
        grid=(2, nblocks),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, 1), lambda phase, b: (0, 0), memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rp, wp), dtype),
            jax.ShapeDtypeStruct((1, 1), dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_rows + 2 * a, wp), dtype),
            pltpu.VMEM((block_rows, wp), dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES
        ),
        input_output_aliases={0: 0},
        interpret=interpret,
    )

    def rb_iter(p_padded, rhs_padded):
        p_padded, res = call(p_padded, rhs_padded)
        return p_padded, res[0, 0]

    return rb_iter, block_rows
