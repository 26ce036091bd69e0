"""Distributed QUARTER-layout red-black SOR: geometry, packing, deep-halo
exchange, and the jnp twin of the per-shard Pallas kernel.

This is the production multi-chip pressure-solve path (round-3 close of the
round-2 gap "the hot Pallas kernels are not wired into the distributed
solvers"): the quarter decomposition of ops/sor_quarters.py — every 5-point
neighbour a uniform ±1 shift, every lane productive (the 4096² single-chip
headline kernel) — carried ACROSS the distributed convergence loop, with one
communication-avoiding deep-halo exchange per n red-black iterations, exactly
like the jnp CA path of parallel/stencil2d.py. In the reference the hot SOR
kernel is what runs on every rank (assignment-5/ex5-nazifkar/src/solver.c:
586-655); here the quarters kernel runs on every TPU chip.

LAYOUT (the one idea everything below depends on): all four quarters of a
shard are GLOBALLY ALIGNED — stored row ρ of every quarter slot holds global
quarter-row gqr = ρ - h - n + qoff_j (qoff_j = joff/2; h = kernel window
halo, n = CA depth in quarter rows), and stored col c holds
gqc = c - n + qoff_i. Because shard extents jl/il are even, joff/ioff are
even on every shard, so the parity split is decomposition-invariant and the
same-index inter-quarter identities of the single-device kernel (W/E/S/N
uniform shifts, 8 same-index Neumann edge selects) hold verbatim. What
becomes per-parity is only WHICH stored rows are owned: even-parity rows own
[h+n+1, h+n+jl/2], odd-parity rows [h+n, h+n+jl/2-1] — static bounds, baked
into masks.

CA semantics (≙ stencil2d.ca_rb_iters): one iteration consumes ONE quarter
row of ghost validity per side; a depth-n quarter exchange buys n exact
iterations; ghost cells are redundantly recomputed by both neighbouring
shards with identical arithmetic, so the distributed trajectory equals the
single-device quarters trajectory. Updates are clipped to the stored logical
region (static bounds), so dead padding never evolves and every value is
deterministic.

The Pallas kernel twin lives in ops/sor_qdist.py; this module's
`rb_iters_q_jnp` mirrors its per-cell arithmetic op-for-op (roll +
where-select, reference association) so interpret-mode kernel output is
bitwise-comparable on the CPU mesh (tests/test_quarters_dist.py).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp
from jax import lax

from .comm import CartComm, _nbr_perm

# slot order in the stacked (4, rp, w2p) array; (pr, pc) = global row/col
# parity of the cells each slot holds (ops/sor_quarters.py derivation)
SLOTS = ("R0", "R1", "B0", "B1")
SLOT_PARITY = ((0, 0), (1, 1), (0, 1), (1, 0))  # (pr, pc) per slot


@dataclass(frozen=True)
class QGeom:
    """Static geometry of the distributed stacked quarter layout."""

    jmax: int  # global interior rows
    imax: int
    jl: int  # per-shard interior rows (even)
    il: int
    n: int  # CA depth in quarter rows = RB iterations per exchange
    h: int  # kernel window halo (>= n, sublane-aligned)
    brq: int  # kernel block height (quarter rows)
    jq: int  # logical stored row span: jl/2 + 2n + 1
    iq: int  # logical stored col span: il/2 + 2n + 1
    rp: int  # padded stored rows: nblocks*brq + 2h
    w2p: int  # padded stored cols (lane multiple)
    nblocks: int

    @property
    def row_base(self) -> int:
        """Stored row of global quarter-row qoff_j (= λ n + window halo h)."""
        return self.h + self.n

    @property
    def col_base(self) -> int:
        return self.n


def make_qgeom(jmax, imax, jl, il, n, dtype, brq: int | None = None) -> QGeom:
    from ..ops import sor_pallas as sp

    a = sp._align(dtype)
    h = max(a, -(-n // a) * a)  # sublane-aligned window halo >= n
    jq = jl // 2 + 2 * n + 1
    iq = il // 2 + 2 * n + 1
    if brq is None:
        # same depth-aware policy as the single-device maker: deeper
        # temporal blocking wants taller blocks to amortize halo recompute
        # (sor_pallas.make_rb_iter_tblock_quarters round-3 sweep)
        whole = -(-jq // a) * a
        base = 64 if n < 12 else 128
        brq = max(a, h, min(base, whole))
    nblocks = -(-jq // brq)
    rp = nblocks * brq + 2 * h
    w2p = -(-iq // sp.LANE) * sp.LANE
    return QGeom(jmax, imax, jl, il, n, h, brq, jq, iq, rp, w2p, nblocks)


def qdist_supported(jmax, imax, jl, il) -> bool:
    """Even global dims (quarter structure) + even shard extents (parity
    alignment) + enough owned rows to ship a depth-1 strip."""
    return (
        jmax % 2 == 0 and imax % 2 == 0
        and jl % 2 == 0 and il % 2 == 0
        and jl >= 4 and il >= 4
    )


def qdist_clamp(n: int, jl: int, il: int) -> int:
    """Ghost strips must come from owned cells: n <= min(jl, il)/2 - 1
    (the odd-parity owned extent is jl/2 with a one-row stagger, so keep a
    one-row margin)."""
    return max(1, min(n, min(jl, il) // 2 - 1))


def quarters_dispatch(param, jmax, imax, jl, il, dx, dy, dtype,
                      record_key: str, plain_sor: bool):
    """The dispatch ladder shared by the 2-D distributed solvers
    (models/poisson_dist, models/ns2d_dist): decide whether the
    quarter-layout production path runs, build the per-shard Pallas kernel
    (interpret off-TPU) or the jnp twin under a forced layout, and record
    the decision in the dispatch probe.

    Returns (rb_q, qg, n_q, pallas_q); rb_q is None when the caller should
    run its grid-space jnp CA path (and record its own fallback label).
    Raises ValueError on a forced `tpu_sor_layout quarters` that is
    structurally ineligible."""
    from ..utils import dispatch as _dispatch

    layout = param.tpu_sor_layout
    qsup = qdist_supported(jmax, imax, jl, il)
    if layout == "quarters" and not (qsup and plain_sor):
        raise ValueError(
            "tpu_sor_layout quarters needs even global and per-shard "
            "extents (>= 4) and the plain tpu_solver sor path"
        )
    if not (plain_sor and qsup and layout in ("auto", "quarters")):
        return None, None, 0, False
    from ..models.poisson import _use_pallas

    if not (layout == "quarters" or _use_pallas("auto", dtype)):
        return None, None, 0, False
    n_q = qdist_clamp(max(param.tpu_ca_inner, param.tpu_sor_inner), jl, il)
    qg = make_qgeom(jmax, imax, jl, il, n_q, dtype)
    try:
        from ..ops.sor_qdist import make_rb_iters_qdist

        rb_q = make_rb_iters_qdist(qg, dx, dy, param.omg, dtype)
    except ValueError:
        rb_q = None
    if rb_q is not None:
        _dispatch.record(record_key, f"pallas_quarters ca{n_q}")
        return rb_q, qg, n_q, True
    if layout == "quarters":
        # forced layout without a lowerable kernel (e.g. f64): the jnp twin
        # runs the same quarter-space CA choreography
        dx2, dy2 = dx * dx, dy * dy
        factor = param.omg * 0.5 * (dx2 * dy2) / (dx2 + dy2)

        def rb_q(qoffs, xq, rq):
            m = q_masks(qg, qoffs[0], qoffs[1])
            return rb_iters_q_jnp(
                xq, rq, qg, m, factor, 1.0 / dx2, 1.0 / dy2
            )

        _dispatch.record(record_key, f"jnp_quarters ca{n_q}")
        return rb_q, qg, n_q, False
    return None, None, 0, False


# ----------------------------------------------------------------------
# Packing: (jl+2, il+2) extended block <-> stacked (4, rp, w2p)
# ----------------------------------------------------------------------


def pack_ext_to_q(ext, g: QGeom):
    """Extended halo-1 block -> stacked quarter layout. Extended cell (a, b)
    is global (a + joff, b + ioff); joff/ioff even, so local parity IS
    global parity and the slot split is the single-device one. All four
    quarters land at the same stored offsets [row_base, row_base + jl/2]
    × [col_base, col_base + il/2] (the +1 ghost row/col included).

    The split is the single-device one, rows then lanes
    (ops/sor_pallas.split_quarters and its layout-safety note), not a 2-D
    stride-2 index `ext[pr::2, pc::2]`: jnp lowers a 2-D index whose steps
    are not 1 to an element-wise gather (1×1 slices through an s32 index
    vector of every cell), ~90% of a 4096² step on a v5e 2x2 mesh. A
    `lax.slice` with strides (2, 2) has no gather but was 5× slower than
    the staged split there (1.04 vs 0.21 ms a pack at a 2048² shard)."""
    from ..ops.sor_pallas import split_quarters

    stacked = split_quarters(ext)
    out = jnp.zeros((4, g.rp, g.w2p), ext.dtype)
    return out.at[
        :,
        g.row_base : g.row_base + g.jl // 2 + 1,
        g.col_base : g.col_base + g.il // 2 + 1,
    ].set(stacked)


def unpack_q_to_ext(xq, g: QGeom):
    """Inverse of pack_ext_to_q (staged axis-at-a-time interleave — the
    layout-safe form of ops/sor_pallas.unpad_quarters)."""
    j2 = g.jl // 2 + 1
    i2 = g.il // 2 + 1
    q = xq[:, g.row_base : g.row_base + j2, g.col_base : g.col_base + i2]
    r_even = jnp.zeros((j2, 2 * i2), xq.dtype)
    r_even = r_even.at[:, 0::2].set(q[0])  # R0
    r_even = r_even.at[:, 1::2].set(q[2])  # B0
    r_odd = jnp.zeros((j2, 2 * i2), xq.dtype)
    r_odd = r_odd.at[:, 0::2].set(q[3])  # B1
    r_odd = r_odd.at[:, 1::2].set(q[1])  # R1
    p = jnp.zeros((2 * j2, 2 * i2), xq.dtype)
    p = p.at[0::2].set(r_even)
    p = p.at[1::2].set(r_odd)
    return p


# ----------------------------------------------------------------------
# Deep-halo exchange in quarter space
# ----------------------------------------------------------------------


def _owned_start_row(g: QGeom, pr: int) -> int:
    return g.row_base + (1 if pr == 0 else 0)


def _owned_start_col(g: QGeom, pc: int) -> int:
    return g.col_base + (1 if pc == 0 else 0)


def q_exchange(xq, comm: CartComm, g: QGeom):
    """commExchange in quarter space: refresh the depth-n ghost strips of
    every quarter from the ±1 mesh neighbours, PROC_NULL semantics at the
    physical walls (≙ halo_exchange(depth=2n) of the grid-space CA path —
    n quarter rows = 2n grid rows). Slots pair by parity — (R0, B0) share
    row offsets, (R1, B1) the staggered ones — so each (axis, direction,
    parity) is ONE ppermute of a stacked 2-slot strip: 8 ppermutes total."""
    n = g.n
    jl2, il2 = g.jl // 2, g.il // 2

    # rows over mesh axis "j" (array axis 1 of each slot)
    nper = comm.axis_size("j")
    if nper > 1:
        idx = lax.axis_index("j")
        for pr, slots in ((0, (0, 2)), (1, (1, 3))):
            os = _owned_start_row(g, pr)
            pair = jnp.stack([xq[slots[0]], xq[slots[1]]])
            # low ghosts [os-n, os) <- -1 neighbour's owned top strip
            strip = pair[:, os + jl2 - n : os + jl2, :]
            recv = lax.ppermute(strip, "j", _nbr_perm(nper, True, False))
            recv = jnp.where(idx > 0, recv, pair[:, os - n : os, :])
            pair = pair.at[:, os - n : os, :].set(recv)
            # high ghosts [os+jl2, os+jl2+n) <- +1 neighbour's owned bottom
            strip = pair[:, os : os + n, :]
            recv = lax.ppermute(strip, "j", _nbr_perm(nper, False, False))
            recv = jnp.where(
                idx < nper - 1, recv, pair[:, os + jl2 : os + jl2 + n, :]
            )
            pair = pair.at[:, os + jl2 : os + jl2 + n, :].set(recv)
            xq = xq.at[slots[0]].set(pair[0]).at[slots[1]].set(pair[1])

    # cols over mesh axis "i" (array axis 2 of each slot)
    nper = comm.axis_size("i")
    if nper > 1:
        idx = lax.axis_index("i")
        for pc, slots in ((0, (0, 3)), (1, (1, 2))):
            os = _owned_start_col(g, pc)
            pair = jnp.stack([xq[slots[0]], xq[slots[1]]])
            strip = pair[:, :, os + il2 - n : os + il2]
            recv = lax.ppermute(strip, "i", _nbr_perm(nper, True, False))
            recv = jnp.where(idx > 0, recv, pair[:, :, os - n : os])
            pair = pair.at[:, :, os - n : os].set(recv)
            strip = pair[:, :, os : os + n]
            recv = lax.ppermute(strip, "i", _nbr_perm(nper, False, False))
            recv = jnp.where(
                idx < nper - 1, recv, pair[:, :, os + il2 : os + il2 + n]
            )
            pair = pair.at[:, :, os + il2 : os + il2 + n].set(recv)
            xq = xq.at[slots[0]].set(pair[0]).at[slots[1]].set(pair[1])
    return xq


# ----------------------------------------------------------------------
# Masks + the jnp twin of the per-shard kernel
# ----------------------------------------------------------------------


def q_masks(g: QGeom, qoff_j, qoff_i):
    """Per-slot boolean masks on the full (rp, w2p) stored plane, from
    GLOBAL quarter coordinates (qoff_j/qoff_i are the shard's traced
    offsets). Same formulas the Pallas kernel computes from its scalar
    prefetch — keep the two in lockstep (ops/sor_qdist.py).

    Returns dict with per-slot 'upd' (update = global interior ∩ stored
    logical region), 'own' (static owned region, residual accounting),
    and the 8 wall-refresh masks keyed like the kernel's select order."""
    rho = jnp.arange(g.rp, dtype=jnp.int32)[:, None]
    col = jnp.arange(g.w2p, dtype=jnp.int32)[None, :]
    lam = rho - g.h  # logical stored row
    gqr = lam - g.n + qoff_j
    gqc = col - g.n + qoff_i
    valid = (lam >= 0) & (lam < g.jq) & (col >= 0) & (col < g.iq)
    # updates freeze the outermost stored ring (read-only, like the grid CA
    # path's ca_half_sweep [1:-1] slice): its neighbours are dead padding.
    # In grid space the frozen ring IS the outermost grid ghost row/col, so
    # the proven depth-2n CA validity argument carries over unchanged.
    valid_upd = (
        (lam >= 1) & (lam <= g.jq - 2) & (col >= 1) & (col <= g.iq - 2)
    )

    def row_int(pr):
        if pr == 0:
            return (gqr >= 1) & (gqr <= g.jmax // 2)
        return (gqr >= 0) & (gqr <= g.jmax // 2 - 1)

    def col_int(pc):
        if pc == 0:
            return (gqc >= 1) & (gqc <= g.imax // 2)
        return (gqc >= 0) & (gqc <= g.imax // 2 - 1)

    def own_rows(pr):
        os = _owned_start_row(g, pr)
        return (rho >= os) & (rho < os + g.jl // 2)

    def own_cols(pc):
        os = _owned_start_col(g, pc)
        return (col >= os) & (col < os + g.il // 2)

    m = {"upd": [], "own": []}
    for pr, pc in SLOT_PARITY:
        m["upd"].append(row_int(pr) & col_int(pc) & valid_upd)
        m["own"].append(own_rows(pr) & own_cols(pc))
    # wall-refresh masks (tangentially clipped to the global interior)
    m["row_lo_pc0"] = (gqr == 0) & col_int(0) & valid  # gj==0, even i
    m["row_lo_pc1"] = (gqr == 0) & col_int(1) & valid  # gj==0, odd i
    m["row_hi_pc0"] = (gqr == g.jmax // 2) & col_int(0) & valid
    m["row_hi_pc1"] = (gqr == g.jmax // 2) & col_int(1) & valid
    m["col_lo_pr0"] = (gqc == 0) & row_int(0) & valid
    m["col_lo_pr1"] = (gqc == 0) & row_int(1) & valid
    m["col_hi_pr0"] = (gqc == g.imax // 2) & row_int(0) & valid
    m["col_hi_pr1"] = (gqc == g.imax // 2) & row_int(1) & valid
    return m


def _upd(center, rhs_q, w, e, s, n_, mask, factor, idx2, idy2):
    """The kernel's per-cell arithmetic, op-for-op (reference association;
    where-select, not multiply — ghost garbage must not poison via inf·0)."""
    r = rhs_q - ((e - 2.0 * center + w) * idx2 + (n_ - 2.0 * center + s) * idy2)
    rm = jnp.where(mask, r, jnp.zeros_like(r))
    return center - factor * rm, rm


def rb_iters_q_jnp(xq, rhsq, g: QGeom, m, factor, idx2, idy2):
    """n full red-black iterations + Neumann refresh on the stacked stored
    plane — the jnp twin of ops/sor_qdist's Pallas kernel (identical
    neighbour identities, select masks, and update order; rolls wrap dead
    cells that every mask excludes). Returns (xq', owned sum of r² of the
    LAST iteration)."""
    R0, R1, B0, B1 = xq[0], xq[1], xq[2], xq[3]
    F0, F1, G0, G1 = rhsq[0], rhsq[1], rhsq[2], rhsq[3]

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
        R0, r0 = _upd(R0, F0, west(B0), B0, south(B1), B1, m["upd"][0],
                      factor, idx2, idy2)
        R1, r1 = _upd(R1, F1, B1, east(B1), B0, north(B0), m["upd"][1],
                      factor, idx2, idy2)
        B0, r2 = _upd(B0, G0, R0, east(R0), south(R1), R1, m["upd"][2],
                      factor, idx2, idy2)
        B1, r3 = _upd(B1, G1, west(R1), R1, R0, north(R0), m["upd"][3],
                      factor, idx2, idy2)
        R0 = jnp.where(m["row_lo_pc0"], B1, R0)
        B0 = jnp.where(m["row_lo_pc1"], R1, B0)
        R1 = jnp.where(m["row_hi_pc1"], B0, R1)
        B1 = jnp.where(m["row_hi_pc0"], R0, B1)
        R0 = jnp.where(m["col_lo_pr0"], B0, R0)
        B1 = jnp.where(m["col_lo_pr1"], R1, B1)
        B0 = jnp.where(m["col_hi_pr0"], R0, B0)
        R1 = jnp.where(m["col_hi_pr1"], B1, R1)

    rsq = jnp.zeros((), xq.dtype)
    for rq, own in zip((r0, r1, r2, r3), m["own"]):
        rsq = rsq + jnp.sum(jnp.where(own, rq * rq, jnp.zeros_like(rq)))
    return jnp.stack([R0, R1, B0, B1]), rsq
