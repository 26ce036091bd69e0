"""Comm/compute overlap: the interior/boundary split machinery shared by
the overlapped distributed solvers (models/ns2d_dist, ns3d_dist).

The overlapped step (`tpu_overlap`, ROADMAP item 2) restructures the
fused deep-halo step so the ppermute exchange for step N+1's halos rides
the loop carry as a DOUBLE-BUFFERED pair of deep blocks: posted right
after step N's POST kernel (the moment the new edge cells exist), and
consumed one iteration later by the BOUNDARY half of the PRE kernel only.
The INTERIOR half of PRE runs on the stale re-embedded block, so the
traced program carries no dependency path from the exchange to it — the
structural property that lets XLA's latency-hiding scheduler / collective
pipeliner fly the exchange behind the interior compute, and the property
`analysis/commcheck.overlap_schedule_violations` pins statically.

The split is write-gated, not grid-gated: both halves are the SAME
Pallas kernel (ops/ns2d_fused, ns3d_fused — the global-coordinate-gated
discipline) on the two buffers, merged by `merge_halves` with the
interior mask below. Cells in the interior region have a FUSE_CHAIN
dependency cone that never reaches the exchanged strips (the outer
FUSE_DEEP_HALO layers of the deep block), so the interior half's values
are bitwise those of the serial fused step; the boundary half reads the
exchanged buffer — bitwise the block the serial step exchanges — so the
merge reproduces the serial trajectory exactly (parity test-pinned,
tests/test_overlap.py; footprint-pinned, analysis/halocheck.py's
overlap-interior entries). Restricting each half's GRID to its region is
the follow-on optimization; the dataflow split is what buys the overlap.

Staleness safety: the carried buffers wear a generation tag (the step
count they were exchanged for). `generation_guard` poisons dt with NaN
on a mismatch, which the drive loop's divergence trigger catches — a
skewed double buffer is detected, never silently consumed (mutation
test-pinned via the GEN_SKEW hook).
"""

from __future__ import annotations

import jax.numpy as jnp

# Test hook: the generation-skew mutation test (tests/test_overlap.py)
# monkeypatches this to a nonzero offset before building an overlapped
# solver, forging a step that consumes a stale double buffer. Production
# value is 0 — the guard then compiles to a compare that always passes.
GEN_SKEW = 0


def interior_slices(local_extents, rim: int, partitioned=None):
    """Per-axis slices of the interior region on the (l+2)-extended
    block: indices [rim, l+2-rim). Empty when a shard is thinner than
    two rims — the split then degenerates to boundary-everywhere, which
    is correct (and overlap-free).

    `partitioned` (per-axis bools, default all True) drops the rim on
    UNPARTITIONED mesh axes: a size-1 axis exchanges nothing
    (`_exchange_axis` short-circuits), so the stale block and the
    double-buffered exchanged block are bit-identical along it — the
    interior half's cone may touch those sides freely. This is what
    lets the grid-restricted boundary half shrink to two row bands on
    a (P, 1) mesh instead of sweeping every row for column strips that
    do not exist."""
    if partitioned is None:
        partitioned = (True,) * len(local_extents)
    return tuple(
        slice(rim if part else 0, ext + 2 - (rim if part else 0))
        for ext, part in zip(local_extents, partitioned)
    )


def interior_mask(local_extents, rim: int, partitioned=None):
    """Boolean interior mask on the extended block (the merge gate of
    `merge_halves`). Local-geometry only: ragged pad cells and wall
    shards need no special case — both halves compute identical values
    wherever the cone avoids the strips, and the strips are a local
    property of the block. See `interior_slices` for `partitioned`."""
    shape = tuple(ext + 2 for ext in local_extents)
    m = jnp.zeros(shape, bool)
    return m.at[interior_slices(local_extents, rim, partitioned)].set(True)


def merge_halves(mask, interior_vals, boundary_vals):
    """Elementwise merge of the two PRE halves: interior cells from the
    stale-block call, the rim from the exchanged-buffer call. A
    `jnp.where` (not masked addition) so -0.0/NaN payloads survive
    bit-exactly."""
    return tuple(
        jnp.where(mask, i, b) for i, b in zip(interior_vals, boundary_vals)
    )


# ----------------------------------------------------------------------
# Grid restriction (ROADMAP item 3 / `tpu_overlap_restrict`): the region
# plan that turns the two full write-gated PRE sweeps into banded Pallas
# grids — the interior half sweeps only the row blocks of the interior
# core, the boundary half only the OVERLAP_RIM bands (plus the full rows
# whenever a non-leading axis is partitioned: column strips cannot be
# row-banded). Rows are in the padded-layout frame the fused kernels
# block over (ops/ns2d_fused._layout): the full sweep's block k covers
# rows [k*br, (k+1)*br) of R = nblocks*br total.
# ----------------------------------------------------------------------


def check_bands(grid_bands, block_rows: int, nblocks: int,
                label: str = "block_rows") -> None:
    """Refuse a band list that is not sorted-disjoint or that overhangs
    the padded layout — the one validation both fused-PRE builders run
    on `grid_bands` before restricting their grid (a double-stored row
    would race the output DMA; an overhanging band would DMA past the
    padded array)."""
    last_end = 0
    for s, n in grid_bands:
        if s < last_end or n < 1 or s + n * block_rows > \
                nblocks * block_rows:
            raise ValueError(
                f"grid_bands {grid_bands} do not tile the padded "
                f"layout ({label}={block_rows}, nblocks={nblocks}) "
                "disjointly")
        last_end = s + n * block_rows


def band_cover(lo: int, hi: int, block_rows: int, total_rows: int,
               align: int = 1):
    """The (start_row, n_blocks) band of `block_rows`-row blocks that
    covers rows [lo, hi) and stays inside [0, total_rows): the start is
    rounded down to a multiple of `align` (a DMA row offset must sit on
    the sublane tile) and shifted down when the rounded-up coverage would
    overhang (extra covered rows are valid compute — every write is
    globally gated)."""
    lo = lo // align * align
    n = -(-(hi - lo) // block_rows)
    start = max(0, min(lo, total_rows - n * block_rows))
    return (start, n)


def _merge_bands(bands, block_rows, total_rows):
    """Coalesce overlapping/adjacent bands so no row is stored twice
    (a double-store would race the output DMA), keeping every band
    inside [0, total_rows): a merged band's rounded-up block count can
    overhang the layout (its end is the max of the inputs' ends but its
    count is re-derived by ceil), so merged starts are re-clamped like
    `band_cover`'s — which can re-overlap the previous band, hence the
    fixpoint loop (bands only move down and merge, so it terminates)."""
    out = [b for b in bands if b[1] > 0]
    while True:
        merged = []
        for s, n in sorted(out):
            if merged and s <= merged[-1][0] + merged[-1][1] * block_rows:
                ps, pn = merged[-1]
                end = max(ps + pn * block_rows, s + n * block_rows)
                merged[-1] = (ps, -(-(end - ps) // block_rows))
            else:
                merged.append((s, n))
        clamped = [(max(0, min(s, total_rows - n * block_rows)), n)
                   for s, n in merged]
        if clamped == out:
            return tuple(clamped)
        out = clamped


def region_plan(local_extents, rim: int, ext_pad: int, block_rows: int,
                nblocks: int, width: int, partitioned, align: int = 1):
    """Banded grid plan for the two PRE halves of one shard geometry,
    over the LEADING (block-tiled) axis. Returns None when the interior
    region is empty (the split is boundary-everywhere — nothing to
    restrict); otherwise a dict:

      int_bands / bnd_bands   ((start_row, n_blocks), ...) for the
                              interior / boundary half's Pallas grid
      cells                   summed swept cells of the two banded
                              grids (blocks x block_rows x width)
      cells_full              the 2x full-sweep count they replace
      win                     cells < cells_full — the `auto` predicate

    The interior band covers exactly the interior-merge region
    (`interior_slices` with the same `partitioned` flags — the mask and
    the grid cannot drift apart); the boundary band covers the rim rows,
    widened to every row when any non-leading axis is partitioned (its
    column strips live in every row). `align` rounds every band start down
    to a multiple of it: the 2-D kernels DMA row windows from the band
    start, and Mosaic refuses a row offset off the sublane tile (found
    compiling the 2x2 dcavity 4096² chunk for a v5e)."""
    L0 = local_extents[0]
    R = nblocks * block_rows
    lead = partitioned[0]
    cross = any(partitioned[1:])
    rim0 = rim if lead else 0
    int_lo = ext_pad + rim0
    int_hi = ext_pad + L0 + 2 - rim0
    if int_hi <= int_lo:
        return None
    int_bands = _merge_bands(
        [band_cover(int_lo, int_hi, block_rows, R, align)], block_rows, R)
    if cross:
        bnd = [band_cover(ext_pad, ext_pad + L0 + 2, block_rows, R, align)]
    elif lead:
        bnd = [band_cover(ext_pad, ext_pad + rim, block_rows, R, align),
               band_cover(ext_pad + L0 + 2 - rim, ext_pad + L0 + 2,
                          block_rows, R, align)]
    else:
        # no partitioned axis at all: no exchange, no overlap, no plan
        return None
    bnd_bands = _merge_bands(bnd, block_rows, R)
    blocks = sum(n for _, n in int_bands) + sum(n for _, n in bnd_bands)
    cells = blocks * block_rows * width
    cells_full = 2 * R * width
    return {
        "int_bands": int_bands,
        "bnd_bands": bnd_bands,
        "cells": cells,
        "cells_full": cells_full,
        "win": cells < cells_full,
    }


def generation_guard(dt, gen, nt):
    """Stale-double-buffer detector: the carried halo buffers were
    exchanged for step `gen`; the consuming step is `nt`. On a mismatch
    dt is poisoned with NaN, so t goes NaN and the drive loop's
    divergence trigger (models/_driver.drive_chunks) reports a
    structured failure instead of the solver silently consuming stale
    halos. GEN_SKEW (module hook) forges the mismatch for the mutation
    test."""
    ok = (gen + GEN_SKEW) == nt
    return jnp.where(ok, dt, jnp.asarray(jnp.nan, dt.dtype))
