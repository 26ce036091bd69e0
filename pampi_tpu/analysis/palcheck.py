"""Pallas kernel-resource checker: would this `pallas_call` compile and
fit on a TPU core?

The bug class this guards: a VMEM-overflowing scratch buffer, a mistiled
block, or an out-of-bounds index map in a Pallas kernel fails only at
Mosaic compile time ON A TPU — which this container does not have. Every
such failure found during the on-chip campaign so far (the tblock
feasibility guard, the quarters VMEM fallback, the 128-lane padding
convention) is statically decidable from the traced program, so this pass
decides them at lint time, on CPU, over the same `jaxprcheck`
trace matrix the launch-count contract uses plus standalone large-grid
kernel builds (`extra_entries`) where the grids are big enough to
actually partition into blocks.

Per `pallas_call` eqn (all data read off `grid_mapping` — block shapes,
index maps, memory spaces — and the kernel jaxpr's scratch operands):

  tiling       blocks that PARTITION an array dimension (block extent <
               array extent) must be multiples of the dtype tile
               granularity in the last two dims — lane 128 always,
               sublane 8/16/32 by itemsize (f32 (8,128), bf16 (16,128),
               int8 (32,128)). Full-extent blocks are exempt: Mosaic
               pads a whole-array window, but a misaligned PARTITIONED
               block re-tiles every grid step.
  vmem budget  static per-launch footprint: block windows bound to VMEM
               (double-buffered when the grid pipelines, i.e. >1 step)
               plus VMEM scratch, against the kernel's own declared
               `vmem_limit_bytes` (falling back to the repo-wide
               `ops/sor_pallas.VMEM_LIMIT_BYTES`). `pl.ANY` operands
               live in HBM and are charged nothing — their windows enter
               via the explicit scratch buffers the kernel DMAs into.
  index bounds grid × index map must stay in-bounds of each operand:
               every grid point's block start (Blocked semantics:
               index × block shape) must land inside the array (the
               final block may overhang — Mosaic masks it). Index maps
               are evaluated concretely per grid point; maps that read
               scalar-prefetch operands with nontrivial arithmetic are
               reported unevaluable rather than guessed at.
  aliasing     `input_output_aliases` pairs must window the SAME
               geometry (equal array shape/dtype, block shape, index
               map), and a donated input buffer must not also be read
               through another operand of the same call — the classic
               use-after-donation hazard.

Diagnostics carry the kernel's own file:line (from the kernel jaxpr's
`debug_info.func_src_info`), so a violation points at the kernel source,
not at the solver that dispatched it.
"""

from __future__ import annotations

import re
from dataclasses import dataclass

from .astlint import Violation
from .jaxprcheck import iter_eqns

RULE_TILE = "pallas-tile"
RULE_VMEM = "pallas-vmem"
RULE_OOB = "pallas-index-oob"
RULE_ALIAS = "pallas-alias"

# enumerate the full grid up to this many points; beyond it, check the
# corner/edge sample (first/middle/last per dim) — index maps are affine
# in practice, so extremes catch sign/offset errors
GRID_ENUM_LIMIT = 4096

_SRC_RE = re.compile(r"at (.+?):(\d+)")


def min_tile(dtype) -> tuple[int, int]:
    """TPU native tile granularity (sublane, lane) by dtype width: f32
    (8, 128); second-to-last dim doubles as the dtype narrows."""
    import numpy as np

    itemsize = np.dtype(dtype).itemsize
    return {2: 16, 1: 32}.get(itemsize, 8), 128


def _size(s):
    """One `block_shape` entry's element extent, or None for a squeezed
    dim (`None` in the BlockSpec, a `Squeezed` entry in the jaxpr param;
    sized dims arrive wrapped as `Blocked(block_size=n)`)."""
    import numpy as np

    size = getattr(s, "block_size", s)
    return int(size) if isinstance(size, (int, np.integer)) else None


def block_extents(bm) -> tuple[int, ...]:
    """`block_shape` as plain element extents: squeezed dims are extent
    1 — one element per grid step along that dim."""
    return tuple(_size(s) or 1 for s in bm.block_shape)


def _mspace(aval) -> str:
    """Normalized memory-space tag of a MemRef aval: 'vmem' (the default
    when unannotated), 'smem', 'any', 'semaphore_mem'."""
    ms = getattr(aval, "memory_space", None)
    if ms is None:
        return "vmem"
    return getattr(ms, "value", str(ms))


@dataclass
class Launch:
    """One pallas_call eqn, decoded for checking."""

    name: str
    path: str
    line: int
    grid: tuple
    in_mappings: list
    out_mappings: list
    scratch_avals: list
    aliases: tuple
    vmem_limit: int | None
    num_index_operands: int
    eqn: object

    @property
    def mappings(self):
        return self.in_mappings + self.out_mappings


def decode(eqn) -> Launch:
    gm = eqn.params["grid_mapping"]
    kernel_jaxpr = eqn.params["jaxpr"]
    src = getattr(kernel_jaxpr.debug_info, "func_src_info", "") or ""
    m = _SRC_RE.search(src)
    path, line = (m.group(1), int(m.group(2))) if m else ("<unknown>", 1)
    nscratch = gm.num_scratch_operands
    scratch = [v.aval for v in kernel_jaxpr.invars[len(kernel_jaxpr.invars)
                                                   - nscratch:]] \
        if nscratch else []
    mosaic = (eqn.params.get("compiler_params") or {}).get("mosaic_tpu")
    return Launch(
        name=eqn.params.get("name") or src.split(" at ")[0],
        path=path,
        line=line,
        grid=tuple(gm.grid),
        in_mappings=list(gm.block_mappings[:gm.num_inputs]),
        out_mappings=list(
            gm.block_mappings[gm.num_inputs:gm.num_inputs + gm.num_outputs]),
        scratch_avals=scratch,
        aliases=tuple(eqn.params.get("input_output_aliases") or ()),
        vmem_limit=getattr(mosaic, "vmem_limit_bytes", None),
        num_index_operands=gm.num_index_operands,
        eqn=eqn,
    )


def launches(jaxpr) -> list[Launch]:
    """Every pallas_call anywhere in the program (while/cond/pjit bodies
    included)."""
    return [decode(e) for e in iter_eqns(jaxpr)
            if e.primitive.name == "pallas_call"]


# ---------------------------------------------------------------------------
# index-map evaluation
# ---------------------------------------------------------------------------

def eval_index_map(closed, grid_idx: tuple) -> tuple | None:
    """Concrete block indices for one grid point, or None when the map
    depends on a scalar-prefetch operand through real arithmetic (then
    the coverage check abstains instead of guessing)."""
    from jax.extend.core import Literal, jaxpr_as_fun

    jaxpr = closed.jaxpr
    n = len(grid_idx)
    if not jaxpr.eqns:
        env = dict(zip(jaxpr.invars[:n], grid_idx))
        out = []
        for v in jaxpr.outvars:
            if isinstance(v, Literal):
                out.append(int(v.val))
            elif v in env:
                out.append(int(env[v]))
            else:
                return None
        return tuple(out)
    if len(jaxpr.invars) == n and all(
            getattr(v.aval, "shape", None) == () for v in jaxpr.invars):
        import numpy as np

        args = [np.asarray(i, dtype=v.aval.dtype)
                for v, i in zip(jaxpr.invars, grid_idx)]
        vals = jaxpr_as_fun(closed)(*args)
        return tuple(int(v) for v in vals)
    return None


def grid_points(grid: tuple):
    """Every grid point when the grid is small; the first/middle/last
    corner sample otherwise."""
    import itertools

    total = 1
    for g in grid:
        total *= g
    if total <= GRID_ENUM_LIMIT:
        yield from itertools.product(*(range(g) for g in grid))
        return
    axes = [sorted({0, g // 2, g - 1}) for g in grid]
    yield from itertools.product(*axes)


# ---------------------------------------------------------------------------
# the checks
# ---------------------------------------------------------------------------

def vmem_estimate(launch: Launch) -> int:
    """Static per-launch VMEM bytes: VMEM-bound block windows (×2 when
    the grid pipelines — Mosaic double-buffers the automatic windows)
    plus VMEM scratch."""
    import numpy as np

    pipelined = 1
    for g in launch.grid:
        pipelined *= g
    buf = 2 if pipelined > 1 else 1
    total = 0
    for bm in launch.mappings:
        aval = bm.transformed_block_aval
        if _mspace(aval) != "vmem":
            continue
        n = 1
        for s in block_extents(bm):
            n *= s
        total += buf * n * np.dtype(aval.dtype).itemsize
    for aval in launch.scratch_avals:
        if _mspace(aval) != "vmem":
            continue
        n = 1
        for s in aval.shape:
            n *= int(s)
        total += n * np.dtype(aval.dtype).itemsize
    return total


def check_launch(launch: Launch, budget: int | None = None,
                 context: str = "") -> list[Violation]:
    """All four rules over one decoded pallas_call."""
    vs: list[Violation] = []
    where = f"{context}{launch.name}"

    def emit(rule, msg):
        vs.append(Violation(launch.path, launch.line, rule,
                            f"{where}: {msg}"))

    # --- tiling ---------------------------------------------------------
    for bm in launch.mappings:
        aval = bm.transformed_block_aval
        if _mspace(aval) not in ("vmem",):
            continue
        array = bm.array_aval.shape
        block = block_extents(bm)
        if len(block) < 2 or len(block) != len(array):
            continue
        # squeezed dims (extent 1 by iteration, not by windowing) are
        # the programmer's explicit layout choice — not a tiling bug
        squeezed = {d for d, s in enumerate(bm.block_shape)
                    if _size(s) is None}
        sub, lane = min_tile(aval.dtype)
        for dim, need in ((len(block) - 1, lane), (len(block) - 2, sub)):
            if dim in squeezed:
                continue
            if block[dim] < array[dim] and block[dim] % need:
                emit(RULE_TILE,
                     f"operand {bm.origin}: block {block} partitions a "
                     f"{array} {aval.dtype} array but dim {dim} extent "
                     f"{block[dim]} is not a multiple of the tile "
                     f"granularity {need} — Mosaic re-tiles every grid "
                     "step (or refuses the layout)")
    # --- vmem budget ----------------------------------------------------
    est = vmem_estimate(launch)
    limit = budget if budget is not None else launch.vmem_limit
    if limit is None:
        from ..ops.sor_pallas import VMEM_LIMIT_BYTES

        limit = VMEM_LIMIT_BYTES
    if est > limit:
        emit(RULE_VMEM,
             f"static VMEM footprint {est} bytes ({est >> 20} MiB) "
             f"exceeds the budget {limit} bytes — blocks "
             f"{[block_extents(bm) for bm in launch.mappings if _mspace(bm.transformed_block_aval) == 'vmem']}, "
             f"scratch {[tuple(a.shape) for a in launch.scratch_avals if _mspace(a) == 'vmem']}"
             )
    # --- grid × index-map coverage --------------------------------------
    for bm in launch.mappings:
        array = bm.array_aval.shape
        block = block_extents(bm)
        if len(block) != len(array):
            continue
        for point in grid_points(launch.grid):
            idx = eval_index_map(bm.index_map_jaxpr, point)
            if idx is None:
                break  # unevaluable map: abstain for this operand
            if len(idx) != len(block):
                break
            for d, (i, b, a) in enumerate(zip(idx, block, array)):
                start = i * b
                if start < 0 or start >= a:
                    emit(RULE_OOB,
                         f"operand {bm.origin}: grid point {point} maps "
                         f"to block index {idx} — dim {d} starts at "
                         f"element {start}, outside the array extent "
                         f"{a} (stale/garbage window every launch)")
                    break
            else:
                continue
            break
    # --- aliasing -------------------------------------------------------
    seen_in, seen_out = set(), set()
    for i, o in launch.aliases:
        if i in seen_in or o in seen_out:
            emit(RULE_ALIAS,
                 f"alias ({i} -> {o}) re-donates an operand already "
                 "aliased — double donation")
        seen_in.add(i)
        seen_out.add(o)
        if i >= len(launch.in_mappings) or o >= len(launch.out_mappings):
            emit(RULE_ALIAS, f"alias ({i} -> {o}) out of operand range")
            continue
        bi, bo = launch.in_mappings[i], launch.out_mappings[o]
        same = (
            bi.array_aval.shape == bo.array_aval.shape
            and bi.array_aval.dtype == bo.array_aval.dtype
            and tuple(bi.block_shape) == tuple(bo.block_shape)
            and str(bi.index_map_jaxpr) == str(bo.index_map_jaxpr)
        )
        if not same:
            how = ("index maps differ"
                   if tuple(bi.block_shape) == tuple(bo.block_shape)
                   and bi.array_aval == bo.array_aval
                   else f"input block {tuple(bi.block_shape)} of "
                        f"{bi.array_aval.shape} vs output block "
                        f"{tuple(bo.block_shape)} of "
                        f"{bo.array_aval.shape}")
            emit(RULE_ALIAS,
                 f"alias ({i} -> {o}) windows differ ({how}) — the "
                 "donated buffer is rewritten through a different window "
                 "than it is read")
        # a donated input read through a SECOND operand of the same call
        invars = list(launch.eqn.invars)
        opvars = invars[launch.num_index_operands:]
        if i < len(opvars):
            donated = opvars[i]
            dups = [k for k, v in enumerate(opvars)
                    if v is donated and k != i]
            if dups:
                emit(RULE_ALIAS,
                     f"donated input #{i} is also read through operand(s) "
                     f"{dups} of the same call — use-after-donation")
    return vs


# ---------------------------------------------------------------------------
# the pass
# ---------------------------------------------------------------------------

def extra_entries() -> list:
    """Standalone large-grid kernel builds: the production solve kernels
    at extents big enough that the grid actually partitions (the matrix
    configs trace at 16²/8³ where every launch collapses to one
    full-array block). Trace-only — nothing executes."""
    import jax
    import jax.numpy as jnp

    from ..ops import sor_pallas as sp

    out = []
    n = 512
    rb, br = sp.make_rb_iter_pallas(n, n, 1.0 / n, 1.0 / n, 1.7,
                                    jnp.float32, interpret=True)
    if rb is not None:
        p = jnp.zeros((sp.padded_rows(n, br, jnp.float32),
                       sp.padded_width(n)), jnp.float32)
        out.append(("sor_pallas.rb_iter[512²]", jax.make_jaxpr(rb)(p, p)))
    rb_t, br_t, h = sp.make_rb_iter_tblock(n, n, 1.0 / n, 1.0 / n, 1.7,
                                           jnp.float32, n_inner=4,
                                           interpret=True)
    if rb_t is not None:
        nblocks = -(-(n + 2) // br_t)
        pt = jnp.zeros((nblocks * br_t + 2 * h, sp.padded_width(n)),
                       jnp.float32)
        out.append(("sor_pallas.rb_iter_tblock[512²]",
                    jax.make_jaxpr(rb_t)(pt, pt)))
    rb_q, brq, hq = sp.make_rb_iter_tblock_quarters(
        n, n, 1.0 / n, 1.0 / n, 1.7, jnp.float32, n_inner=2,
        interpret=True)
    if rb_q is not None:
        pq = sp.pad_quarters(jnp.zeros((n + 2, n + 2), jnp.float32),
                             brq, hq)
        out.append(("sor_pallas.rb_iter_tblock_quarters[512²]",
                    jax.make_jaxpr(rb_q)(pq, pq)))
    from ..ops import sor3d_pallas as sp3

    m = 64
    rb_3, bk = sp3.make_rb_iter_tblock_3d(
        m, m, m, 1.0 / m, 1.0 / m, 1.0 / m, 1.7, jnp.float32,
        n_inner=1, interpret=True)
    if rb_3 is not None:
        p3 = sp3.pad_array_3d(jnp.zeros((m + 2, m + 2, m + 2),
                                        jnp.float32), bk, 1)
        out.append(("sor3d_pallas.rb_iter_tblock_3d[64³]",
                    jax.make_jaxpr(rb_3)(p3, p3)))
    return out


RULE_GRID = "pallas-grid-region"


def restricted_grid_entries():
    """The grid-restricted overlap PRE halves at a geometry where the
    bands actually differ from the full sweep (explicit block_rows — the
    matrix's 16² shards collapse to one block): builds the interior and
    boundary halves for a (P,1)-mesh shard plus the full-sweep control,
    and returns [(name, jaxpr, expected_grid_blocks, full_blocks), ...].
    Trace-only. The standard resource rules run over these launches too
    (`run`), and `restricted_grid_violations` pins that each half's grid
    covers only its region — fewer grid steps than the full sweep, and
    interior + boundary strictly below the 2x full-sweep count the
    restriction replaced."""
    import jax
    import jax.numpy as jnp

    from ..ops import ns2d_fused as nf
    from ..parallel import overlap as ovl
    from ..utils.params import Parameter

    jl = il = 40
    ext_pad = nf.FUSE_DEEP_HALO - 1
    param = Parameter(name="dcavity", imax=80, jmax=80)
    dt = jnp.float32
    kw = dict(jl=jl, il=il, ext_pad=ext_pad, block_rows=8, interpret=True)
    br, _h, wp, nb = nf.fused_deep_layout_2d(jl, il, dt, ext_pad,
                                             block_rows=8)
    plan = ovl.region_plan((jl, il), nf.OVERLAP_RIM, ext_pad, br, nb, wp,
                           (True, False), align=8)
    out = []
    for name, bands in (("interior", plan["int_bands"]),
                        ("boundary", plan["bnd_bands"]), ("full", None)):
        pre, pad, _unpad, _hh = nf.make_fused_pre_2d(
            param, 80, 80, 1.0 / 80, 1.0 / 80, dt, **kw, grid_bands=bands)
        z = pad(jnp.zeros((jl + 2 + 2 * ext_pad,) * 2, dt))
        offs = jnp.zeros((2,), jnp.int32)
        dt11 = jnp.full((1, 1), 0.01, dt)
        jx = jax.make_jaxpr(pre)(offs, dt11, z, z)
        expect = (sum(n for _, n in bands) if bands is not None else nb)
        out.append((f"ns2d_fused.PRE[restricted {name} half]", jx,
                    expect, nb))
    return out


def restricted_grid_violations() -> list[Violation]:
    """Grid-coverage pin for the restricted halves (see
    restricted_grid_entries): each half's Pallas grid must have exactly
    its band's block count, each below the full sweep, and the two
    halves summed strictly below 2x full — the acceptance contract of
    `tpu_overlap_restrict`."""
    entries = restricted_grid_entries()
    vs: list[Violation] = []
    halves = {}
    for name, jx, expect, full in entries:
        ls = launches(jx.jaxpr)
        if len(ls) != 1:
            vs.append(Violation("<restricted-grid>", 1, RULE_GRID,
                                f"{name}: expected 1 pallas_call, "
                                f"traced {len(ls)}"))
            continue
        got = ls[0].grid[0] if ls[0].grid else 0
        if got != expect:
            vs.append(Violation(ls[0].path, ls[0].line, RULE_GRID,
                                f"{name}: grid covers {got} blocks, the "
                                f"region plan declares {expect} (of "
                                f"{full} full-sweep blocks)"))
        if "full" not in name:
            halves[name] = got
    if len(halves) == 2 and entries:
        full = entries[0][3]
        if sum(halves.values()) >= 2 * full:
            vs.append(Violation(
                "<restricted-grid>", 1, RULE_GRID,
                f"restricted halves sweep {halves} blocks — not below "
                f"the 2x{full} full-sweep count they must beat"))
    return vs


RULE_SHAPECLASS = "shapeclass-waste"


def shapeclass_violations() -> list[Violation]:
    """The shape-class padding-waste contract (fleet/shapeclass.py,
    serving v2): for every class-eligible extent the rung ladder must be
    covering (class >= live), idempotent (a class maps to itself — a
    padded lane re-bucketed lands in the same compile), power-of-two
    above the floor, and BOUNDED — per-axis padded extent under 2x the
    live extent, so a 2-D class never burns more than WASTE_BOUND (4x)
    the live cells. Checked over the whole eligible range plus explicit
    rung-differing geometries; stateless, like every palcheck rule."""
    from ..fleet import shapeclass as sc

    where = "pampi_tpu/fleet/shapeclass.py"
    vs: list[Violation] = []
    for n in range(sc.MIN_CLASS_EXTENT, 4097):
        c = sc.class_extent(n)
        if c < n:
            vs.append(Violation(where, 1, RULE_SHAPECLASS,
                                f"class_extent({n}) = {c} < live"))
        if sc.class_extent(c) != c:
            vs.append(Violation(where, 1, RULE_SHAPECLASS,
                                f"rung {c} is not idempotent"))
        if c > sc.RUNG_FLOOR and (c & (c - 1)) != 0:
            vs.append(Violation(where, 1, RULE_SHAPECLASS,
                                f"rung {c} not a power of two"))
        if c + 2 >= 2 * (n + 2):
            vs.append(Violation(
                where, 1, RULE_SHAPECLASS,
                f"extent {n}: padded {c + 2} >= 2x live {n + 2} — "
                "per-axis waste bound broken"))
    # rung-differing 2-D geometries: the cells bound (the palcheck
    # contract ISSUE 14 names) must hold where the two axes land on
    # different rungs
    for grid in ((17, 33), (9, 129), (20, 48), (16, 16), (255, 9),
                 (100, 100), (8, 4096)):
        w = sc.padding_waste(grid)
        if w >= sc.WASTE_BOUND:
            vs.append(Violation(
                where, 1, RULE_SHAPECLASS,
                f"grid {grid}: padding waste {w:.2f}x >= the "
                f"{sc.WASTE_BOUND}x bound"))
    # 3-D rungs (serving v3): the same per-axis bound cubed
    for grid in ((17, 33, 9), (9, 9, 9), (20, 48, 12), (16, 16, 16),
                 (100, 100, 100), (8, 8, 255)):
        w = sc.padding_waste(grid)
        if w >= sc.WASTE_BOUND_3D:
            vs.append(Violation(
                where, 1, RULE_SHAPECLASS,
                f"grid {grid}: padding waste {w:.2f}x >= the 3-D "
                f"{sc.WASTE_BOUND_3D}x bound"))
    return vs


def class_kernel_entries() -> list:
    """The dynamic-extent CLASS kernels at padded geometries sized for
    the 2x-per-axis waste bound's worst case (live extent one past half
    the rung, so the padded block is as oversized as eligibility ever
    allows): the fused 2-D PRE/POST + the padded-class tblock solve at a
    256² class, and the 3-D PRE/POST at a 32³ class. Trace-only — the
    standard resource rules (tiling/VMEM/index/alias) then price the
    class blocks the serving plane actually launches."""
    import jax
    import jax.numpy as jnp

    from ..fleet.shapeclass import make_padded_class_solve
    from ..ops import ns2d_fused as nf
    from ..ops import ns3d_fused as nf3
    from ..utils.params import Parameter

    out = []
    n = 256  # rung for live extents 129..256 (worst pad: live 129)
    param = Parameter(name="dcavity", imax=n, jmax=n)
    dt = jnp.float32
    solve, br, h = make_padded_class_solve(param, n, n, dt,
                                           interpret=True)
    pre, pad, _unpad, _h = nf.make_fused_pre_2d(
        param, n, n, 1.0, 1.0, dt, block_rows=br, interpret=True,
        dynamic=True)
    post, _p, _u, _h2 = nf.make_fused_post_2d(
        param, n, n, 1.0, 1.0, dt, block_rows=br, ragged=True,
        interpret=True, dynamic=True)
    z = pad(jnp.zeros((n + 2, n + 2), dt))
    offs = jnp.zeros((2,), jnp.int32)
    ext = jnp.asarray([[129, 129]], jnp.int32)
    geo = jnp.asarray([[1.0 / 129, 1.0 / 129]], dt)
    dt11 = jnp.full((1, 1), 0.01, dt)
    out.append((f"ns2d_class.PRE[{n}²]",
                jax.make_jaxpr(pre)(offs, ext, geo, dt11, z, z)))
    out.append((f"ns2d_class.POST[{n}²]",
                jax.make_jaxpr(post)(offs, ext, geo, dt11,
                                     z, z, z, z, z)))
    sgeo = jnp.asarray([[0.9, 1.0, 1.0]], dt)
    norm = jnp.asarray(129.0 * 129.0, dt)
    out.append((f"ns2d_class.solve[{n}²]",
                jax.make_jaxpr(solve)(z, z, ext, sgeo, norm)))
    m = 32  # 3-D rung for live extents 17..32
    param3 = Parameter(name="dcavity3d", imax=m, jmax=m, kmax=m,
                       seen_keys=("kmax",))
    pre3, pad3, _u3, _h3 = nf3.make_fused_pre_3d(
        param3, m, m, m, 1.0, 1.0, 1.0, dt, interpret=True, dynamic=True)
    post3, _p3, _uu3, _hh3 = nf3.make_fused_post_3d(
        param3, m, m, m, 1.0, 1.0, 1.0, dt, ragged=True, interpret=True,
        dynamic=True)
    z3 = pad3(jnp.zeros((m + 2, m + 2, m + 2), dt))
    offs3 = jnp.zeros((3,), jnp.int32)
    ext3 = jnp.asarray([[17, 17, 17]], jnp.int32)
    geo3 = jnp.asarray([[1.0 / 17, 1.0 / 17, 1.0 / 17]], dt)
    out.append((f"ns3d_class.PRE[{m}³]",
                jax.make_jaxpr(pre3)(offs3, ext3, geo3, dt11,
                                     z3, z3, z3)))
    out.append((f"ns3d_class.POST[{m}³]",
                jax.make_jaxpr(post3)(offs3, ext3, geo3, dt11,
                                      z3, z3, z3, z3, z3, z3, z3)))
    return out


def mg_cycle_entries() -> list:
    """The fused V-cycle kernels (ops/mg_fused.py, ISSUE 16) at the
    worst-case geometries the solo dispatchers can actually build: the
    2-D DOWN/UP pair at the 512x256 two-level plan (the smallest plain
    grid whose plan survives the default DCT-bottom budget — and so the
    largest plane per level the dispatcher emits), the 3-D pair at the
    64³ plan, the masked obstacle pair (fluid + factor stacks double the
    resident inputs — the VMEM worst case per plane), and the one-launch
    class cycle at a 256² class with a worst-pad live extent (129: the
    deepest unroll at the biggest plane). Trace-only — the standard
    resource rules (tiling/VMEM/index/alias) then price every launch."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..ops import mg_fused as mf

    out = []
    dt = jnp.float32
    for tag, levels, spacings in (
            ("mg2d_cycle[512x256]", [(256, 512), (128, 256)],
             (1.0 / 512, 1.0 / 256)),
            ("mg3d_cycle[64³]", [(64, 64, 64), (32, 32, 32)],
             (1.0 / 64, 1.0 / 64, 1.0 / 64))):
        down, up, plane = mf.make_cycle_kernels(levels, spacings, dt,
                                                interpret=True)
        stack = (len(levels),) + plane
        p = jnp.zeros(plane, dt)
        s = jnp.zeros(stack, dt)
        out.append((f"{tag}.DOWN", jax.make_jaxpr(down)(p, p)))
        out.append((f"{tag}.UP", jax.make_jaxpr(up)(s, s, p)))
    # the masked obstacle pair: per-level fluid/factor stacks ride as two
    # extra VMEM-resident inputs (the fused cycle's heaviest layout)
    levels = [(64, 64), (32, 32)]
    fluids = [np.ones((j + 2, i + 2)) for j, i in levels]
    factors = [np.full((j, i), 0.25) for j, i in levels]
    down, up, plane = mf.make_cycle_kernels(
        levels, (1.0 / 64, 1.0 / 64), dt, interpret=True,
        fluid_levels=fluids, factor_levels=factors)
    stack = (len(levels),) + plane
    p = jnp.zeros(plane, dt)
    s = jnp.zeros(stack, dt)
    out.append(("mg2d_obstacle_cycle[64²].DOWN",
                jax.make_jaxpr(down)(p, p)))
    out.append(("mg2d_obstacle_cycle[64²].UP",
                jax.make_jaxpr(up)(s, s, p)))
    # the one-launch class cycle at the worst-pad lane of a 256² class
    n = 256
    cycle, plane, lmax = mf.make_class_cycle_2d(n, n, dt, interpret=True)
    live = jnp.asarray(129, jnp.int32)  # worst pad on the 256 rung
    inv2 = jnp.asarray(129.0 * 129.0, dt)
    ext, geo = mf.class_level_plan(live, live, inv2, inv2, lmax, dt)
    pc = jnp.zeros(plane, dt)
    out.append((f"mg_class_cycle[{n}²]",
                jax.make_jaxpr(cycle)(pc, pc, ext, geo)))
    return out


def check_jaxpr(jaxpr, budget: int | None = None,
                context: str = "") -> list[Violation]:
    vs: list[Violation] = []
    for launch in launches(jaxpr):
        vs += check_launch(launch, budget=budget, context=context)
    return vs


def run(traced=None, configs=None, budget: int | None = None,
        extras: bool = True) -> list[Violation]:
    """Check every pallas_call of the trace matrix plus the standalone
    large-grid builds. Stateless (no baseline): every rule is decidable
    from the program alone."""
    from . import jaxprcheck

    if traced is None:
        traced = jaxprcheck.trace_matrix(configs)
    vs: list[Violation] = []
    for t in traced:
        vs += check_jaxpr(t.jaxpr.jaxpr, budget=budget,
                          context=f"{t.cfg.name}/")
    if extras:
        for name, jx in extra_entries():
            vs += check_jaxpr(jx.jaxpr, budget=budget, context=f"{name}/")
        # the grid-restricted overlap halves: resource rules + the
        # region-coverage pin (tpu_overlap_restrict)
        for name, jx, _expect, _full in restricted_grid_entries():
            vs += check_jaxpr(jx.jaxpr, budget=budget, context=f"{name}/")
        vs += restricted_grid_violations()
        # the serving-v2 shape-class rung ladder: covering, idempotent,
        # waste-bounded (fleet/shapeclass.py)
        vs += shapeclass_violations()
        # the serving-v3 class KERNELS (fused PRE/POST + padded-class
        # solve) at the waste bound's worst-case padded geometry
        for name, jx in class_kernel_entries():
            vs += check_jaxpr(jx.jaxpr, budget=budget, context=f"{name}/")
        # the fused V-cycle kernels (ISSUE 16): DOWN/UP pairs at the
        # worst-case solo level plans + the one-launch class cycle
        for name, jx in mg_cycle_entries():
            vs += check_jaxpr(jx.jaxpr, budget=budget, context=f"{name}/")
    return vs
