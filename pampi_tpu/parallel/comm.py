"""The distributed communication layer, TPU-native.

Capability parity with the reference's only real abstraction boundary — the
ten-function Comm API of /root/reference/assignment-6/src/comm.h:104-138
(commInit/commPartition/commFinalize/commPrintConfig/commExchange/commShift/
commReduction/commIsBoundary/commCollectResult/commIsMaster + commGetOffsets)
— re-designed for a TPU device mesh instead of translated from MPI:

  MPI concept (reference)                   TPU-native equivalent (here)
  ----------------------------------------  ---------------------------------
  MPI_Init / MPI_Comm_size  (commInit)      jax.devices() / jax.distributed
  MPI_Dims_create+Cart_create(commPartition) dims_create() + jax.sharding.Mesh
  MPI_Cart_shift neighbours                 lax.ppermute permutation lists
  MPI_Neighbor_alltoallw halo (commExchange) halo_exchange(): per-axis ppermute
                                            of edge strips inside shard_map
  one-directional staggered shift(commShift) halo_shift(): single-direction
                                            ppermute (F/G/H donor edges)
  MPI_Allreduce MAX|SUM     (commReduction) lax.pmax / lax.psum over mesh axes
  cart coords boundary test (commIsBoundary) lax.axis_index() == 0 / dim-1
  subarray gather to rank 0 (commCollectResult) the sharded global array IS the
                                            result — jax.device_get triggers
                                            XLA's gather; no assembly code
  prefix-sum of local sizes (commGetOffsets) axis_index * block (uniform blocks)
  MPI_PROC_NULL edges                       jnp.where(has_neighbour, recv, old)

Design notes (TPU-first, not a translation):
- Decomposition is UNIFORM: XLA sharding wants equal blocks, so instead of the
  reference's remainder-spread `sizeOfRank` (comm.c:19-22) we require
  divisibility (pad-with-mask is the policy for ragged cases). This is a
  documented deviation, not an omission.
- Halo exchange is axis-by-axis with FULL edge strips (ghost corners included),
  which makes corners consistent after the second axis — equivalent to the
  reference's ordered per-direction sends.
- Exchanges live INSIDE jit/shard_map: XLA schedules the ppermutes
  asynchronously and overlaps them with compute — the hand-rolled goal of
  assignment-3b's Isend/Irecv overlap, for free.
- Fields inside the kernel are "extended" local blocks (+1 ghost layer per
  side). Physical-boundary ghosts are never written by the exchange (the
  MPI_PROC_NULL convention), so BC code owns them exactly as in the reference.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# dimension order matches the reference's enum {KDIM, JDIM, IDIM} (comm.h:101):
# slowest-varying first; arrays are [k, j, i] / [j, i].
AXIS_NAMES = ("k", "j", "i")

# mesh interconnect tiers, in POSTING order: DCN (inter-slice, the slow
# fabric of a multi-slice pod) strips are posted first/deepest so they
# have the whole interior compute to hide behind; ICI (intra-slice)
# strips last/shallowest. "Persistent and Partitioned MPI for Stencil
# Communication" (PAPERS.md) is the per-strip partitioned-send pattern
# this ordering realizes on the ExchangeSchedule seam.
TIERS = ("dcn", "ici")


def parse_mesh_tiers(spec: str, axis_names) -> dict:
    """`tpu_mesh_tiers` -> {axis name: tier}. "auto" (the default) maps
    every axis to the single "ici" tier — today's single-slice meshes,
    bitwise-unchanged exchange order. A comma list "k=dcn,j=ici,i=ici"
    declares the hierarchy explicitly; unlisted axes default to "ici",
    unknown axes/tiers refuse loudly (a typo'd tier map must not
    silently serve the flat schedule)."""
    tiers = {name: "ici" for name in axis_names}
    spec = (spec or "auto").strip()
    if spec == "auto":
        return tiers
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(
                f"tpu_mesh_tiers entry {part!r} is not axis=tier "
                f"(axes {tuple(axis_names)}, tiers {TIERS})")
        axis, tier = (t.strip() for t in part.split("=", 1))
        if axis not in tiers:
            raise ValueError(
                f"tpu_mesh_tiers names unknown mesh axis {axis!r} "
                f"(this mesh has {tuple(axis_names)})")
        if tier not in TIERS:
            raise ValueError(
                f"tpu_mesh_tiers tier {tier!r} for axis {axis!r} not in "
                f"{TIERS}")
        tiers[axis] = tier
    return tiers


def master_print(comm: "CartComm", fmt: str, *args) -> None:
    """`jax.debug.print` from the (0,...,0) mesh shard only — the rank-0
    printing convention of the reference drivers, usable INSIDE shard_map
    (plain is_master can't be: it's a host-side property). Values printed
    after a `reduction` are identical on every shard, so one line loses
    nothing."""
    idx = jnp.int32(0)
    for ax in comm.axis_names:
        idx = idx + lax.axis_index(ax)
    lax.cond(
        idx == 0,
        lambda: jax.debug.print(fmt, *args),
        lambda: None,
    )


def dims_create(nranks: int, ndims: int,
                extents: tuple[int, ...] | None = None) -> tuple[int, ...]:
    """Balanced factorization of nranks over ndims — MPI_Dims_create
    semantics (used by commPartition, and by
    assignment-5/ex5-nazifkar/src/solver.c:445).

    Without `extents`: non-increasing balanced factors (the MPI default).
    With `extents` (the grid's interior extents in mesh-axis order): GRID-
    AWARE — among all ordered factorizations, prefer (1) every axis evenly
    divisible, then (2) least pad-with-mask overhead, then (3) smallest
    local-block perimeter (halo volume), then (4) most balanced. MPI gets
    this for free because its ranks tolerate remainders (sizeOfRank,
    assignment-6/src/comm.c:19-22); uniform XLA shardings do not, so the
    factorization must look at the grid: e.g. the reference's canal.par
    (200x50) on 8 devices needs (2,4), not the blind (4,2)."""
    if extents is not None and len(extents) != ndims:
        raise ValueError(
            f"extents {extents} rank does not match ndims={ndims}"
        )

    def factorizations(n, k):
        if k == 1:
            yield (n,)
            return
        for f in range(1, n + 1):
            if n % f == 0:
                for rest in factorizations(n // f, k - 1):
                    yield (f,) + rest

    if extents is None:
        primes = []
        n = nranks
        f = 2
        while f * f <= n:
            while n % f == 0:
                primes.append(f)
                n //= f
            f += 1
        if n > 1:
            primes.append(n)
        dims = [1] * ndims
        for prime in sorted(primes, reverse=True):
            # multiply the currently-smallest dimension (latest index on
            # ties so dims stays non-increasing)
            k = min(range(ndims), key=lambda d: (dims[d], -d))
            dims[k] *= prime
        return tuple(sorted(dims, reverse=True))

    import math as _math

    def score(dims):
        locals_ = [-(-e // p) for e, p in zip(extents, dims)]
        nondiv = sum(1 for e, p in zip(extents, dims) if e % p)
        pad = sum((l * p - e) / e for e, p, l in zip(extents, dims, locals_))
        # halo traffic: cut-plane area summed over the partitioned axes
        padded = [l * p for l, p in zip(locals_, dims)]
        vol = _math.prod(padded)
        comm_vol = sum(
            (p - 1) * vol // ep for p, ep in zip(dims, padded) if p > 1
        )
        spread = max(dims) - min(dims)
        # final tie-break keeps the MPI-style non-increasing order
        return (nondiv, round(pad, 9), comm_vol, spread,
                tuple(-d for d in dims))

    return min(factorizations(nranks, ndims), key=score)


def compat_shard_map(fn, mesh, in_specs, out_specs, check_vma: bool = True):
    """`jax.shard_map` with this repo's keyword spelling — the ONE call
    site of it (CartComm.shard_map, models/dmvm.py and
    tests/test_sor_pallas.py all route through here; astlint's
    raw-shard-map rule keeps it that way)."""
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )


@dataclass
class CartComm:
    """Cartesian device-mesh communicator (≙ the Comm struct, comm.h:104-115).

    ndims-dimensional mesh over the given devices; axis names are the last
    `ndims` of ("k", "j", "i") so a 2-D field [j, i] shards over ("j", "i").
    """

    ndims: int = 2
    dims: tuple[int, ...] | None = None
    devices: list | None = None
    extents: tuple[int, ...] | None = None  # grid interior extents, mesh
    #   order — makes auto dims GRID-AWARE (prefers feasible factorizations)
    tiers: str | dict | None = None  # axis->interconnect-tier map
    #   (tpu_mesh_tiers spec string or a ready dict); None/"auto" = one
    #   tier — exchange order and every cached schedule bitwise-unchanged
    mesh: Mesh = field(init=False)
    axis_names: tuple[str, ...] = field(init=False)

    def __post_init__(self):
        devs = self.devices if self.devices is not None else jax.devices()
        n = len(devs)
        if self.dims is None:
            self.dims = dims_create(n, self.ndims, self.extents)
        if len(self.dims) != self.ndims:
            raise ValueError(
                f"tpu_mesh has {len(self.dims)} dims {self.dims} but this "
                f"problem needs a {self.ndims}-D mesh"
            )
        if any(d < 1 for d in self.dims):
            raise ValueError(f"mesh dims must be positive, got {self.dims}")
        if math.prod(self.dims) > n:
            raise ValueError(
                f"mesh dims {self.dims} need {math.prod(self.dims)} devices "
                f"but only {n} are available"
            )
        # like `mpirun -n k` on a larger node: an explicit smaller mesh uses
        # the first prod(dims) devices
        devs = list(devs)[: math.prod(self.dims)]
        self.axis_names = AXIS_NAMES[3 - self.ndims :]
        self.mesh = Mesh(np.asarray(devs).reshape(self.dims), self.axis_names)
        if not isinstance(self.tiers, dict):
            self.tiers = parse_mesh_tiers(self.tiers, self.axis_names)
        else:
            # a ready dict still goes through validation (the cli passes
            # the spec string; tests may hand a dict)
            self.tiers = parse_mesh_tiers(
                ",".join(f"{a}={t}" for a, t in self.tiers.items()),
                self.axis_names)

    def tier_of(self, axis: str) -> str:
        return self.tiers[axis]

    @property
    def multi_tier(self) -> bool:
        return len(set(self.tiers.values())) > 1

    # --- commIsMaster (comm.h:138) -------------------------------------
    @property
    def is_master(self) -> bool:
        return jax.process_index() == 0

    @property
    def size(self) -> int:
        return math.prod(self.dims)

    def axis_size(self, axis: str) -> int:
        return self.dims[self.axis_names.index(axis)]

    # --- commPartition helpers -----------------------------------------
    def spec(self) -> P:
        """PartitionSpec sharding array dim d over mesh axis d."""
        return P(*self.axis_names)

    def sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec())

    def shard(self, arr):
        """Place a global (interior-only) array sharded over the mesh."""
        return jax.device_put(arr, self.sharding())

    def replicate(self, arr):
        """Place an array replicated over the mesh — where the chunk's
        P() outputs (loop time, step count, metrics) come back, so the
        first call sees the shardings every later call does (placed on
        one device, the overlapped 2x2 dcavity 4096² chunk compiled twice
        on the chip, 35 s each). Built per addressable device, so it holds
        under a multi-process mesh too."""
        host = np.asarray(arr)
        return jax.make_array_from_callback(
            host.shape, NamedSharding(self.mesh, P()), lambda _idx: host)

    def local_shape(self, global_shape, ragged: bool = False) -> tuple[int, ...]:
        """Uniform per-shard block extents. ragged=False enforces the
        divisibility policy; ragged=True returns ceil-divided blocks — the
        pad-with-mask decomposition (trailing shards carry dead cells that
        the global-coordinate masks exclude from updates, residuals, walls
        and collection; ≙ the reference's remainder-spread sizeOfRank,
        assignment-6/src/comm.c:19-22, realized the uniform-sharding way)."""
        if ragged:
            return tuple(-(-e // p) for e, p in zip(global_shape, self.dims))
        for ext, p in zip(global_shape, self.dims):
            if ext % p:
                raise ValueError(
                    f"extent {ext} not divisible by mesh dim {p} "
                    f"(uniform-block policy; ragged pad-with-mask runs pass "
                    f"ragged=True, or change tpu_mesh)"
                )
        return tuple(e // p for e, p in zip(global_shape, self.dims))

    def shard_map(self, fn, in_specs, out_specs, check_vma: bool = True):
        """Wrap `jax.shard_map` over this comm's mesh.

        check_vma=False is required ONLY when the traced body dispatches a
        pallas_call (its out_shape declares no varying-mesh-axes info — the
        standard composition form, validated bitwise on real TPU hardware).
        The relaxation is necessarily step-wide (JAX scopes the check per
        shard_map, not per region), which disables varying-mesh-axes
        validation for EVERY collective in that body — so callers must NOT
        widen its use beyond the pallas-dispatch case: every solver keeps a
        jnp twin of the same step that runs with check_vma=True on the CPU
        test meshes (test_ns2d_dist/test_ns3d_dist/test_poisson_dist), which
        is what catches out_spec/ppermute mistakes the relaxed production
        trace would hide."""
        return compat_shard_map(
            fn, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=check_vma,
        )

    # --- commPrintConfig (comm.c:429-462) ------------------------------
    def print_config(self, out=None) -> None:
        import sys

        out = out or sys.stdout
        out.write("Communication setup:\n")
        out.write(f"\tMesh dims: {self.dims} axes {self.axis_names}\n")
        for d in self.mesh.devices.flat:
            out.write(f"\tDevice {d.id}: {d.platform} {getattr(d, 'coords', '')}\n")

    # --- commCollectResult (comm.c:246-427) ----------------------------
    @staticmethod
    def collect(arr) -> np.ndarray:
        """Gather a sharded global array to the host. The reference needs 80
        lines of subarray datatypes + Isend/Irecv (assembleResult); here the
        sharded array is already globally addressable. Under a multi-process
        launch shards live on other hosts, so the fetch is a cross-process
        allgather (every process gets the full array — the reference gathers
        to rank 0 only, but its non-root ranks simply discard theirs)."""
        # branch on process_count, NOT per-array addressability: with a
        # sub-mesh one process could own every shard and skip a collective
        # the others enter — all processes must take the same path
        if jax.process_count() == 1:
            return np.asarray(jax.device_get(arr))
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(arr, tiled=True))


# ----------------------------------------------------------------------
# In-kernel collectives: call these INSIDE a shard_map-wrapped function.
# ----------------------------------------------------------------------


def axis_coord(axis_name: str):
    """Cartesian coordinate along a mesh axis (≙ Comm.coords, comm.h:113)."""
    return lax.axis_index(axis_name)


def is_boundary(axis_name: str, nper: int, side: str):
    """commIsBoundary (comm.c:169-182): True on shards owning the physical
    wall. side is "lo" (LEFT/BOTTOM/FRONT) or "hi" (RIGHT/TOP/BACK)."""
    idx = lax.axis_index(axis_name)
    return idx == 0 if side == "lo" else idx == nper - 1


def get_offsets(axis_name: str, local_extent: int):
    """commGetOffsets (comm.c:491-513): global start index of this shard's
    block — uniform blocks, so a multiply instead of a prefix sum."""
    return lax.axis_index(axis_name) * local_extent


def strip_key(shape, dtype) -> str:
    """Canonical name of one exchange message: '4x16:float64'. The ONE
    naming convention across the observability plane — the commcheck
    collective census keys ppermute messages with it
    (analysis/commcheck.py), the `jax.named_scope` device-time scopes
    below embed it, and `utils/xprof.py` aggregates trace events back by
    the same token — so a lint census entry, a profiler scope and a
    telemetry record all name the same strip."""
    return "x".join(str(int(s)) for s in shape) + f":{jnp.dtype(dtype).name}"


def _scope(kind: str, axis_name: str, shape, dtype):
    """Device-time attribution scope of one exchange axis:
    `halo_exchange.j.4x18:float64`. jax.named_scope leaves the jaxpr
    byte-identical (only eqn name stacks / lowered-HLO metadata change),
    so the flag-off trace-identity contract (CONTRACTS.json hashes) is
    untouched — test-pinned in tests/test_xprof.py."""
    return jax.named_scope(f"{kind}.{axis_name}.{strip_key(shape, dtype)}")


def _nbr_perm(nper: int, up: bool, periodic: bool):
    if periodic:
        return [(r, (r + 1) % nper) for r in range(nper)] if up else [
            ((r + 1) % nper, r) for r in range(nper)
        ]
    return [(r, r + 1) for r in range(nper - 1)] if up else [
        (r + 1, r) for r in range(nper - 1)
    ]


def _exchange_axis(x, axis_name: str, nper: int, dim: int, periodic: bool,
                   depth: int = 1, perms=None):
    """Fill both `depth`-wide ghost strips of `x` along array dim `dim` from
    the ±1 neighbours on mesh axis `axis_name`. Physical-wall ghosts keep
    their previous contents (MPI_PROC_NULL semantics). `perms` is an
    optional precomputed (up, down) permutation-list pair — the
    persistent-schedule path (ExchangeSchedule) resolves them once per
    (mesh, depth, dtype); the default recomputes the identical lists, so
    both paths trace the same program."""
    if nper == 1 and not periodic:
        return x
    n = x.shape[dim]
    d = depth
    up, down = perms if perms is not None else (
        _nbr_perm(nper, True, periodic), _nbr_perm(nper, False, periodic))
    strip = tuple(d if a == dim else x.shape[a] for a in range(x.ndim))
    with _scope("halo_exchange", axis_name, strip, x.dtype):
        # my high/low OWNED strips (d innermost owned layers on each side)
        hi_edge = lax.slice_in_dim(x, n - 2 * d, n - d, axis=dim)
        lo_edge = lax.slice_in_dim(x, d, 2 * d, axis=dim)
        # strip travelling "up" (to +1 neighbour) fills their LOW ghost,
        # and v.v.
        from_lo = lax.ppermute(hi_edge, axis_name, up)
        from_hi = lax.ppermute(lo_edge, axis_name, down)
        if not periodic:
            idx = lax.axis_index(axis_name)
            old_lo = lax.slice_in_dim(x, 0, d, axis=dim)
            old_hi = lax.slice_in_dim(x, n - d, n, axis=dim)
            from_lo = jnp.where(idx > 0, from_lo, old_lo)
            from_hi = jnp.where(idx < nper - 1, from_hi, old_hi)
        x = lax.dynamic_update_slice_in_dim(x, from_lo, 0, axis=dim)
        x = lax.dynamic_update_slice_in_dim(x, from_hi, n - d, axis=dim)
    return x


def halo_exchange(x, comm: CartComm, periodic=(), depth: int = 1):
    """commExchange (comm.c:184-195): refresh ALL ghost layers of the extended
    local block `x` (`depth` ghost layers per side, array dims ordered like
    the mesh axes). Axis-by-axis with full strips ⇒ ghost corners are
    consistent after the last axis. depth > 1 is the communication-avoiding
    deep-halo exchange: one fat ppermute message replaces `depth` thin ones —
    the right trade on latency-bound ICI hops (see parallel/stencil2d.py
    `ca_rb_iters` for the local temporal blocking that consumes it)."""
    for dim, axis_name in enumerate(comm.axis_names):
        x = _exchange_axis(
            x, axis_name, comm.axis_size(axis_name), dim,
            axis_name in periodic, depth,
        )
    return x


def capture_axis_strips(x_ext, comm: CartComm, axis: str, depth: int,
                        inner: int, periodic: bool = False):
    """The capture half of the per-tier depth schedule (ISSUE 17,
    `tpu_exchange_depth axis=H`): ONE depth-`depth` exchange on the slow
    mesh `axis` over the deep-embedded block, cropped to the two
    paste-ready `inner`-deep ghost strips of the step's own deep layout.
    A fused-chunk depth block calls this once, then `paste_axis_strips`
    re-applies the strips for `depth` scan steps — one slow-fabric
    exchange amortized over H steps (the partitioned-communication
    trade: bounded staleness <= H-1 steps on the slow rim, fresh
    exchanges everywhere else). Requires depth >= inner; `x_ext` is the
    1-ghost-layer extended block."""
    if depth < inner:
        raise ValueError(f"capture depth {depth} < inner depth {inner}")
    dim = comm.axis_names.index(axis)
    xw = jnp.pad(x_ext, [(depth - 1, depth - 1)] * x_ext.ndim)
    xw = _exchange_axis(
        xw, axis, comm.axis_size(axis), dim, periodic, depth)
    # the inner-deep block's window starts at depth-inner along every
    # axis; its two `axis` ghost strips are the innermost `inner` layers
    # of the fat captured halo
    lo_start = [depth - inner] * x_ext.ndim
    hi_start = [depth - inner] * x_ext.ndim
    hi_start[dim] = depth + (x_ext.shape[dim] - 2)
    sizes = [x_ext.shape[a] + 2 * (inner - 1) for a in range(x_ext.ndim)]
    sizes[dim] = inner
    lo = lax.dynamic_slice(xw, lo_start, sizes)
    hi = lax.dynamic_slice(xw, hi_start, sizes)
    return lo, hi


def paste_axis_strips(xd, comm: CartComm, axis: str, inner: int, lo, hi,
                      periodic=()):
    """The per-step paste half: fill `axis`'s two `inner`-deep ghost
    strips of the deep-embedded block `xd` from the block-start captured
    strips (no collective — the amortized slow-tier exchange already
    ran in `capture_axis_strips`), then run the fresh per-step exchange
    on every OTHER mesh axis. Wall shards keep their own ghost contents
    (the MPI_PROC_NULL gate `_exchange_axis` applies), so the paste is
    an identity there and wall-BC history stays current. Axis-by-axis
    order puts the pasted axis first: ghost corners take the fresh
    axes' strips, exactly like `halo_exchange`'s last-axis rule."""
    dim = comm.axis_names.index(axis)
    nper = comm.axis_size(axis)
    n = xd.shape[dim]
    if nper > 1:
        idx = lax.axis_index(axis)
        old_lo = lax.slice_in_dim(xd, 0, inner, axis=dim)
        old_hi = lax.slice_in_dim(xd, n - inner, n, axis=dim)
        lo = jnp.where(idx > 0, lo, old_lo)
        hi = jnp.where(idx < nper - 1, hi, old_hi)
        xd = lax.dynamic_update_slice_in_dim(xd, lo, 0, axis=dim)
        xd = lax.dynamic_update_slice_in_dim(xd, hi, n - inner, axis=dim)
    for d2, name in enumerate(comm.axis_names):
        if name == axis:
            continue
        xd = _exchange_axis(
            xd, name, comm.axis_size(name), d2, name in periodic, inner)
    return xd


class ExchangeSchedule:
    """Persistent halo-exchange schedule — the partitioned-MPI seam
    (ROADMAP item 2; "Persistent and Partitioned MPI for Stencil
    Communication", PAPERS.md): everything static about one exchange
    class — the per-axis neighbour permutation lists, the travelling-strip
    depth, the dtype contract — is resolved ONCE per (mesh, halo-depth,
    dtype, periodic set) and reused by every exchange of that class,
    instead of being re-derived at every `halo_exchange` trace site.
    `__call__` traces the IDENTICAL program to
    `halo_exchange(x, comm, periodic, depth)` (same slices, same
    ppermutes with the same permutation lists, same named scopes), so a
    solver can swap between the two forms without moving a byte of the
    collective contract (commcheck census, CONTRACTS.json).

    Hierarchical meshes (ROADMAP item 3): the plan is TIER-ORDERED by the
    comm's axis->tier map (`tpu_mesh_tiers`) — DCN-tier axes exchange
    first (posted deepest/earliest, the partitioned-send discipline:
    inter-slice strips have the most latency to hide and the whole
    interior compute to hide behind), ICI-tier axes last. Reordering
    full-strip axis exchanges is VALUE-safe: every strip spans the full
    extended extent of the other axes, so a ghost corner receives the
    diagonal neighbour's owned value by either route — the same copied
    bytes, just posted in a latency-aware order. With the single-tier
    default the plan keeps the historical axis order and traces
    bitwise-identically (test-pinned)."""

    def __init__(self, comm: CartComm, depth: int = 1, dtype=None,
                 periodic=()):
        self.comm = comm
        self.depth = int(depth)
        self.dtype = None if dtype is None else jnp.dtype(dtype)
        self.periodic = tuple(periodic)
        # the static plan: one entry per mesh axis, permutation lists
        # resolved now (MPI_Send_init semantics — the "build once" half
        # of persistent requests), tier-ordered (DCN first, stable
        # within a tier — the single-tier default is the identity order)
        self.plan = []
        order = sorted(
            range(comm.ndims),
            key=lambda d: (TIERS.index(comm.tier_of(comm.axis_names[d])),
                           d))
        for dim in order:
            name = comm.axis_names[dim]
            nper = comm.axis_size(name)
            per = name in self.periodic
            self.plan.append((dim, name, nper, per, (
                _nbr_perm(nper, True, per), _nbr_perm(nper, False, per))))

    def __call__(self, x):
        if self.dtype is not None and x.dtype != self.dtype:
            raise TypeError(
                f"ExchangeSchedule built for {self.dtype} applied to "
                f"{x.dtype} — schedules are cached per (mesh, depth, "
                "dtype); take the right one from persistent_exchange()"
            )
        for dim, name, nper, per, perms in self.plan:
            x = _exchange_axis(x, name, nper, dim, per, self.depth, perms)
        return x

    def strip_shapes(self, owned_extents) -> list[tuple[int, ...]]:
        """The per-axis message shapes of this schedule over a block with
        the given owned extents (see halo_strip_shapes)."""
        return halo_strip_shapes(owned_extents, self.depth)


_SCHEDULE_CACHE: dict = {}


def _mesh_key(comm: CartComm) -> tuple:
    """Hashable identity of a comm's mesh (axis names + dims + device
    ids + the axis->tier map) — stable across jax versions that may or
    may not hash Mesh. The tier map is part of the identity: a re-tiered
    mesh orders its exchange plan differently, so neither a cached
    schedule nor a cached `.exchange`-span probe may be served across a
    tier change (the stale-probe bug class)."""
    return (tuple(comm.axis_names), tuple(comm.dims),
            tuple(d.id for d in comm.mesh.devices.flat),
            tuple(sorted(comm.tiers.items())))


def persistent_exchange(comm: CartComm, depth: int = 1, dtype=None,
                        periodic=()) -> ExchangeSchedule:
    """The cached `ExchangeSchedule` for (mesh incl. tier map,
    halo-depth, dtype, periodic) — built once per process, returned by
    identity afterwards (test-pinned). Callers that exchange the same
    class of block many times (the overlapped solvers, the exchange
    probe) hold one schedule instead of re-deriving the plan per trace
    site."""
    key = (_mesh_key(comm), int(depth),
           None if dtype is None else jnp.dtype(dtype).name,
           tuple(sorted(periodic)))
    sched = _SCHEDULE_CACHE.get(key)
    if sched is None:
        sched = ExchangeSchedule(comm, depth, dtype, periodic)
        _SCHEDULE_CACHE[key] = sched
    return sched


def halo_strip_shapes(extents, depth: int = 1) -> list[tuple[int, ...]]:
    """Per-axis ppermute message shapes of ONE full `halo_exchange` over an
    extended block with the given OWNED extents: along each exchanged axis
    the two travelling strips are `depth` ghost layers wide and span the
    full EXTENDED extent of every other axis (ghost corners included —
    that is what makes the axis-by-axis exchange corner-consistent). This
    is the one statement of the exchange's message geometry: the byte
    accounting below, the PR 3 telemetry records, and the commcheck trace
    census (analysis/commcheck.py) all derive from it, so the accountings
    cannot diverge."""
    ext = [e + 2 * depth for e in extents]
    return [
        tuple(depth if a == ax else ext[a] for a in range(len(ext)))
        for ax in range(len(extents))
    ]


def halo_exchange_bytes(extents, depth: int, itemsize: int) -> int:
    """Static per-shard bytes one full `halo_exchange` moves: two strips
    (one per direction) of every `halo_strip_shapes` message. THE shared
    byte accounting — solver-__init__ telemetry `halo` records
    (models/ns*_dist.py) and the commcheck contract pass both call this
    helper rather than re-deriving."""
    total = 0
    for shape in halo_strip_shapes(extents, depth):
        n = 1
        for s in shape:
            n *= s
        total += 2 * n
    return total * itemsize


def halo_tier_bytes(comm: CartComm, extents, depth: int,
                    itemsize: int) -> dict:
    """Per-TIER bytes of one full `halo_exchange` over a block with the
    given OWNED extents: each axis's two travelling strips charged to
    that axis's interconnect tier (`tpu_mesh_tiers`). Axes of size 1
    move nothing and charge nothing — this is the traffic accounting,
    not the static geometry. The single-tier default puts everything
    under "ici", so the per-tier sum equals the moved subset of
    `halo_exchange_bytes` by construction."""
    out: dict[str, int] = {t: 0 for t in sorted(set(comm.tiers.values()))}
    for ax, shape in enumerate(halo_strip_shapes(extents, depth)):
        name = comm.axis_names[ax]
        if comm.axis_size(name) == 1:
            continue
        n = 1
        for s in shape:
            n *= s
        out[comm.tiers[name]] += 2 * n * itemsize
    return out


def exchange_schedule_tier_bytes(comm: CartComm, record: dict) -> dict:
    """Per-tier twin of `exchange_schedule_bytes`: the per-step bytes of
    a solver's declared step-level schedule broken out by interconnect
    tier. The `dcn` entry is the first-class BENCH metric
    (`dcn_exchange_bytes`) — the slow-fabric traffic a multi-slice pod
    pays per step. Priced through the same strip helpers as the flat
    total, but counting only strips that MOVE (size-1 mesh axes charge
    nothing — see `halo_tier_bytes`), so on a partially-partitioned
    mesh the per-tier sum is the moved subset of
    `exchange_schedule_bytes`, not its full static geometry."""
    import numpy as np

    shard = tuple(record["shard"])
    isz = np.dtype(record["dtype"]).itemsize
    per = record.get("exchanges_per_step", {})
    out: dict[str, int] = {t: 0 for t in sorted(set(comm.tiers.values()))}

    def add(bytes_by_tier, times):
        for t, b in bytes_by_tier.items():
            out[t] += times * b

    add(halo_tier_bytes(comm, shard, 1, isz), per.get("depth1", 0))
    if "deep" in per:
        # per-tier depth map (ISSUE 17): mapped axes capture ONE
        # depth-H strip pair per `depth_block` steps (amortized, like
        # the flat accounting below); unmapped axes keep the per-step
        # deep strip. Empty map reduces to the historical flat add.
        depths = record.get("exchange_depths") or {}
        blk = max(int(record.get("depth_block", 1)), 1)
        epb = record.get("exchanges_per_block", {}).get(
            "deep", per["deep"])
        for ax, shape in enumerate(
                halo_strip_shapes(shard, record["deep_halo"])):
            name = comm.axis_names[ax]
            if comm.axis_size(name) == 1:
                continue
            if name in depths:
                cap = halo_strip_shapes(shard, depths[name])[ax]
                n = 1
                for s in cap:
                    n *= s
                out[comm.tiers[name]] += int(round(
                    epb * 2 * n * isz / blk))
            else:
                n = 1
                for s in shape:
                    n *= s
                out[comm.tiers[name]] += per["deep"] * 2 * n * isz
    if per.get("shift"):
        # one single-direction depth-1 strip per shifted axis
        per_axis = per["shift"] // len(shard)
        for ax, shape in enumerate(halo_strip_shapes(shard, 1)):
            name = comm.axis_names[ax]
            if comm.axis_size(name) == 1:
                continue
            n = 1
            for s in shape:
                n *= s
            out[comm.tiers[name]] += per_axis * n * isz
    return out


def halo_shift(x, comm: CartComm, axis: str):
    """commShift (comm.c:196-244): one-directional staggered exchange — fill
    the LOW ghost strip along `axis` from the minus-neighbour's high interior
    edge (the donor edge of staggered fluxes F/G/H). The plus-most shard's
    physical ghost is untouched."""
    dim = comm.axis_names.index(axis)
    nper = comm.axis_size(axis)
    if nper == 1:
        return x
    n = x.shape[dim]
    strip = tuple(1 if a == dim else x.shape[a] for a in range(x.ndim))
    with _scope("halo_shift", axis, strip, x.dtype):
        hi_edge = lax.slice_in_dim(x, n - 2, n - 1, axis=dim)
        from_lo = lax.ppermute(hi_edge, axis, _nbr_perm(nper, True, False))
        idx = lax.axis_index(axis)
        old_lo = lax.slice_in_dim(x, 0, 1, axis=dim)
        from_lo = jnp.where(idx > 0, from_lo, old_lo)
        return lax.dynamic_update_slice_in_dim(x, from_lo, 0, axis=dim)


def exchange_schedule_bytes(record: dict) -> int:
    """Per-step bytes of a solver's declared step-level exchange schedule
    (the `_halo_record()` dict): full exchanges at their depths plus the
    one-strip staggered shifts. Priced through `halo_exchange_bytes` /
    `halo_strip_shapes` so this total and the commcheck census cannot
    diverge. Per-STEP only: the overlap path's once-per-chunk prologue
    exchanges (`exchanges_per_chunk`) amortize to ~0 and are excluded,
    like the solve's internal exchanges."""
    import numpy as np

    shard = tuple(record["shard"])
    isz = np.dtype(record["dtype"]).itemsize
    per = record.get("exchanges_per_step", {})
    total = per.get("depth1", 0) * halo_exchange_bytes(shard, 1, isz)
    if "deep" in per:
        # per-tier depth map (ISSUE 17): mapped axes amortize ONE
        # depth-H capture pair over `depth_block` steps; unmapped axes
        # keep the per-step deep strip. Static geometry like the rest
        # of this accounting (size-1 axes count); empty map reduces to
        # the historical flat line bit-for-bit.
        depths = record.get("exchange_depths") or {}
        if not depths:
            total += per["deep"] * halo_exchange_bytes(
                shard, record["deep_halo"], isz)
        else:
            blk = max(int(record.get("depth_block", 1)), 1)
            epb = record.get("exchanges_per_block", {}).get(
                "deep", per["deep"])
            axes = record.get("axes") or [str(a) for a in range(len(shard))]
            for ax, shape in enumerate(
                    halo_strip_shapes(shard, record["deep_halo"])):
                if axes[ax] in depths:
                    cap = halo_strip_shapes(shard, depths[axes[ax]])[ax]
                    total += int(round(
                        epb * 2 * int(np.prod(cap)) * isz / blk))
                else:
                    total += per["deep"] * 2 * int(np.prod(shape)) * isz
    if per.get("shift"):
        # one shift per axis (F/G/H donor edges): a single depth-1 strip,
        # one direction
        per_axis = per["shift"] // len(shard)
        total += sum(per_axis * int(np.prod(s)) * isz
                     for s in halo_strip_shapes(shard, 1))
    return total


_PROBE_CACHE: dict = {}


def make_exchange_probe(comm: CartComm, record: dict):
    """Jitted exchange-only program of a solver's declared step-level
    schedule (`_halo_record()`): the SERIAL cost of one step's halo
    traffic with nothing overlapping it — the `exchange` span's
    critical-path number (ROADMAP item 2: the comm/compute-overlap
    refactor is judged by how much of this time it hides). The exchanges
    chain through one carried block per depth class so XLA cannot
    reorder or elide them. Returns (fn, args).

    Cached per (mesh, record geometry, dtype) — the first consumer of
    the persistent-schedule layer: repeated `time_exchange_ms` spans
    (every dist run's epilogue, every `dist_step_decomposition`) reuse
    one compiled probe instead of recompiling per call (identity
    test-pinned). The deep exchange routes through the cached
    `persistent_exchange` schedule; the per-step schedule it prices is
    unchanged by the overlap refactor (`exchanges_per_chunk` prologue
    exchanges are amortized over the chunk and deliberately excluded,
    like the solve's internal exchanges)."""
    per = record.get("exchanges_per_step", {})
    shard = tuple(int(s) for s in record["shard"])
    dtype = jnp.dtype(record["dtype"])
    H = int(record.get("deep_halo", 1))
    key = (_mesh_key(comm), shard, dtype.name, H,
           tuple(sorted((k, int(v)) for k, v in per.items())))
    fn = _PROBE_CACHE.get(key)
    if fn is None:
        names = comm.axis_names
        deep_sched = persistent_exchange(comm, H, dtype)

        def body(x1, xd):
            for _ in range(int(per.get("depth1", 0))):
                x1 = halo_exchange(x1, comm)
            for k in range(int(per.get("shift", 0))):
                x1 = halo_shift(x1, comm, names[k % len(names)])
            for _ in range(int(per.get("deep", 0))):
                xd = deep_sched(xd)
            return x1, xd

        spec = comm.spec()
        fn = jax.jit(comm.shard_map(body, in_specs=(spec, spec),
                                    out_specs=(spec, spec)))
        _PROBE_CACHE[key] = fn
    # only the jitted program is cached (the recompile was the cost);
    # the zero-filled argument blocks are rebuilt per call so the cache
    # never pins two full-grid device buffers for the process lifetime
    sh = comm.sharding()
    x1 = jax.device_put(
        jnp.zeros(tuple(p * (s + 2) for p, s in zip(comm.dims, shard)),
                  dtype), sh)
    xd = jax.device_put(
        jnp.zeros(tuple(p * (s + 2 * H) for p, s in zip(comm.dims, shard)),
                  dtype), sh)
    return fn, (x1, xd)


def time_exchange_ms(comm: CartComm, record: dict, reps: int = 3) -> float:
    """Best-of-reps wall time of ONE serial pass of the declared exchange
    schedule, in ms (compile + one warm dispatch excluded). Off-TPU the
    number is trend-only, like every other wall measurement here."""
    import time as _time

    fn, args = make_exchange_probe(comm, record)
    jax.block_until_ready(fn(*args))  # compile + warm
    best = float("inf")
    for _ in range(max(1, reps)):
        t0 = _time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, _time.perf_counter() - t0)
    return best * 1e3


def reduction(val, comm: CartComm, op: str = "sum"):
    """commReduction (comm.c:158-167): global MAX/SUM across the whole mesh."""
    axes = tuple(comm.axis_names)
    if op == "sum":
        return lax.psum(val, axes)
    if op == "max":
        return lax.pmax(val, axes)
    raise ValueError(f"unknown reduction op {op!r}")
