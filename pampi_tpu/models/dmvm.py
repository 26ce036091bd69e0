"""Distributed dense matrix-vector multiply with ring rotation of x —
the assignment-3a/3b capability, TPU-native.

Reference structure (/root/reference/assignment-3a/src/main.c): A row-block
scattered (:52), x broadcast (:54), then per rotation a local GEMV (:70-74)
followed by a ring shift of x to the next rank (`MPI_Sendrecv_replace` to
lowerNeighbor/from upperNeighbor, :77); MFLOP/s = 2·N²·iter/walltime/1e6
(:93-95). Assignment-3b is the same with `MPI_Isend/Irecv` posted around the
GEMV for communication/computation overlap (main.c:71-83).

TPU-native design — a ring-allgather matvec (the collective-matmul skeleton):
- A is row-sharded over a 1-D "r" mesh axis; x is BLOCK-sharded (each device
  holds N/R entries), not replicated.
- Each rotation multiplies the resident x block against the matching column
  block of the local A rows (`dynamic_slice`), then `ppermute`s the x block
  to rank+1 — the exact communication skeleton of the reference's ring, and
  of ring attention (SURVEY.md §5 long-context analog).
- After R rotations y_local = A_local · x exactly. DOCUMENTED DEVIATION: the
  shipped reference keeps a REPLICATED x and multiplies the full vector every
  rotation (main.c:70-74), doing R× redundant flops and computing R·A·x
  (and reading uninitialised x on rank 0 — the quirk list in SURVEY.md §7);
  we implement the blocked semantics the exercise is built around, so y=A·x.
- Overlap (the 3b exercise) comes from XLA's latency-hiding scheduler: the
  ppermute of the x block is independent of the GEMV's output, so with
  `overlap=True` the carry is double-buffered and XLA can overlap the
  collective with the matmul; the reference needed hand-rolled Isend/Irecv
  (with a latent overlap race, main.c:71-80 — impossible here by
  construction: ppermute is functional).

Init parity: a[i,j] = i+j, x[i] = i (main.c:45-50).

Kernel choice (measured, v5e): XLA's own gemv streams A at ~260-380 GB/s
at 8192² f32; hand-written Pallas alternatives (VPU lane-reduce over
(rows, cols) blocks, and an MXU dot_general accumulating over column
blocks) measured 0.5-0.75× that in the same session windows. The jnp
matmul IS the right TPU kernel here — the framework keeps it and spends
Pallas effort where it wins (the stencil kernels).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils import flags as _flags
from ..utils.precision import resolve_dtype
from ..utils.timing import get_timestamp


def _print_sum(s):
    import sys

    print("Sum: %f" % float(s), file=sys.stderr)  # lint: allow(print-call) — -DCHECK stderr parity (A3a dmvm.c:26-36)


def _fence(y) -> None:
    """Force device completion via a host readback of one LOCAL element —
    y[0] itself may live on another process under a multi-process launch."""
    _ = np.asarray(y.addressable_data(0)).ravel()[0]


def init_ax(N: int, dtype):
    """a[i,j] = i+j, x[i] = i (assignment-3a/src/main.c:45-50)."""
    i = np.arange(N, dtype=np.float64)
    a = i[:, None] + i[None, :]
    return jnp.asarray(a, dtype), jnp.asarray(i, dtype)


class SequentialDMVM:
    """Single-device timed y += A·x loop (≙ assignment-3a/src/dmvm.c:11-41)."""

    def __init__(self, N: int, dtype=None):
        self.N = N
        self.dtype = dtype or resolve_dtype("float32")
        self.a, self.x = init_ax(N, self.dtype)

        check = _flags.check()

        @jax.jit
        def run(a, x, iters):
            def body(_, y):
                # tie x to the carry with an exact no-op the compiler cannot
                # fold (0·y[0] is only provably 0 for finite y), so the
                # loop-invariant A·x cannot be hoisted out of the timed loop
                xdep = x * (1.0 + 0.0 * y[0])
                y = y + a @ xdep
                if check:
                    # ≙ -DCHECK (dmvm.c:26-36): print the running sum of y
                    # to stderr and zero y each iteration
                    jax.debug.callback(_print_sum, jnp.sum(y))
                    y = jnp.zeros_like(y)
                return y

            return lax.fori_loop(0, iters, body, jnp.zeros((N,), self.dtype))

        self._run = run

    def run(self, iters: int):
        """Timed single-dispatch loop; completion is forced by a host
        readback of one element."""
        # warm-up compiles the loop but executes ZERO iterations (iters is a
        # traced operand), so CHECK mode prints exactly `iters` Sum lines,
        # matching the reference's count
        y = self._run(self.a, self.x, 0)
        _ = float(y[0])
        t0 = get_timestamp()
        y = self._run(self.a, self.x, iters)
        _ = float(y[0])
        walltime = get_timestamp() - t0
        if _flags.check():
            # debug callbacks are async; drain them before returning so no
            # Sum line can be lost at process exit (and counts are exact)
            jax.effects_barrier()
        return y, walltime


class RingDMVM:
    """R-device ring matvec over a 1-D mesh (≙ assignment-3a/3b main loop)."""

    def __init__(
        self, N: int, devices=None, dtype=None, overlap: bool = True
    ):
        devs = devices if devices is not None else jax.devices()
        R = len(devs)
        if N % R:
            raise ValueError(f"N={N} not divisible by ring size {R}")
        self.N, self.R = N, R
        self.Nl = N // R  # rows per device
        self.Nb = N // R  # x block entries per device
        self.dtype = dtype or resolve_dtype("float32")
        self.mesh = Mesh(np.asarray(devs), ("r",))
        self.overlap = overlap
        a, x = init_ax(N, self.dtype)
        self.a = jax.device_put(a, NamedSharding(self.mesh, P("r", None)))
        self.x = jax.device_put(x, NamedSharding(self.mesh, P("r")))
        self._pass = jax.jit(self._build())

    def _build(self):
        R, Nl, Nb = self.R, self.Nl, self.Nb
        dtype = self.dtype
        perm = [(i, (i + 1) % R) for i in range(R)]
        overlap = self.overlap

        def kernel(a_local, x_blk, iters):
            r = lax.axis_index("r")

            def rot_body(rot, carry):
                y, xb = carry
                blk = (r - rot) % R
                start = (blk * Nb).astype(jnp.int32)
                cols = lax.dynamic_slice(
                    a_local, (jnp.asarray(0, jnp.int32), start), (Nl, Nb)
                )
                if overlap:
                    # double-buffer: the shift is independent of the GEMV, so
                    # XLA overlaps the collective with the compute (the 3b
                    # exercise, race-free)
                    xb_next = lax.ppermute(xb, "r", perm)
                    y = y + cols @ xb
                    xb = xb_next
                else:
                    y = y + cols @ xb
                    xb = lax.ppermute(xb, "r", perm)
                return y, xb

            def iter_body(_, carry):
                y, xb = carry
                # tie the x block to the carry (see SequentialDMVM) so the
                # per-iteration ring pass cannot be hoisted
                xb = xb * (1.0 + 0.0 * y[0])
                return lax.fori_loop(0, R, rot_body, (y, xb))

            y0 = jnp.zeros((Nl,), dtype)
            if hasattr(lax, "pcast"):  # newer jax: mark the accumulator
                y0 = lax.pcast(y0, ("r",), to="varying")  # mesh-varying
            y, _ = lax.fori_loop(0, iters, iter_body, (y0, x_blk))
            return y

        from ..parallel.comm import compat_shard_map

        return compat_shard_map(
            kernel,
            mesh=self.mesh,
            in_specs=(P("r", None), P("r"), None),
            out_specs=P("r"),
        )

    def run(self, iters: int):
        """Timed single-dispatch run; returns (y global, walltime, MFLOP/s).
        Completion forced by host readback (see SequentialDMVM.run).
        MFLOP/s = 2·N²·iter/walltime/1e6 (main.c:93-95) — for the blocked
        ring this counts exactly the executed flops."""
        y = self._pass(self.a, self.x, 1)
        _fence(y)  # warm-up/compile
        t0 = get_timestamp()
        y = self._pass(self.a, self.x, iters)
        _fence(y)
        walltime = get_timestamp() - t0
        mflops = 1.0e-6 * 2.0 * self.N * self.N * iters / walltime
        return y, walltime, mflops


def main(argv) -> int:
    """CLI parity: `<prog> <N> <iter>` prints `iter N MFlops walltime`
    (assignment-3a/src/main.c:25-34, 93-95) and appends a bench-harness CSV
    row `Ranks,NITER,N,MFlops,Time` (bash scripts/bench-node.sh:25)."""
    if len(argv) < 3:
        print(f"Usage: {argv[0]} <N> <iter>")  # lint: allow(print-call) — CLI usage line (reference main.c parity)
        return 0
    N, iters = int(argv[1]), int(argv[2])
    ndev = len(jax.devices())
    if ndev > 1 and N % ndev == 0:
        ring = RingDMVM(N)
        y, walltime, mflops = ring.run(iters)
        ranks = ring.R
    else:
        if ndev > 1:
            import sys as _sys

            print(  # lint: allow(print-call) — pre-run CLI warning (stderr)
                f"warning: N={N} not divisible by {ndev} devices; "
                "running single-device",
                file=_sys.stderr,
            )
        seq = SequentialDMVM(N)
        y, walltime = seq.run(iters)
        mflops = 1.0e-6 * 2.0 * N * N * iters / walltime
        ranks = 1
    print("%d %d %.2f %.2f" % (iters, N, mflops, walltime))  # lint: allow(print-call) — the bench headline the harness greps (A3a main.c:93-95)
    from ..parallel import multihost

    # read per RUN through the registered accessor (utils/flags.py) — the
    # bench harness exports PAMPI_CSV between dmvm invocations of one
    # process, so an import-time or first-call cache would miss it
    csv_path = _flags.env("PAMPI_CSV",
                          doc="dmvm bench CSV append path (rank 0 only)")
    if csv_path and multihost.is_master():
        # one CSV row per RUN, not per process (rank-0 convention)
        with open(csv_path, "a") as fh:
            fh.write("%d,%d,%d,%.2f,%.2f\n" % (ranks, iters, N, mflops, walltime))
    return 0
