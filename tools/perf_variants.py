"""Bound the compute cost of kernel components: time tblock k=4 br=256 as-is
vs with BC refresh removed vs with red-sweep only (halved stencil work).
Throwaway measurement harness — numerics of the stripped variants are WRONG
(no BC), only timings matter."""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pampi_tpu.models.poisson import init_fields
from pampi_tpu.ops import sor_pallas as sp
from pampi_tpu.utils.params import Parameter

N = int(os.environ.get("VAR_N", 4096))
TOTAL = int(os.environ.get("VAR_TOTAL", 96))  # one dispatch; raise to
# amortize a high per-dispatch latency floor
K = int(os.environ.get("VAR_K", 4))
BR = int(os.environ.get("VAR_BR", 256))


def make_variant(no_bc=False, red_only=False, no_res=False, inc_black=False,
                 bc_cond=False):
    dtype = jnp.float32
    h = sp.tblock_halo(K, dtype)
    wp = sp.padded_width(N)
    width = N + 2
    nblocks = -(-(N + 2) // BR)
    rp = nblocks * BR + 2 * h
    dx2 = (1.0 / N) ** 2
    factor = 1.9 * 0.5 * (dx2 * dx2) / (dx2 + dx2)
    idx2 = 1.0 / dx2

    def kernel(p_in, rhs, p_out, res, pw2, rw2, ob2, ld_sem, st_sem):
        b = pl.program_id(0)
        slot = b % 2
        nslot = (b + 1) % 2

        def load(k, s):
            return (
                pltpu.make_async_copy(
                    p_in.at[pl.ds(k * BR, BR + 2 * h), :], pw2.at[s],
                    ld_sem.at[s, 0]),
                pltpu.make_async_copy(
                    rhs.at[pl.ds(k * BR, BR + 2 * h), :], rw2.at[s],
                    ld_sem.at[s, 1]),
            )

        def store(k, s):
            return pltpu.make_async_copy(
                ob2.at[s], p_out.at[pl.ds(h + k * BR, BR), :], st_sem.at[s])

        @pl.when(b == 0)
        def _():
            res[0, 0] = jnp.zeros((), jnp.float32)
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

        def lap(x):
            e = jnp.roll(x, -1, axis=1)
            w = jnp.roll(x, 1, axis=1)
            n = jnp.roll(x, -1, axis=0)
            s = jnp.roll(x, 1, axis=0)
            return (e - 2.0 * x + w) * idx2 + (n - 2.0 * x + s) * idx2

        jj = b * BR - h + jax.lax.broadcasted_iota(jnp.int32, p.shape, 0)
        ii = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
        interior = (jj >= 1) & (jj <= N) & (ii >= 1) & (ii <= width - 2)
        red = interior & (((ii + jj) % 2) == 0)
        black = interior & (((ii + jj) % 2) == 1)
        rgl = (jj == 0) & (ii >= 1) & (ii <= width - 2)
        rgh = (jj == N + 1) & (ii >= 1) & (ii <= width - 2)
        rint = (jj >= 1) & (jj <= N)
        cgl = (ii == 0) & rint
        cgh = (ii == width - 1) & rint

        r_red = r_blk = jnp.zeros_like(p)
        for t in range(K):
            if inc_black:
                # one lap; black residual reconstructed from the red deltas
                # (linear stencil: r_blk = r_all + factor*stencil(r_red))
                r_all = rw - lap(p)
                r_red = jnp.where(red, r_all, 0.0)
                p = p - factor * r_red
                corr = (
                    jnp.roll(r_red, -1, 1) + jnp.roll(r_red, 1, 1)
                    + jnp.roll(r_red, -1, 0) + jnp.roll(r_red, 1, 0)
                ) * idx2
                r_blk = jnp.where(black, r_all + factor * corr, 0.0)
                p = p - factor * r_blk
            else:
                r_red = jnp.where(red, rw - lap(p), 0.0)
                p = p - factor * r_red
                if not red_only:
                    r_blk = jnp.where(black, rw - lap(p), 0.0)
                    p = p - factor * r_blk
            if not no_bc:
                if bc_cond:
                    # row-ghost refresh only in the blocks that contain a
                    # ghost row (first/last) — scf.if at runtime
                    p = jax.lax.cond(
                        b == 0,
                        lambda q: jnp.where(rgl, jnp.roll(q, -1, axis=0), q),
                        lambda q: q, p)
                    p = jax.lax.cond(
                        b == nblocks - 1,
                        lambda q: jnp.where(rgh, jnp.roll(q, 1, axis=0), q),
                        lambda q: q, p)
                else:
                    p = jnp.where(rgl, jnp.roll(p, -1, axis=0), p)
                    p = jnp.where(rgh, jnp.roll(p, 1, axis=0), p)
                p = jnp.where(cgl, jnp.roll(p, -1, axis=1), p)
                p = jnp.where(cgh, jnp.roll(p, 1, axis=1), p)

        @pl.when(b >= 2)
        def _():
            store(b - 2, slot).wait()

        ob2[slot] = p[h:h + BR, :]
        store(b, slot).start()

        if not no_res:
            ro = r_red[h:h + BR, :]
            bo = r_blk[h:h + BR, :]
            res[0, 0] += jnp.sum(ro * ro) + jnp.sum(bo * bo)

        @pl.when(b == nblocks - 1)
        def _():
            store(b, slot).wait()
            if nblocks > 1:
                store(b - 1, nslot).wait()

    call = pl.pallas_call(
        kernel,
        grid=(nblocks,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, 1), lambda b: (0, 0), memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rp, wp), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, BR + 2 * h, wp), jnp.float32),
            pltpu.VMEM((2, BR + 2 * h, wp), jnp.float32),
            pltpu.VMEM((2, BR, wp), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=100 << 20),
    )
    return call, h


def timeit(callable_, p, rhs):
    @jax.jit
    def loop(p, rhs):
        def body(_, c):
            pp, _ = c
            pp, r = callable_(pp, rhs)
            return pp, r[0, 0]
        return lax.fori_loop(0, TOTAL // K, body, (p, jnp.float32(0)))

    out = loop(p, rhs)
    float(out[1])
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        out = loop(p, rhs)
        float(out[1])
        best = min(best, time.perf_counter() - t0)
    return best


def main():
    param = Parameter(imax=N, jmax=N, tpu_dtype="float32")
    p, rhs = init_fields(param, problem=2, dtype=jnp.float32)
    for label, kw in [
        ("full        ", {}),
        ("no-bc       ", dict(no_bc=True)),
        ("no-res      ", dict(no_res=True)),
        ("red-only    ", dict(red_only=True)),
        ("red+nobc    ", dict(red_only=True, no_bc=True)),
        ("inc-black   ", dict(inc_black=True)),
        ("bc-cond     ", dict(bc_cond=True)),
        ("inc+cond    ", dict(inc_black=True, bc_cond=True)),
    ]:
        call, h = make_variant(**kw)
        pp = sp.pad_array(p, BR, h)
        rr = sp.pad_array(rhs, BR, h)
        t = timeit(call, pp, rr)
        print(f"{label} {t*1e3/TOTAL:7.3f} ms/it "
              f"ups={N*N*TOTAL/t/1e9:6.2f}e9")


if __name__ == "__main__":
    main()
