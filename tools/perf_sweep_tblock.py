"""Sweep n_inner (temporal blocking depth) x block_rows for the tblock
kernel on the real chip. Total RB iterations fixed so throughput numbers
compare directly with bench.py."""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax

from pampi_tpu.models.poisson import init_fields
from pampi_tpu.ops import sor_pallas as sp
from pampi_tpu.utils.params import Parameter

N = int(os.environ.get("SWEEP_N", 4096))
# total RB iterations per timed run (pick divisible by all k swept; raise it
# when the per-dispatch latency floor is high — the loop is ONE
# dispatch, so iterations amortize the floor)
TOTAL = int(os.environ.get("SWEEP_TOTAL", 120))
KS = tuple(int(x) for x in os.environ.get("SWEEP_K", "3,4,5,6").split(","))
BRS = tuple(int(x) for x in os.environ.get("SWEEP_BR", "256").split(","))


def timeit(fn, *args):
    out = fn(*args)
    float(jax.tree.leaves(out)[-1].ravel()[0])
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        out = fn(*args)
        float(jax.tree.leaves(out)[-1].ravel()[0])
        best = min(best, time.perf_counter() - t0)
    return best


def main():
    param = Parameter(imax=N, jmax=N, tpu_dtype="float32")
    p, rhs = init_fields(param, problem=2, dtype=jnp.float32)

    for k in KS:
        for br in BRS:
            try:
                rb, brr, h = sp.make_rb_iter_tblock(
                    N, N, 1.0 / N, 1.0 / N, 1.9, jnp.float32,
                    n_inner=k, block_rows=br,
                )
                pp = sp.pad_array(p, brr, h)
                rr = sp.pad_array(rhs, brr, h)

                @jax.jit
                def loop(p, rhs):
                    def body(_, c):
                        p, _ = c
                        return rb(p, rhs)
                    return lax.fori_loop(0, TOTAL // k, body,
                                         (p, jnp.float32(0)))

                t = timeit(loop, pp, rr)
                ups = N * N * TOTAL / t
                print(f"k={k:2d} br={br:4d} {t*1e3/TOTAL:7.3f}ms/it "
                      f"ups={ups/1e9:6.2f}e9  vs_base={ups/1.32e9:5.1f}x")
            except Exception as e:
                print(f"k={k:2d} br={br:4d} FAILED {type(e).__name__}: "
                      f"{str(e)[:120]}")


if __name__ == "__main__":
    main()
